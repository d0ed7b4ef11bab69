"""Reader kind ``span_cpu_share``: of the wall time a span's name took in
the window, the share (%) its threads spent on the core: 100 × Σ window
samples of ``vmt_span_cpu_ms{name=span}`` / Σ window samples of
``vmt_span_ms{name=span}`` over every task. The rest is time off the core:
the interpreter lock, another lock, the kernel or the device. Params:
``span``. Returns None where either histogram holds no sample of the span
(a program whose spans read no CPU clock)."""

from __future__ import annotations


def _window_sum(ctx, instrument: str, span: str):
    series = ctx["histograms"].get(instrument, {})
    values = [v for labels, samples in series.items()
              if labels and labels[0] == span for v in samples]
    return sum(values) if values else None


def read(ctx, span: str):
    cpu = _window_sum(ctx, "vmt_span_cpu_ms", span)
    wall = _window_sum(ctx, "vmt_span_ms", span)
    if cpu is None or not wall:
        return None
    return 100.0 * cpu / wall
