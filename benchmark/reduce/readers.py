"""Per-layer metric readers, by kind. A metric is a file
``benchmark/metrics/<name>.json`` that names a kind below and its
parameters; the harness hands every reader the same context of what the
traced run recorded. A reader that finds nothing to read returns ``None``
and the metric is left out of the line; none ever returns a made-up 0.

Context keys: ``spans`` [(name, start_s, dur_s)] of the program's tracer in
the window; ``histograms`` {instrument: {label tuple: [samples in window]}};
``counters`` {"before"/"after": {name: value}} of the program's registry
(label sets summed); ``stamps`` the generator's stamps of the window's
requests; ``setup`` {phase: seconds}; ``seconds`` the window's length;
``trace`` {"ops", "modules", "busy_s", "window_s"} of the traced part of
the window, on the device that was busiest; ``units_in_trace`` the units of
work (the family's: image rows, tokens) dispatched in that part;
``flops_per_unit`` the matmul FLOPs of one; ``peaks``.

A kind is a function here (:data:`KINDS`) or a file brought beside this one:
``kinds/<kind>.py`` with ``read(ctx, **params)``.
"""

from __future__ import annotations

import importlib
import os
import re
import statistics

from . import flops


def percentile(values: list, p: float):
    """Linear-interpolated percentile; None on an empty list."""
    if not values:
        return None
    ordered = sorted(values)
    if len(ordered) == 1:
        return float(ordered[0])
    rank = (len(ordered) - 1) * p / 100.0
    lo = int(rank)
    hi = min(lo + 1, len(ordered) - 1)
    return float(ordered[lo] + (ordered[hi] - ordered[lo]) * (rank - lo))


def span_percentile(ctx, span: str, percentile_: float):
    return percentile([d * 1e3 for name, _, d in ctx["spans"]
                       if name == span], percentile_)


def histogram_percentile(ctx, instrument: str, percentile_: float):
    series = ctx["histograms"].get(instrument, {})
    return percentile([v for values in series.values() for v in values],
                      percentile_)


def histogram_mean(ctx, instrument: str, times_label: int = None):
    """Mean of the window's samples, each multiplied by the numeric value of
    its ``times_label``-th label where that is given (a fill share times its
    bucket's rows is rows)."""
    series = ctx["histograms"].get(instrument, {})
    values = [v * (float(labels[times_label])
                   if times_label is not None else 1.0)
              for labels, samples in series.items() for v in samples]
    return statistics.fmean(values) if values else None


def counter_delta(ctx, counter: str):
    before, after = ctx["counters"]["before"], ctx["counters"]["after"]
    if counter not in after:
        return None
    return float(after[counter] - before.get(counter, 0.0))


def counter_ratio(ctx, numerator: list, denominator: list,
                  scale: float = 1.0):
    """scale × Δ(sum of numerator counters) / Δ(sum of denominator ones)."""
    def delta(names):
        parts = [counter_delta(ctx, n) for n in names]
        return None if any(p is None for p in parts) else sum(parts)

    num, den = delta(numerator), delta(denominator)
    if num is None or not den:
        return None
    return scale * num / den


def stamps(ctx, what: str, percentile_: float):
    """``late_ms``: how long after its due time the generator sent a request;
    ``latency_ms``: due time (or send, in a closed loop) to result frame."""
    values = []
    for s in ctx["stamps"]:
        if what == "late_ms" and s.get("due") is not None:
            values.append((s["send"] - s["due"]) * 1e3)
        elif what == "latency_ms" and "recv" in s:
            start = s["due"] if s.get("due") is not None else s["send"]
            values.append((s["recv"] - start) * 1e3)
    return percentile(values, percentile_)


def setup_phase(ctx, phase: str):
    return ctx["setup"].get(phase)


def _module_events(ctx, module_contains: str):
    trace = ctx.get("trace")
    if not trace:
        return []
    return [e for e in trace["modules"] if module_contains in e[0]]


def trace_module_ms(ctx, module_contains: str):
    """Mean device time of one run of the executables so named."""
    events = _module_events(ctx, module_contains)
    if not events:
        return None
    return 1e3 * sum(d for _, _, d in events) / len(events)


def trace_mfu(ctx, module_contains: str, over: str = "modules"):
    """A share of the chip's bf16 peak: matmul FLOPs of the units of work
    dispatched in the traced part, over the peak times either the device
    time of the forward executables there (``over: "modules"``) or the whole
    traced part's length (``over: "window"``, the end-to-end utilization)."""
    trace = ctx.get("trace")
    if not trace or not ctx.get("units_in_trace"):
        return None
    seconds = (trace["window_s"] if over == "window" else
               sum(d for _, _, d in _module_events(ctx, module_contains)))
    if not seconds:
        return None
    flops = ctx["units_in_trace"] * ctx["flops_per_unit"]
    return 100.0 * flops / (seconds * ctx["peaks"]["bf16_flops_per_s"])


def trace_idle(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


SHAPE = re.compile(r"(\w+)\[([\d,]+)\]")


def trace_attention_roofline(ctx, op_prefix: str, real_lengths: list):
    """A blockwise attention kernel's share of its roofline. Each event of
    the kernel names its shapes (``%kernel.1 = bf16[B,H,Nq,D] custom-call(
    q, bf16[B,H,Nk,D] k, v, bias)``, lengths padded to tiles); the least
    time the chip could take for that call is the larger of its FLOPs over
    the bf16 peak and its bytes over the HBM bandwidth (``reduce/flops.py``,
    at the real lengths: those of ``real_lengths`` that pad to the shapes
    seen). The share is the sum of those least times over the sum of the
    events' device times."""
    trace = ctx.get("trace")
    if not trace:
        return None
    least = spent = 0.0
    for name, _, dur in trace["ops"]:
        if not name.startswith(op_prefix):
            continue
        shapes = SHAPE.findall(name)
        if len(shapes) < 3:
            continue
        dtype, out = shapes[0][0], [int(d) for d in shapes[0][1].split(",")]
        keys = [int(d) for d in shapes[2][1].split(",")]
        if len(out) != 4 or len(keys) != 4:
            continue
        rows, heads, nq, depth = out
        real = {-(-n // 8) * 8: n for n in real_lengths}
        cost, moved = flops.attention_kernel_cost(
            rows, real.get(nq, nq), real.get(keys[2], keys[2]), heads, depth,
            2 if dtype in ("bf16", "f16") else 4)
        least += max(cost / ctx["peaks"]["bf16_flops_per_s"],
                     moved / ctx["peaks"]["hbm_bytes_per_s"])
        spent += dur
    return 100.0 * least / spent if spent else None


KINDS = {
    "span_percentile": span_percentile,
    "histogram_percentile": histogram_percentile,
    "histogram_mean": histogram_mean,
    "counter_delta": counter_delta,
    "counter_ratio": counter_ratio,
    "stamps": stamps,
    "setup_phase": setup_phase,
    "trace_module_ms": trace_module_ms,
    "trace_mfu": trace_mfu,
    "trace_idle": trace_idle,
    "trace_attention_roofline": trace_attention_roofline,
}


def find_kind(kind: str):
    """The reader of that kind: one of :data:`KINDS`, else ``read`` of
    ``kinds/<kind>.py`` beside this file; an unknown kind is an error."""
    if kind in KINDS:
        return KINDS[kind]
    here = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kinds")
    if os.path.exists(os.path.join(here, kind + ".py")):
        return importlib.import_module(f".kinds.{kind}", __package__).read
    raise SystemExit(f"metric reader kind {kind!r} is neither one of "
                     f"{sorted(KINDS)} nor a file benchmark/reduce/kinds/"
                     f"{kind}.py")


def read(reader: dict, ctx: dict):
    """Apply one metric file's reader."""
    params = {("percentile_" if k == "percentile" else k): v
              for k, v in reader.get("params", {}).items()}
    return find_kind(reader["kind"])(ctx, **params)
