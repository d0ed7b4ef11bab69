"""The reduction from a trace to numbers, on a small recorded trace
(``data/trace_small.json``: device operations and executables of a few
forwards on the v5e, with the program's spans over the same stretch) and on
a hand-made one whose answers are known by inspection."""

import json
import os

import numpy as np
import pytest

from benchmark.reduce import readers, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_made():
    ops = [("a", 1.0, 1.0), ("b", 1.5, 1.0), ("a", 4.0, 0.5),
           ("c", 4.5, 0.00002), ("a", 4.50004, 0.1)]
    spans = [("outer", 0.0, 10.0), ("inner", 2.4, 1.0), ("late", 7.0, 1.0)]
    return ops, spans


def test_busy_is_the_union_and_gaps_are_what_is_left():
    ops, _ = hand_made()
    busy, gaps = trace.busy_and_gaps(ops, 0.0, 6.0)
    assert busy == pytest.approx(1.5 + 0.5 + 0.00002 + 0.1)
    assert sum(d for _, d in gaps) == pytest.approx(6.0 - busy)
    assert [round(s, 5) for s, _ in gaps] == [0.0, 2.5, 4.50002, 4.60004]


def test_a_window_cuts_the_events_that_straddle_it():
    ops, _ = hand_made()
    busy, gaps = trace.busy_and_gaps(ops, 1.25, 2.0)
    assert busy == pytest.approx(0.75) and gaps == []
    assert trace.top_events(ops, 1.25, 2.0) == [["a", 0.75], ["b", 0.5]]


def test_idle_is_cut_at_span_borders_and_goes_to_the_innermost_span():
    ops, spans = hand_made()
    _, gaps = trace.busy_and_gaps(ops, 0.0, 9.0)
    idle = dict(trace.attribute_gaps(gaps, spans))
    assert idle["between_operations"] == pytest.approx(0.00002)
    assert idle["inner"] == pytest.approx(0.9)         # 2.5 .. 3.4
    assert idle["late"] == pytest.approx(1.0)          # 7 .. 8
    # 0 .. 1, 3.4 .. 4.0, 4.60004 .. 7, 8 .. 9
    assert idle["outer"] == pytest.approx(1.0 + 0.6 + 2.39996 + 1.0)
    assert sum(idle.values()) == pytest.approx(sum(d for _, d in gaps))
    # A gap that a span only reaches into: the rest is nobody's.
    assert dict(trace.attribute_gaps([(9.5, 1.5), (20.0, 1.0)], spans)) == {
        "outer": pytest.approx(0.5), "no_span": pytest.approx(2.0)}


def test_clock_offset_joins_the_two_clocks():
    assert trace.clock_offset({"start": 105.0, "end": 111.0},
                              {"start": 5.0, "end": 11.0}) == 100.0
    with pytest.raises(ValueError):
        trace.clock_offset({"start": 1.0}, {"end": 2.0})


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_recorded_trace_against_a_one_microsecond_grid(recorded):
    a, b = recorded["window"]
    ops = [tuple(e) for e in recorded["ops"]]
    busy, gaps = trace.busy_and_gaps(ops, a, b)
    grid = np.zeros(int(round((b - a) * 1e6)) + 1, bool)
    for _, start, dur in ops:
        lo = int(round((max(start, a) - a) * 1e6))
        hi = int(round((min(start + dur, b) - a) * 1e6))
        if hi > lo:
            grid[lo:hi] = True
    assert busy == pytest.approx(grid.sum() * 1e-6, abs=len(ops) * 1e-6)
    assert busy + sum(d for _, d in gaps) == pytest.approx(b - a)
    top = trace.top_events(ops, a, b, n=3)
    for name, seconds in top:
        direct = sum(min(s + d, b) - max(s, a) for n, s, d in ops
                     if trace.short_name(n) == name
                     and min(s + d, b) > max(s, a))
        assert seconds == pytest.approx(direct)
    assert recorded["expect"]["busy_s"] == pytest.approx(busy, rel=1e-9)
    assert recorded["expect"]["top"] == top[0][0]


def test_readers_on_the_recorded_trace(recorded):
    a, b = recorded["window"]
    ops = [tuple(e) for e in recorded["ops"]]
    modules = [tuple(e) for e in recorded["modules"]]
    busy, _ = trace.busy_and_gaps(ops, a, b)
    ctx = {"trace": {"ops": trace.clip(ops, a, b),
                     "modules": trace.clip(modules, a, b),
                     "busy_s": busy, "window_s": b - a},
           "units_in_trace": 10.0, "flops_per_unit": 1e9,
           "peaks": {"bf16_flops_per_s": 197e12}}
    idle = readers.read({"kind": "trace_idle"}, ctx)
    assert idle == pytest.approx(100 * (1 - busy / (b - a)))
    ms = readers.read({"kind": "trace_module_ms",
                       "params": {"module_contains": "jit_fwd"}}, ctx)
    fwd = [d for n, _, d in ctx["trace"]["modules"] if "jit_fwd" in n]
    assert ms == pytest.approx(1e3 * sum(fwd) / len(fwd))
    mfu = readers.read({"kind": "trace_mfu", "params": {
        "module_contains": "jit_fwd", "over": "modules"}}, ctx)
    assert mfu == pytest.approx(100 * 1e10 / (sum(fwd) * 197e12))
    assert 0 < mfu < 100


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = {"spans": [], "histograms": {}, "stamps": [], "setup": {},
             "counters": {"before": {}, "after": {}}, "trace": None}
    for reader in (
            {"kind": "span_percentile",
             "params": {"span": "x", "percentile": 50}},
            {"kind": "histogram_percentile",
             "params": {"instrument": "x", "percentile": 50}},
            {"kind": "histogram_mean", "params": {"instrument": "x"}},
            {"kind": "counter_delta", "params": {"counter": "x"}},
            {"kind": "counter_ratio",
             "params": {"numerator": ["x"], "denominator": ["y"]}},
            {"kind": "stamps", "params": {"what": "late_ms",
                                          "percentile": 95}},
            {"kind": "setup_phase", "params": {"phase": "x"}},
            {"kind": "trace_idle"},
            {"kind": "trace_module_ms", "params": {"module_contains": "x"}},
            {"kind": "trace_mfu", "params": {"module_contains": "x"}},
            {"kind": "counter_rate", "params": {"counter": "x"}}):
        assert readers.read(reader, empty) is None


def test_a_kind_is_a_function_here_or_a_file_brought_beside():
    ctx = {"counters": {"before": {"n": 2.0}, "after": {"n": 12.0}},
           "seconds": 4.0}
    assert "counter_rate" not in readers.KINDS
    assert readers.read({"kind": "counter_rate",
                         "params": {"counter": "n"}}, ctx) == 2.5
    with pytest.raises(SystemExit, match="reduce/kinds/no_such_kind.py"):
        readers.read({"kind": "no_such_kind"}, ctx)


def test_percentile_and_counters():
    assert readers.percentile([1, 2, 3, 4], 50) == 2.5
    assert readers.percentile([], 50) is None
    ctx = {"counters": {"before": {"h": 10.0, "m": 5.0},
                        "after": {"h": 40.0, "m": 15.0}}}
    assert readers.counter_delta(ctx, "h") == 30.0
    assert readers.counter_ratio(ctx, ["h"], ["h", "m"], 100.0) == 75.0
    ctx = {"histograms": {"fill": {("32",): [0.5, 1.0], ("10",): [1.0]}}}
    assert readers.histogram_mean(ctx, "fill", 0) == pytest.approx(58 / 3)
