"""From a profiler trace to numbers: device busy time, the idle gaps and what
the host was doing in them, the operations that took most time.

Two layers. :func:`read_xplane` turns the profiler's ``.xplane.pb`` into
plain lists of ``(name, start_s, dur_s)``; everything else is arithmetic on
such lists and is checked on a small recorded one (``benchmark/tests``).

Clocks: ``run.py`` drops two marks into the trace (``TraceAnnotation``) and
notes ``time.monotonic()`` at each; :func:`clock_offset` is trace time less
monotonic time, so the program's spans (monotonic) and the device's
operations (trace time) land on one axis.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_PREFIX = "bench.mark."
SHORT_GAP_S = 50e-6
OP_TEXT = re.compile(r"^%(\S+) = \(?(\w+\[[\d,]*\])")
LOOK_BACK = 1024  # spans searched behind a gap's end for those reaching into it


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path: str) -> dict:
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "marks":
    {name: start_s}}``, every event ``(name, start_s, dur_s)`` in trace
    time. Needs nothing but JAX."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    marks: dict = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                lines[key] = [(e.name, e.start_ns * 1e-9,
                               e.duration_ns * 1e-9) for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(MARK_PREFIX):
                        marks[e.name[len(MARK_PREFIX):]] = e.start_ns * 1e-9
    return {"devices": devices, "marks": marks}


def clock_offset(marks_trace: dict, marks_monotonic: dict) -> float:
    """Trace time less monotonic time, averaged over the marks both have."""
    shared = [k for k in marks_trace if k in marks_monotonic]
    if not shared:
        raise ValueError("the trace holds none of the run's marks")
    return sum(marks_trace[k] - marks_monotonic[k]
               for k in shared) / len(shared)


def clip(events: list, a: float, b: float) -> list:
    """Events cut to the window [a, b)."""
    out = []
    for name, start, dur in events:
        lo, hi = max(start, a), min(start + dur, b)
        if hi > lo:
            out.append((name, lo, hi - lo))
    return out


def busy_and_gaps(events: list, a: float, b: float) -> tuple:
    """(seconds in [a, b) in which some event ran, the gaps between them as
    ``(start_s, dur_s)``)."""
    spans = sorted((s, s + d) for _, s, d in clip(events, a, b))
    busy, gaps, cursor = 0.0, [], a
    for lo, hi in spans:
        if lo > cursor:
            gaps.append((cursor, lo - cursor))
        if hi > cursor:
            busy += hi - max(lo, cursor)
            cursor = hi
    if b > cursor:
        gaps.append((cursor, b - cursor))
    return busy, gaps


def short_name(name: str) -> str:
    """``%copy.3 = bf16[8,2]{1,0:T(8,128)} copy(...)`` → ``copy.3 bf16[8,2]``:
    an operation's name and result, without layouts and operands."""
    m = OP_TEXT.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name


def top_events(events: list, a: float, b: float, n: int = 10) -> list:
    """The ``n`` names with most time in [a, b): ``[[name, seconds], ..]``."""
    total: dict = {}
    for name, _, dur in clip(events, a, b):
        name = short_name(name)
        total[name] = total.get(name, 0.0) + dur
    return [[name, seconds] for name, seconds in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def attribute_gaps(gaps: list, spans: list, n: int = 10) -> list:
    """Idle seconds by what the host was doing. Each gap is cut at the
    borders of the program spans that reach into it, and every piece goes
    to the span that started last among those covering it (the innermost);
    a piece no span covers goes to ``no_span`` (nothing of the program ran:
    it waited for a request), gaps under 50 us to ``between_operations``.
    ``spans`` are ``(name, start_s, dur_s)`` on the gaps' clock."""
    ordered = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in ordered]
    total: dict = {}
    for start, dur in gaps:
        end = start + dur
        if dur < SHORT_GAP_S:
            total["between_operations"] = total.get(
                "between_operations", 0.0) + dur
            continue
        k = bisect.bisect_left(starts, end)
        inside = [(name, s, s + d)
                  for name, s, d in ordered[max(0, k - LOOK_BACK):k]
                  if s + d > start]
        cuts = sorted({start, end, *(t for _, s, e in inside
                                     for t in (s, e) if start < t < end)})
        for lo, hi in zip(cuts, cuts[1:]):
            covering = [c for c in inside if c[1] <= lo and c[2] >= hi]
            key = (max(covering, key=lambda c: c[1])[0] if covering
                   else "no_span")
            total[key] = total.get(key, 0.0) + hi - lo
    return [[name, seconds] for name, seconds in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
