"""Reader kind ``trace_selective_scan_roofline``: the selective scan's share
of its roofline, with its cost function beside it.

The least work is the *recurrence's own*, whatever implements it: per token,
channel and state dimension the decay's exponent (``dt A``: one product,
the exponent itself), the state's update (two products and a sum) and the
read-out (a product and a sum), counted as 9 operations; and the bytes no
implementation can avoid: ``c`` and ``dt`` read and ``m`` written once a
token and channel at their stored width (float32: four bytes), ``B`` and
``C`` once a token and state dimension at four, and the ``[d_state,
d_inner]`` float32 state read once and written once a call. What the kernel
of ``ops/selective_scan.py`` spends beyond that is its own overhead and
counts against it. The work is the vector unit's and the peak in the
denominator is the matrix unit's, so the larger term is the bytes' and the
share reads low: it says how far the scan is from streaming its operands at
the memory's speed.

**The tokens come from the program's counters**, never from the shapes in
the event's name: those are the prefill bucket, padded (``%selective_scan.N
= (f32[T,rows,128], f32[N,rows,128]) custom-call(...)``: ``T`` the bucket,
``rows x 128`` the channels, ``N`` the state), and a padded shape would
count rows of padding as work done. Real prompt tokens a chunk is
``vmt_prefill_tokens_total`` over ``vmt_prefill_attention_chunks_total``
(the window's mean: every state-space layer of a chunk sees the same
tokens). The least time of a call is the larger of its FLOPs over the bf16
peak and its bytes over the HBM bandwidth, taken at the mean call (the
function is convex, so the mean call's least time is at most the calls'
mean least time: the share errs low), times the kernel's events in the
traced part, over their device time. Params: ``op_contains``. Returns None
where the trace holds no such event or the program has no such counters (a
program without the kernel).
"""

from __future__ import annotations

import re

from ..readers import counter_delta

RESULTS = re.compile(r"f32\[(\d+),(\d+),(\d+)\]")


def selective_scan_cost(tokens: float, d_inner: int, d_state: int) -> tuple:
    """(FLOPs, bytes) the recurrence needs for one call over ``tokens``
    tokens of one sequence."""
    flops = 9.0 * d_inner * d_state * tokens
    moved = (tokens * (3 * d_inner + 2 * d_state) * 4
             + 2 * d_state * d_inner * 4)
    return flops, moved


def read(ctx, op_contains: str):
    trace = ctx.get("trace")
    if not trace:
        return None
    tokens = counter_delta(ctx, "vmt_prefill_tokens_total")
    chunks = counter_delta(ctx, "vmt_prefill_attention_chunks_total")
    if not chunks or tokens is None:
        return None
    events, spent, sizes = 0, 0.0, None
    for name, _, dur in trace["ops"]:
        head = name.split(" custom-call(")[0]
        if op_contains not in head.split(" = ")[0] \
                or "custom-call" not in name:
            continue
        found = RESULTS.findall(head)
        if len(found) < 2:
            continue
        (_, rows, lanes), (states, _, _) = (
            tuple(int(d) for d in dims) for dims in found[:2])
        sizes = (rows * lanes, states)
        events += 1
        spent += dur
    if not events or not spent:
        return None
    flops, moved = selective_scan_cost(tokens / chunks, *sizes)
    least = events * max(flops / ctx["peaks"]["bf16_flops_per_s"],
                         moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
