"""Reader kind ``trace_gated_delta_roofline``: the gated delta rule's
chunked scan's share of its roofline, with its cost function beside it.

The least work is the *recurrence's own*, whatever implements it: per token
and head ``S k`` (2 d_k d_v), the rank-1 update (2 d_k d_v) and ``S q`` (2
d_k d_v), and the bytes no implementation can avoid: ``q, k`` (d_k each)
and ``v`` (d_v) read and ``o`` (d_v) written once a token at two bytes
(the model's bfloat16), alpha and beta at four each, and the ``[d_k, d_v]``
float32 state read and written once a call. What the kernel of
``ops/gated_delta.py`` moves beyond that (its float32 operands, the
chunk-local matrices stage one prepares) is its own overhead and counts
against it: the share says how far the whole scan stage is from what the
recurrence needs, and reads at most 100%.

The shapes come from the kernel's events' names: ``%gated_delta_scan.N =
(f32[H,N,C,dv], f32[H,dk,dv]) custom-call(...)``: heads, chunks x chunk =
tokens, and both widths. The least time of a call is the larger of its
FLOPs over the bf16 peak and its bytes over the HBM bandwidth; the share is
the sum of those over the sum of the events' device times. Params:
``op_contains``. Returns None where the trace holds no such event (a
program without the kernel).
"""

from __future__ import annotations

import re

SHAPE = re.compile(r"(\w+)\[([\d,]+)\]")


def gated_delta_cost(tokens: int, heads: int, dk: int, dv: int) -> tuple:
    """(FLOPs, bytes) the recurrence needs for one call over ``tokens``
    tokens of one sequence."""
    flops = 6 * dk * dv * heads * tokens
    moved = (tokens * heads * ((2 * dk + 2 * dv) * 2 + 2 * 4)
             + 2 * heads * dk * dv * 4)
    return flops, moved


def read(ctx, op_contains: str):
    trace = ctx.get("trace")
    if not trace:
        return None
    least = spent = 0.0
    for name, _, dur in trace["ops"]:
        head = name.split(" custom-call(")[0]
        if op_contains not in head or "custom-call" not in name:
            continue
        dims = [[int(d) for d in s.split(",")] for _, s in
                SHAPE.findall(head)]
        if len(dims) < 2 or len(dims[0]) != 4 or len(dims[1]) != 3:
            continue
        (heads, chunks, chunk, dv), (_, dk, _) = dims[0], dims[1]
        flops, moved = gated_delta_cost(chunks * chunk, heads, dk, dv)
        least += max(flops / ctx["peaks"]["bf16_flops_per_s"],
                     moved / ctx["peaks"]["hbm_bytes_per_s"])
        spent += dur
    return 100.0 * least / spent if spent else None
