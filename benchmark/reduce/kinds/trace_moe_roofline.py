"""Reader kind ``trace_moe_roofline``: the grouped expert product's share of
its roofline, with its cost function beside it.

The least work is what the *routing* asked of the chip, whatever implements
it: a (token, expert) pair computed here is the expert's three products over
one row, ``6 x hidden x width`` FLOPs, and its row read and written once at
two bytes a number (the model's bfloat16); an expert that got at least one
pair has its three matrices read once, ``3 x hidden x width`` numbers at two
bytes. What the kernel of ``ops/moe.py`` moves or multiplies beyond that
(rows of padding inside a tile, float32 rows out) is its own overhead and
counts against it.

**The pairs and the experts touched come from the program's counters**
(``vmt_moe_pairs_total``, ``vmt_moe_experts_touched_total`` over
``vmt_moe_calls_total``: the window's mean a call), never from the shapes in
the event's name: those are the buffers, sized for every pair landing here,
and would count the worst case as work done. The least time of a call is
the larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth, taken at the mean call (the function is convex, so the mean
call's least time is at most the calls' mean least time: the share errs
low), times the kernel's events in the traced part, over their device time.
``hidden`` and ``width`` are read from the expert matrices' shapes in the
event's name (``bf16[E, hidden, 2 width]``). Params: ``op_contains``.
Returns None where the trace holds no such event or the program has no such
counters (a program without the expert layer).
"""

from __future__ import annotations

import re

from ..readers import counter_delta

MATRICES = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")


def moe_cost(pairs: float, experts_touched: float, hidden: int,
             width: int) -> tuple:
    """(FLOPs, bytes) the routing needs of one expert-layer call."""
    flops = 6.0 * hidden * width * pairs
    moved = (experts_touched * 3 * hidden * width * 2
             + pairs * hidden * (2 + 2))
    return flops, moved


def read(ctx, op_contains: str):
    trace = ctx.get("trace")
    if not trace:
        return None
    calls = counter_delta(ctx, "vmt_moe_calls_total")
    pairs = counter_delta(ctx, "vmt_moe_pairs_total")
    touched = counter_delta(ctx, "vmt_moe_experts_touched_total")
    if not calls or pairs is None or touched is None:
        return None
    events, spent, sizes = 0, 0.0, None
    for name, _, dur in trace["ops"]:
        if op_contains not in name.split(" = ")[0]:
            continue
        found = MATRICES.findall(name)
        if not found:
            continue
        _, hidden, twice_width = (int(d) for d in found[0])
        sizes = (hidden, twice_width // 2)
        events += 1
        spent += dur
    if not events or not spent:
        return None
    flops, moved = moe_cost(pairs / calls, touched / calls, *sizes)
    least = events * max(flops / ctx["peaks"]["bf16_flops_per_s"],
                         moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
