"""Reader kind ``trace_cca_prep_roofline``: the share of its roofline that
CCA's preparation of queries and keys (``ops/cca.py``'s kernel) reaches,
with its cost function beside it.

The least work is what a row's queries and keys need, whatever implements
it: the second convolution's two ``d x d`` products for each of the
latent channels' ``G`` heads (``2 x 2 x G x d x d`` FLOPs a row; the first
convolution, the means, the norms and the rotary embedding are the vector
unit's and are not counted), the row's ``G d`` latent channels read once as
float32 and its ``G d`` prepared channels written once at the output's
width, and, once a call, the second convolution's matrices at their
stored width. What the kernel reads beyond that (the rows before a block,
the rotary tables, the carried rows) is its own overhead and counts
against it.

**The rows come from the program's counters** (``vmt_cca_rows_total``
over ``vmt_cca_calls_total``: the window's mean a call, real rows only),
never from the shapes in the event's name: those are the prefill bucket
or the decode bucket, padded, and would count rows of padding as work
done. ``G`` and ``d`` are read from the second convolution's matrices in
the event's name (``bf16[2,G,d,d]``), the output's width from its result.
The least time of a call is the larger of its FLOPs over the bf16 peak and
its bytes over the HBM bandwidth, taken at the mean call (convex: the
share errs low), times the kernel's events in the traced part, over their
device time. Params: ``op_contains``. Returns None where the trace holds
no such event or the program has no such counters (a program without CCA).
"""

from __future__ import annotations

import re

from ..readers import counter_delta

MAPS = re.compile(r"(bf16|f32)\[2,(\d+),(\d+),(\d+)\]")
RESULT = re.compile(r"(bf16|f32)\[\d+,\d+\]")
WIDTH = {"bf16": 2, "f32": 4}


def cca_prep_cost(rows: float, groups: int, head_dim: int, out_bytes: int,
                  map_bytes: int) -> tuple:
    """(FLOPs, bytes) the preparation of ``rows`` rows needs in one call."""
    channels = groups * head_dim
    flops = 4.0 * groups * head_dim * head_dim * rows
    moved = (rows * channels * (4 + out_bytes)
             + 2 * groups * head_dim * head_dim * map_bytes)
    return flops, moved


def read(ctx, op_contains: str):
    trace = ctx.get("trace")
    if not trace:
        return None
    rows = counter_delta(ctx, "vmt_cca_rows_total")
    calls = counter_delta(ctx, "vmt_cca_calls_total")
    if not calls or rows is None:
        return None
    events, spent, sizes = 0, 0.0, None
    for name, _, dur in trace["ops"]:
        head, _, operands = name.partition(" custom-call(")
        if op_contains not in head.split(" = ")[0] or not operands:
            continue
        maps = [m for m in MAPS.findall(operands) if m[2] == m[3]]
        result = RESULT.search(head.partition(" = ")[2])
        if not maps or not result:
            continue
        dtype, groups, d, _ = maps[0]
        sizes = (int(groups), int(d), WIDTH[result.group(1)], WIDTH[dtype])
        events += 1
        spent += dur
    if not events or not spent:
        return None
    flops, moved = cca_prep_cost(rows / calls, *sizes)
    least = events * max(flops / ctx["peaks"]["bf16_flops_per_s"],
                         moved / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / spent
