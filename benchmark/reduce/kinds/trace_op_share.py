"""Reader kind ``trace_op_share``: the device time of the operations whose
name (the part before `` = ``) contains ``op_contains``, as a share (%) of
the time the device was busy in the traced part: whether a mechanism does
most of the work. Params: ``op_contains``. Returns None where the trace
holds no such operation."""

from __future__ import annotations


def read(ctx, op_contains: str):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    spent = sum(dur for name, _, dur in trace["ops"]
                if op_contains in name.split(" = ")[0])
    return 100.0 * spent / trace["busy_s"] if spent else None
