"""Reader kind ``counter_rate``: a counter's increase over the window, per
second of it. Params: ``counter``. (The first kind brought as a file: a
kind is ``read(ctx, **params)`` in ``reduce/kinds/<kind>.py``; it returns
None where it finds nothing to read.)"""

from __future__ import annotations

from ..readers import counter_delta


def read(ctx, counter: str):
    delta = counter_delta(ctx, counter)
    if delta is None or not ctx.get("seconds"):
        return None
    return delta / ctx["seconds"]
