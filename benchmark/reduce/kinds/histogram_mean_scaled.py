"""Reader kind ``histogram_mean_scaled``: the mean of a ``vmt_*``
histogram's samples in the window, all label sets, times ``scale`` (a share
observed as 0..1 reported in per cent). Params: ``instrument``, ``scale``.
Returns None where the program has no such histogram or it holds no
sample."""

from __future__ import annotations

from ..readers import histogram_mean


def read(ctx, instrument: str, scale: float = 1.0):
    mean = histogram_mean(ctx, instrument)
    return None if mean is None else scale * mean
