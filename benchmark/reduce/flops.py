"""A kernel's operations and bytes, from shapes alone: the numerators of
``*_roofline``. (A family's whole step, the numerator of ``*_mfu``, is
counted by the family: ``families/<family>``, ``flops_per_unit``.)"""

from __future__ import annotations


def attention_kernel_cost(rows: int, nq: int, nk: int, heads: int,
                          depth: int, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one blockwise attention call needs: the two products
    per head, and q, k, v read and the context written once."""
    flops = rows * heads * 2 * 2 * nq * nk * depth
    moved = rows * heads * depth * itemsize * (2 * nq + 2 * nk)
    return flops, moved
