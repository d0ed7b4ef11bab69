"""Decode attention over the key/value pool as one Pallas kernel: one query
a slot against every page of the pool that is in use, each page read from
HBM once for its keys and once for its values.

The pool of one kind (keys, or values) is ``[P, pages + 1, H_kv, page, D]``:
layer, page, key/value head, token in the page, width (the last page
belongs to nobody; decode never reads it). A page of a layer is one
contiguous ``[H_kv, page, D]`` block, and a head's keys in it are a ``[page,
D]`` tile, which is what both products of the kernel want. Queries may have
more heads than the pool (grouped attention, ``H % H_kv == 0``): the ``G =
H / H_kv`` query heads that read one key/value head are laid as ``G``
stacks of rows under it, so one page fetch serves the whole group and the
products are ``[G * B, D] x [D, page]`` a key/value head. ``G`` = 1 is the
ungrouped model through the same kernel.

The grid is static, one step a page of the pool. Three ``[pages]`` tables
ride ahead of the grid as scalar-prefetch operands: ``owner`` (whose the
page is, -1 where the step has nothing to read: nobody's page, a page of a
slot beyond the batch, a page past ``pool_blocks``), ``where`` (which of its
sequence's pages it is) and ``fetch`` (the page the step's blocks name: the
page itself where it is owned, else the nearest owned page before it, which
is the block the pipeline already holds, so nothing is fetched for a step
that is skipped). A step that owns a page computes the scores of all ``B``
queries against the page's keys, a ``[B, D] x [D, page]`` product a head,
masks them (the page's owner, and the key's position against the slot's),
and folds them into the streaming softmax whose ``m``, ``l`` and ``acc``
live in VMEM scratch across the grid. No tile and no score goes to HBM.

The mathematics is ``models/decoder.py:_decode_attention``'s (the
``jax.numpy`` form: the CPU path and the tests' oracle): operands in the
pool's dtype, float32 accumulation, float32 ``m`` / ``l`` / ``acc``, ``p``
rounded to the values' dtype before the second product, the scale applied
to the float32 scores. One difference, in rows nobody reads: a slot with no
key at all gives exactly 0 here (the oracle gives it the mean of the values
it has masked).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
# The kernel holds two pages of keys and two of values (the pipeline's
# double buffer; 7.9 MB at the served size) beside its scratch: above the
# compiler's default scoped limit on some chips, far inside a v5e's VMEM.
_VMEM_LIMIT = 48 * 1024 * 1024


def _kernel(owner_ref, where_ref, fetch_ref, q_ref, pos_ref, k_ref, v_ref,
            o_ref, m_ref, l_ref, acc_ref, *, scale: float, group: int):
    del fetch_ref                       # the index maps' alone
    i = pl.program_id(0)
    page = k_ref.shape[1]
    rows = pos_ref.shape[0]             # group stacks of the padded batch

    @pl.when(i == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    owner = owner_ref[i]

    @pl.when(owner >= 0)
    def _():
        k_pos = where_ref[i] * page + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page), 1)
        slot = jax.lax.broadcasted_iota(jnp.int32, (rows, page), 0)
        if group > 1:                   # row g * batch + b is slot b
            slot = slot % (rows // group)
        mine = ((slot == owner) & (k_pos <= pos_ref[...]))[None]
        # Every head at once: a loop over heads that keeps ``m`` and ``l``
        # a head in [rows, 1] tiles runs at 0.6 of this (v5e, PERF.md).
        s = jnp.einsum("hbd,hkd->hbk", q_ref[...], k_ref[...],
                       preferred_element_type=jnp.float32)
        s = jnp.where(mine, s * scale, _NEG)
        m = m_ref[...]
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        p = jnp.where(mine, jnp.exp(s - m_new), 0.0)
        fade = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * fade + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * fade + jnp.einsum(
            "hbk,hkd->hbd", p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


def paged_decode_attention(q, k_pool, v_pool, p: int, positions, page_slot,
                           page_pos, pool_blocks, block: int, *,
                           interpret: bool = False):
    """One query a slot ``q`` [B, H, D] (row b is slot b, at position
    ``positions[b]``) over layer ``p`` of the pools [P, pages + 1, H_kv,
    page, D] (``H % H_kv == 0``), read in place. ``page_slot`` / ``page_pos`` [pages] (int32, as
    ``positions``) say whose each page is and which of its sequence's
    pages; pages from ``pool_blocks * block`` on are not in use. Returns the context [B, H, D] float32 (the
    profile's ``paged_decode_attention`` custom call)."""
    B, H, D = q.shape
    Hk, page = k_pool.shape[2], k_pool.shape[3]
    if H % Hk:
        raise ValueError(f"{H} query heads do not divide into {Hk} "
                         "key/value heads")
    G = H // Hk
    pages = page_slot.shape[0]
    # Queries are the sublanes of an operand tile: 16 rows of bfloat16.
    batch = -(-B // 16) * 16
    rows = G * batch
    # Query head j * G + g is stack g of key/value head j.
    qh = jnp.pad(jnp.swapaxes(q, 0, 1), ((0, 0), (0, batch - B), (0, 0))
                 ).reshape(Hk, rows, D)
    pos = jnp.tile(jnp.pad(positions, (0, batch - B), constant_values=-1),
                   G).reshape(rows, 1)
    index = jnp.arange(pages, dtype=jnp.int32)
    read = ((page_slot >= 0) & (page_slot < B)
            & (index < pool_blocks * block))
    owner = jnp.where(read, page_slot, -1)
    fetch = jax.lax.cummax(jnp.where(read, index, 0))

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i, *tables: (0,) * len(shape))

    one_page = pl.BlockSpec(
        (None, None, Hk, page, D),
        lambda i, owner, where, fetch: (p, fetch[i], 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=1.0 / math.sqrt(D), group=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(pages,),
            in_specs=[whole(Hk, rows, D), whole(rows, 1), one_page,
                      one_page],
            out_specs=whole(Hk, rows, D),
            scratch_shapes=[pltpu.VMEM((Hk, rows, 1), jnp.float32),
                            pltpu.VMEM((Hk, rows, 1), jnp.float32),
                            pltpu.VMEM((Hk, rows, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((Hk, rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="paged_decode_attention",
        interpret=interpret,
    )(owner, page_pos, fetch, qh, pos, k_pool, v_pool)
    return jnp.swapaxes(out.reshape(H, batch, D)[:, :B], 0, 1)
