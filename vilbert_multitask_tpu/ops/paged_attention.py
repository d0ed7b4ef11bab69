"""Attention over the key/value pool as two Pallas kernels, one a program
family of the generate engine: :func:`paged_decode_attention`, one query a
slot against every page of the pool that is in use, and
:func:`paged_prefill_attention`, a prefill chunk's queries, causally,
against the pages of their own sequence. Both read a page from HBM in
place, once for its keys and once for its values, and send no score
through memory. Beside them the two kernels of a window layer's *ring*
(the slot's last ``window`` keys and values, ``[S, slots + 1, H_kv, R,
D]``): :func:`ring_decode_attention`, a decode step's queries each over its
own slot's ring, read in place one slot a grid step, and :func:`ring_store`,
one slot's ring written into the donated array by one copy. Both exist
because the ``jax.numpy`` forms (``models/decoder.py``'s ``_sliding_decode``
and a ``dynamic_update_slice``; the CPU's path and the oracles) let the
compiler choose another layout for the whole array and copy it in and out
of every step; a kernel's operand keeps the layout it came in.

The pool of one kind (keys, or values) is ``[P, pages + 1, H_kv, page, D]``:
layer, page, key/value head, token in the page, width (the last page
belongs to nobody; decode never reads it). A page of a layer is one
contiguous ``[H_kv, page, D]`` block, and a head's keys in it are a ``[page,
D]`` tile, which is what both products of the kernels want. Queries may have
more heads than the pool (grouped attention, ``H % H_kv == 0``): the ``G =
H / H_kv`` query heads that read one key/value head are laid as ``G``
stacks of rows under it, so one page fetch serves the whole group. ``G`` =
1 is the ungrouped model through the same kernels.

**Decode.** The grid is static, one step a page of the pool. Three
``[pages]`` tables
ride ahead of the grid as scalar-prefetch operands: ``owner`` (whose the
page is, -1 where the step has nothing to read: nobody's page, a page of a
slot beyond the batch, a page past ``pool_blocks``), ``where`` (which of its
sequence's pages it is) and ``fetch`` (the page the step's blocks name: the
page itself where it is owned, else the nearest owned page before it, which
is the block the pipeline already holds, so nothing is fetched for a step
that is skipped). A step that owns a page computes the scores of all ``B``
queries against the page's keys, a ``[G * B, D] x [D, page]`` product a
head, masks them (the page's owner, and the key's position against the
slot's), and folds them into the streaming softmax whose ``m``, ``l`` and
``acc`` live in VMEM scratch across the grid.

**Prefill.** The grid is one step a *query tile*: ``tq`` positions of the
chunk, all ``G`` stacks of them (:func:`prefill_tile`: as many as keep a
key/value head's rows at some hundreds, so that a page's products outweigh
its fetch). The sequence's page table and the chunk's first position ride
ahead as scalar-prefetch operands; the pools stay in HBM. Inside a step a
loop walks the sequence's pages up to the tile's last position and no
further: its bound is the tile's reach, not the table's length (a grid step
that does nothing still costs a third of a microsecond, and a table is
hundreds of pages long). A page is copied ``[H_kv, page, D]`` into one of
two VMEM buffers while the one before it is computed. Pages every query
of the tile sees whole take no mask; the one or two on the tile's
diagonal are masked by position. Heads are walked a few at a time,
unrolled, so that one head's exponent overlaps the next head's products.
The running maximum is held a vreg's 128 lanes wide, every lane alike, and
the running sum as 128 partial sums a row, folded once when the tile ends:
a step spreads no row vector over a tile's lanes and sums across none.

The mathematics is ``models/decoder.py``'s ``_decode_attention`` and
``_prefill_attention`` (the ``jax.numpy`` forms: the CPU path and the
tests' oracles): operands in the pool's dtype, float32 accumulation,
float32 ``m`` / ``l`` / ``acc``, ``p`` rounded to the values' dtype before
the second product, the scale applied to the float32 scores. One
difference, in rows nobody reads: a decode slot with no key at all gives
exactly 0 here (the oracle gives it the mean of the values it has masked).
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


# ------------------------------------------------------------------ prefill
# Query rows a key/value head takes in one tile (the group's stacks
# together): enough that a page's products outweigh its fetch.
_TILE_ROWS = 768
# Heads walked in one unrolled stretch of a page step.
_HEAD_UNROLL = 8


def prefill_tile(T: int, group: int) -> int:
    """Query positions a tile: the chunk's ``T`` halved until its ``group``
    stacks are at most ``_TILE_ROWS`` rows; 128 x 6 in the sparse-expert
    decoder, 512 x 1 in the hybrid one."""
    tq = T
    while tq % 2 == 0 and group * tq > _TILE_ROWS:
        tq //= 2
    return tq


def prefill_pages_walked(start: int, T: int, page: int, group: int,
                         max_pages: int) -> int:
    """Page steps one call of :func:`paged_prefill_attention` walks, its
    tiles summed: a tile reads every page up to its last position's (never
    past the ``max_pages`` of the sequence's table). What the engine's
    ``vmt_prefill_attention_pages_total`` counts, a paged layer."""
    tq = prefill_tile(T, group)
    return sum(min((start + (i + 1) * tq - 1) // page + 1, max_pages)
               for i in range(T // tq))


def _prefill_vmem_bytes(Hk, rows, D, page, lanes, unroll, itemsize) -> int:
    """What a tile holds in VMEM: the queries and the context twice (the
    pipeline's buffers), two pages of keys and of values, ``acc``, ``m``
    and ``l``, and the scores of the heads walked together in float32, as
    ``p`` and rounded; a quarter more and 8 MiB for what the compiler
    adds, inside the chip's 128 MiB."""
    tile = Hk * rows * D
    held = (2 * tile * itemsize + 2 * tile * 4 + 4 * Hk * page * D * itemsize
            + tile * 4 + 2 * Hk * rows * max(lanes, 128) * 4
            + unroll * rows * page * (4 + 4 + itemsize))
    return min(held * 5 // 4 + 8 * 2 ** 20, 112 * 2 ** 20)


def _prefill_kernel(page_row_ref, start_ref, q_ref, k_hbm, v_hbm, o_ref,
                    k_buf, v_buf, sem, m_ref, l_ref, acc_ref, *,
                    scale: float, layer: int, tq: int, unroll: int):
    heads, rows, D = q_ref.shape
    page = k_buf.shape[2]
    lanes = m_ref.shape[2]
    first = start_ref[0] + pl.program_id(0) * tq    # the tile's positions
    last = first + tq - 1
    # Pages up to the last one any query of the tile sees (never past the
    # table), and of them the pages every query sees whole.
    reach = jnp.minimum(last // page + 1, page_row_ref.shape[0])
    clear = jnp.minimum((first + 1) // page, reach)

    def copies(j, slot):
        at = page_row_ref[j]
        return (pltpu.make_async_copy(k_hbm.at[layer, at], k_buf.at[slot],
                                      sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, at], v_buf.at[slot],
                                      sem.at[1, slot]))

    def fetch(j, slot):
        for copy in copies(j, slot):
            copy.start()

    def across(x, n):
        """``x`` [rows, lanes], every lane alike, as [rows, n]."""
        return jnp.concatenate([x] * (n // lanes), axis=1)

    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    fetch(0, 0)

    def step(j, _, *, masked: bool):
        slot = j % 2

        @pl.when(j + 1 < reach)
        def _():
            fetch(j + 1, 1 - slot)

        for copy in copies(j, slot):
            copy.wait()
        if masked:                      # row g * tq + t is position first + t
            q_pos = first + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page), 0) % tq
            k_pos = j * page + jax.lax.broadcasted_iota(
                jnp.int32, (rows, page), 1)
            seen = k_pos <= q_pos

        def head(h):
            s = jax.lax.dot_general(
                q_ref[h], k_buf[slot, h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(seen, s, _NEG)
            # ``m`` is held ``lanes`` wide, every lane alike, and ``l`` as
            # ``lanes`` partial sums a row: no row vector is spread over a
            # tile's lanes and no sum crosses them until the tile ends (a
            # [rows, 1] column for each ran at 0.48 of this on the v5e).
            m = m_ref[h]
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            p = jnp.exp(s - across(m_new, page))
            fade = jnp.exp(m - m_new)
            l_ref[h] = l_ref[h] * fade + sum(
                p[:, c:c + lanes] for c in range(0, page, lanes))
            acc_ref[h] = acc_ref[h] * across(fade, D) + jnp.dot(
                p.astype(v_buf.dtype), v_buf[slot, h],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        def some_heads(n, _):
            for u in range(unroll):
                head(n * unroll + u)

        jax.lax.fori_loop(0, heads // unroll, some_heads, None)

    jax.lax.fori_loop(0, clear, functools.partial(step, masked=False), None)
    jax.lax.fori_loop(clear, reach, functools.partial(step, masked=True),
                      None)
    o_ref[...] = acc_ref[...] / l_ref[...].sum(-1, keepdims=True)


def paged_prefill_attention(q, k_pool, v_pool, p: int, page_row, start, *,
                            interpret: bool = False):
    """Causal attention of a prefill chunk's queries ``q`` [T, H, D]
    (positions ``start ..``) over layer ``p`` of the pools [P, pages + 1,
    H_kv, page, D] (``H % H_kv == 0``), read in place by ``page_row`` [max
    pages], the sequence's pages in order. Returns the context [T, H, D]
    float32 (the profile's ``paged_prefill_attention`` custom call)."""
    T, H, D = q.shape
    Hk, page = k_pool.shape[2], k_pool.shape[3]
    if H % Hk:
        raise ValueError(f"{H} query heads do not divide into {Hk} "
                         "key/value heads")
    G = H // Hk
    tq = prefill_tile(T, G)
    tiles, rows = T // tq, G * tq
    lanes = math.gcd(128, page, D)      # a vreg's, where the shapes have them
    unroll = max(u for u in range(1, _HEAD_UNROLL + 1) if Hk % u == 0)
    # Tile i, key/value head j, row g * tq + t: query head j * G + g at
    # position start + i * tq + t.
    qt = jnp.transpose(q.reshape(tiles, tq, Hk, G, D), (0, 2, 3, 1, 4)
                       ).reshape(tiles, Hk, rows, D)
    tile = pl.BlockSpec((None, Hk, rows, D), lambda i, *tables: (i, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=1.0 / math.sqrt(D),
                          layer=p, tq=tq, unroll=unroll),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[tile, pool, pool],
            out_specs=tile,
            scratch_shapes=[pltpu.VMEM((2, Hk, page, D), k_pool.dtype),
                            pltpu.VMEM((2, Hk, page, D), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((Hk, rows, lanes), jnp.float32),
                            pltpu.VMEM((Hk, rows, lanes), jnp.float32),
                            pltpu.VMEM((Hk, rows, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tiles, Hk, rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_prefill_vmem_bytes(
                Hk, rows, D, page, lanes, unroll, q.dtype.itemsize)),
        name="paged_prefill_attention",
        interpret=interpret,
    )(page_row, jnp.reshape(start, (1,)).astype(jnp.int32), qt, k_pool,
      v_pool)
    return jnp.transpose(out.reshape(tiles, Hk, G, tq, D), (0, 3, 1, 2, 4)
                         ).reshape(T, H, D)


# --------------------------------------------------------------------- ring
def _ring_kernel(pos_ref, row_ref, q_ref, k_ref, v_ref, o_ref, *,
                 scale: float, window: int):
    b = pl.program_id(0)
    R = k_ref.shape[1]
    rows = q_ref.shape[1]
    # Row j holds the last position at or before the slot's that is j
    # modulo R: ``back`` positions before it.
    j = jax.lax.broadcasted_iota(jnp.int32, (rows, R), 1)
    back = jnp.where(j <= row_ref[b], row_ref[b] - j, row_ref[b] - j + R)
    seen = ((back <= pos_ref[b]) & (back < window))[None]
    s = jnp.einsum("hgd,hrd->hgr", q_ref[...], k_ref[...],
                   preferred_element_type=jnp.float32)
    s = jnp.where(seen, s * scale, _NEG)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    o_ref[...] = jnp.einsum(
        "hgr,hrd->hgd", p.astype(v_ref.dtype), v_ref[...],
        preferred_element_type=jnp.float32) / p.sum(-1, keepdims=True)


def ring_decode_attention(q, ring_k, ring_v, s: int, positions, window: int,
                          *, interpret: bool = False):
    """One query a slot ``q`` [B, H, D] (row b is slot b, at position
    ``positions[b]``) over the slots' rings of window layer ``s``, ``ring_k``
    / ``ring_v`` [S, slots, H_kv, R, D] read in place, one slot a grid
    step: position ``p`` lives at row ``p % R``, and a row counts where the
    position it holds is no earlier than 0 and inside the window (``window
    <= R``). The mathematics is ``models/decoder.py:_sliding_decode``'s.
    Returns the context [B, H, D] float32 (the profile's
    ``ring_decode_attention`` custom call)."""
    B, H, D = q.shape
    Hk, R = ring_k.shape[2], ring_k.shape[3]
    if H % Hk:
        raise ValueError(f"{H} query heads do not divide into {Hk} "
                         "key/value heads")
    G = H // Hk
    rows = -(-G // 16) * 16             # an operand tile's sublanes
    qh = jnp.pad(q.reshape(B, Hk, G, D),
                 ((0, 0), (0, 0), (0, rows - G), (0, 0)))
    mine = pl.BlockSpec((None, Hk, rows, D), lambda b, *tables: (b, 0, 0, 0))
    ring = pl.BlockSpec((None, None, Hk, R, D),
                        lambda b, *tables: (s, b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_ring_kernel, scale=1.0 / math.sqrt(D),
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[mine, ring, ring], out_specs=mine),
        out_shape=jax.ShapeDtypeStruct((B, Hk, rows, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="ring_decode_attention",
        interpret=interpret,
    )(positions.astype(jnp.int32), (positions % R).astype(jnp.int32), qh,
      ring_k, ring_v)
    return out[:, :, :G].reshape(B, H, D)


def _store_kernel(at_ref, new_ref, ring_hbm, out_hbm, sem, *, s: int):
    del ring_hbm                        # the same buffer as ``out_hbm``
    copy = pltpu.make_async_copy(new_ref, out_hbm.at[s, at_ref[0]], sem)
    copy.start()
    copy.wait()


def ring_store(ring, s: int, slot, new, *, interpret: bool = False):
    """``ring`` [S, slots, H_kv, R, D] with slot ``slot``'s ring of window
    layer ``s`` replaced by ``new`` [H_kv, R, D], in place: one copy into
    the donated buffer (a ``dynamic_update_slice`` here lets the compiler
    choose another layout for the whole array and copy it in and out: 2.7
    GB each way at the served size)."""
    return pl.pallas_call(
        functools.partial(_store_kernel, s=s),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(new.shape, lambda i, at: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(ring.shape, ring.dtype),
        input_output_aliases={2: 0},
        name="ring_store",
        interpret=interpret,
    )(jnp.reshape(slot, (1,)).astype(jnp.int32), new.astype(ring.dtype), ring)
