"""What the served decoders share (``models/olmo_hybrid.py``,
``models/laguna.py``, ``models/phi4flash.py``): the layout a model states
for the sequence-state manager, and the pieces of a step that do not depend
on the kind of layer: RMSNorm and LayerNorm, the bfloat16 product, the
streaming softmax, the ``jax.numpy`` attention of a prefill chunk over a
sequence's pages and of a decode step over the pool (the CPU's path and the
oracles of ``ops/paged_attention.py``'s two kernels), the in-place row
writes, a window layer's *ring* (the slot's last ``window`` keys and
values: attention of a chunk and of a step over it, what is left in it,
and the choice between ``ops/paged_attention.py``'s ring kernels and the
``jax.numpy`` forms) and the head.

Attention here is *grouped*: queries of ``H`` heads read pools of ``H_kv``
heads, ``H % H_kv == 0``, query head ``h`` reading key/value head ``h //
(H / H_kv)``; a group's queries are laid side by side as rows of their
key/value head, so one page serves the whole group. ``H == H_kv`` is group
size 1 through the same code.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from vilbert_multitask_tpu.ops import paged_attention

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class SlotArray:
    """One array of *slot state*: what every running sequence holds at a
    fixed size. The device array is ``lead + (slots,) + shape``; ``ring``
    says the rows are a window's last keys or values (a ring over
    positions), which ``vmt_seq_ring_bytes_in_use`` counts, and the array
    has one slot more, which belongs to nobody: where a decode step writes
    the key of a slot it leaves alone."""

    lead: Tuple[int, ...]
    shape: Tuple[int, ...]
    dtype: str
    ring: bool = False

    @property
    def slot_bytes(self) -> int:
        return (math.prod(self.lead) * math.prod(self.shape)
                * jnp.dtype(self.dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class StateLayout:
    """What a model's sequences hold on the device, as its module states it
    (``state_layout``) and ``engine/seqstate.py`` allocates and accounts
    it. *Slot state* (``slot_arrays``) has a fixed size a sequence; *paged
    state* grows with it: keys and values of ``paged_layers`` layers, pools
    ``[paged_layers, pages + 1, kv_heads, page, head_dim]`` of ``dtype``,
    each key/value head read by ``query_group`` query heads."""

    slot_arrays: Dict[str, SlotArray]
    paged_layers: int
    kv_heads: int
    head_dim: int
    dtype: str
    query_group: int = 1


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _layer_norm(x, scale, bias, eps):
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * scale.astype(jnp.float32) + bias.astype(jnp.float32))


def _mm(x, w):
    """bfloat16 operands, float32 result."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _online_softmax(carry, scores, values):
    """One block of a streaming softmax. ``scores`` [H, R, K] float32
    (masked entries at ``_NEG``), ``values`` [H, K, D]."""
    m, l, acc = carry
    m_new = jnp.maximum(m, scores.max(-1))
    p = jnp.exp(scores - m_new[..., None])
    fade = jnp.exp(m - m_new)
    acc = acc * fade[..., None] + jnp.einsum(
        "hrk,hkd->hrd", p.astype(values.dtype), values,
        preferred_element_type=jnp.float32)
    return m_new, l * fade + p.sum(-1), acc


def _head(normed, head, logit_ids, *, tied: bool = False):
    """The logits of given rows, already through the last norm: arg-max
    token, its logit, the logits of ``logit_ids`` [..., n] (all float32).
    ``head`` is the head's matrix [H, V], or with ``tied`` the embedding
    [V, H], contracted over its second axis where it lies (no transposed
    copy of it is made)."""
    if tied:
        logits = jax.lax.dot_general(
            normed.astype(head.dtype), head,
            (((normed.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        logits = _mm(normed, head)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return {"token": token, "token_logit": jnp.max(logits, axis=-1),
            "logits": jnp.take_along_axis(logits, logit_ids, axis=-1)}


def _by_group(q, kv_heads):
    """Queries [N, H, D] as rows of their key/value head: [H_kv, G * N, D],
    row ``g * N + n`` of head ``j`` being query head ``j * G + g``."""
    N, H, D = q.shape
    G = H // kv_heads
    return jnp.swapaxes(q, 0, 1).reshape(kv_heads, G * N, D), G


def _from_group(ctx, N):
    """[H_kv, G * N, D] back to [N, H, D]."""
    kv_heads, _, D = ctx.shape
    return jnp.swapaxes(ctx.reshape(-1, N, D), 0, 1)


def _prefill_attention(cfg, q, k_pool, v_pool, p, page_row, start, block):
    """Causal attention of a chunk's queries [T, H, D] over the sequence's
    pages, ``block`` pages at a time up to the chunk's end. The pools are
    [P, pages, H_kv, page, D]; ``page_row`` names the sequence's pages. The
    ``jax.numpy`` form: every block is copied out of the pool and its
    scores go through memory. It is the CPU's path and the oracle of
    ``ops/paged_attention.py:paged_prefill_attention``, which the chip
    runs."""
    T, _, D = q.shape
    Hk, page = k_pool.shape[2], k_pool.shape[3]
    span = block * page
    qh, G = _by_group(q, Hk)                            # [Hk, G * T, D]
    q_pos = jnp.tile(start + jnp.arange(T), G)

    def gather(pool, j):
        parts = [jax.lax.dynamic_slice(
            pool, (p, page_row[j * block + i], 0, 0, 0),
            (1, 1, Hk, page, D)).reshape(Hk, page, D) for i in range(block)]
        return jnp.concatenate(parts, axis=1)           # [Hk, span, D]

    def body(j, carry):
        scores = jnp.einsum("htd,hkd->htk", qh, gather(k_pool, j),
                            preferred_element_type=jnp.float32)
        k_pos = j * span + jnp.arange(span)
        seen = k_pos[None, :] <= q_pos[:, None]
        scores = jnp.where(seen[None], scores / math.sqrt(D), _NEG)
        return _online_softmax(carry, scores, gather(v_pool, j))

    blocks = (start + T + span - 1) // span
    init = (jnp.full((Hk, G * T), _NEG, jnp.float32),
            jnp.zeros((Hk, G * T), jnp.float32),
            jnp.zeros((Hk, G * T, D), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, blocks, body, init)
    return _from_group(acc / l[..., None], T)           # [T, H, D]


def _write_rows(pool, p, rows, pages, offsets):
    """Write ``rows`` [N, R, H_kv, D] (R tokens of every head) into the pool
    [P, pages, H_kv, page, D] of layer ``p``, row n at page ``pages[n]`` from
    token ``offsets[n]``: one dynamic-update-slice a row, unrolled, each in
    place (a scatter, or a loop that carries the pool, has the compiler
    copy the whole pool)."""
    rows = jnp.swapaxes(rows, 1, 2)                     # [N, H, R, D]
    for n in range(rows.shape[0]):
        pool = jax.lax.dynamic_update_slice(
            pool, rows[n][None, None], (p, pages[n], 0, offsets[n], 0))
    return pool


def _decode_attention(cfg, q, k_pool, v_pool, p, positions, page_slot,
                      page_pos, pool_blocks, block):
    """One query a slot [B, H, D] (row b is slot b) over the whole pool,
    ``block`` pages at a time, each key masked by who owns its page and
    where it lies in its sequence, as far as ``pool_blocks`` says pages are
    in use. The ``jax.numpy`` form: every block is copied out of the pool
    and its scores go through memory. It is the CPU's path and the oracle
    of ``ops/paged_attention.py``, which the chip runs."""
    B, _, D = q.shape
    Hk, page = k_pool.shape[2], k_pool.shape[3]
    span = block * page
    qh, G = _by_group(q, Hk)                            # [Hk, G * B, D]
    slots = jnp.tile(jnp.arange(B), G)
    q_pos = jnp.tile(positions, G)

    def take(pool, j):
        pages = jax.lax.dynamic_slice(
            pool, (p, j * block, 0, 0, 0), (1, block, Hk, page, D))
        return jnp.swapaxes(pages[0], 0, 1).reshape(Hk, span, D)

    def body(j, carry):
        scores = jnp.einsum("hbd,hkd->hbk", qh, take(k_pool, j),
                            preferred_element_type=jnp.float32)
        owner = jax.lax.dynamic_slice_in_dim(page_slot, j * block, block)
        where = jax.lax.dynamic_slice_in_dim(page_pos, j * block, block)
        k_pos = (where[:, None] * page + jnp.arange(page)[None]).reshape(-1)
        mine = (jnp.repeat(owner, page)[None, :] == slots[:, None]) \
            & (k_pos[None, :] <= q_pos[:, None])
        scores = jnp.where(mine[None], scores / math.sqrt(D), _NEG)
        return _online_softmax(carry, scores, take(v_pool, j))

    init = (jnp.full((Hk, G * B), _NEG, jnp.float32),
            jnp.zeros((Hk, G * B), jnp.float32),
            jnp.zeros((Hk, G * B, D), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, pool_blocks, body, init)
    return _from_group(acc / jnp.maximum(l, 1e-30)[..., None], B)


# -------------------------------------------------------------------- ring
# Query rows a window layer's prefill attention takes at once.
SLIDING_QUERY_BLOCK = 256


def _sliding_prefill(window, q, k, v, ring_k, ring_v, start):
    """A window layer over a chunk: queries [T, H, d] at positions
    ``start ..``, the chunk's keys and values [T, H_kv, d], and the slot's
    rings [H_kv, R, d] holding the keys before the chunk. Query rows are
    taken ``SLIDING_QUERY_BLOCK`` at a time against the ``window + block``
    keys that can reach them. Returns the context [T, H, d] float32."""
    T, _, d = q.shape
    kv, R = ring_k.shape[0], ring_k.shape[1]
    W = window
    block = math.gcd(T, SLIDING_QUERY_BLOCK)
    before = jnp.mod(start - W + jnp.arange(W), R)      # rows of start - W ..
    keys = jnp.concatenate([ring_k[:, before], jnp.swapaxes(k, 0, 1)], 1)
    values = jnp.concatenate([ring_v[:, before], jnp.swapaxes(v, 0, 1)], 1)
    qh, G = _by_group(q, kv)                            # [kv, G * T, d]
    qh = qh.reshape(kv, G, T, d)
    # Column c of ``keys`` is position start - W + c.
    col = jnp.arange(W + block)

    def rows(b):
        q_b = jax.lax.dynamic_slice_in_dim(qh, b * block, block, 2
                                           ).reshape(kv, G * block, d)
        k_b = jax.lax.dynamic_slice_in_dim(keys, b * block, W + block, 1)
        v_b = jax.lax.dynamic_slice_in_dim(values, b * block, W + block, 1)
        scores = jnp.einsum("hqd,hkd->hqk", q_b, k_b,
                            preferred_element_type=jnp.float32)
        q_pos = start + b * block + jnp.tile(jnp.arange(block), G)
        k_pos = start - W + b * block + col
        seen = ((k_pos[None, :] <= q_pos[:, None])
                & (q_pos[:, None] - k_pos[None, :] < W)
                & (k_pos[None, :] >= 0))
        probs = jax.nn.softmax(
            jnp.where(seen[None], scores / math.sqrt(d), _NEG), axis=-1)
        return jnp.einsum("hqk,hkd->hqd", probs.astype(v_b.dtype), v_b,
                          preferred_element_type=jnp.float32)

    ctx = jax.lax.map(rows, jnp.arange(T // block))     # [nb, kv, G*blk, d]
    ctx = ctx.reshape(T // block, kv, G, block, d)
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(kv, G * T, d)
    return _from_group(ctx, T)


def _ring_after(ring, rows, start, length):
    """The slot's ring [H_kv, R, d] once the chunk's real rows ``rows`` [T,
    H_kv, d] (positions ``start .. start + length - 1``) are in it: row
    ``j`` holds the last position at or before the chunk's end that is
    ``j`` modulo ``R``, from the chunk where that lies in it, else as it
    was."""
    R, T = ring.shape[1], rows.shape[0]
    last = start + length - 1
    holds = last - jnp.mod(last - jnp.arange(R), R)
    from_chunk = holds >= start
    taken = jnp.swapaxes(rows, 0, 1)[:, jnp.clip(holds - start, 0, T - 1)]
    return jnp.where(from_chunk[None, :, None], taken, ring)


def _write_ring(ring, s, rows, index, active):
    """Row b of ``rows`` [B, H_kv, d] into slot b's ring of window layer
    ``s`` at row ``index[b]``, where ``active[b]``: one dynamic-update-slice
    a slot, in place. An inactive row goes to the array's last slot, which
    belongs to nobody (reading back what the slot held instead, to write it
    again, is a gather that has the compiler lay the whole array out
    another way and copy it in and out every step)."""
    trash = ring.shape[1] - 1
    for b in range(rows.shape[0]):
        ring = jax.lax.dynamic_update_slice(
            ring, rows[b][None, None, :, None, :],
            (s, jnp.where(active[b], b, trash), 0, index[b], 0))
    return ring


def _sliding_decode(window, q, ring_k, ring_v, positions):
    """One query a slot [B, H, d] over the slots' rings [B, H_kv, R, d],
    which already hold the step's own key at ``positions % R``. Row ``j``
    of a ring holds the last position at or before the slot's that is ``j``
    modulo ``R``; it counts where that is no earlier than 0 and inside the
    window."""
    B, n, d = q.shape
    kv, R = ring_k.shape[1], ring_k.shape[2]
    qg = q.reshape(B, kv, n // kv, d)
    scores = jnp.einsum("bhgd,bhrd->bhgr", qg, ring_k,
                        preferred_element_type=jnp.float32)
    pos = positions[:, None]
    holds = pos - jnp.mod(pos - jnp.arange(R)[None, :], R)
    seen = (holds >= 0) & (pos - holds < window)
    probs = jax.nn.softmax(jnp.where(seen[:, None, None, :],
                                     scores / math.sqrt(d), _NEG), axis=-1)
    ctx = jnp.einsum("bhgr,bhrd->bhgd", probs.astype(ring_v.dtype), ring_v,
                     preferred_element_type=jnp.float32)
    return ctx.reshape(B, n, d)


def _ring_prefill(window, q, k, v, ring_k, ring_v, s, slot, start, length, *,
                  kernel: bool, interpret: bool = False):
    """Window layer ``s`` over a chunk of slot ``slot``'s sequence: the
    context [T, H, d] of :func:`_sliding_prefill` over the slot's rings (of
    ``ring_k`` / ``ring_v`` [S, slots + 1, H_kv, R, d]) and the chunk, and
    both arrays with the slot's rings as :func:`_ring_after` leaves them:
    stored by ``ops/paged_attention.py:ring_store`` where ``kernel``, else
    a ``dynamic_update_slice``."""
    at = (s, slot, 0, 0, 0)
    size = (1, 1) + ring_k.shape[2:]
    mine_k = jax.lax.dynamic_slice(ring_k, at, size)[0, 0]
    mine_v = jax.lax.dynamic_slice(ring_v, at, size)[0, 0]
    ctx = _sliding_prefill(window, q, k, v, mine_k, mine_v, start)

    def store(ring, mine, rows):
        new = _ring_after(mine, rows, start, length)
        if kernel:
            return paged_attention.ring_store(ring, s, slot, new,
                                              interpret=interpret)
        return jax.lax.dynamic_update_slice(ring, new[None, None], at)

    return ctx, store(ring_k, mine_k, k), store(ring_v, mine_v, v)


def _ring_decode(window, q, k, v, ring_k, ring_v, s, positions, active, *,
                 kernel: bool, interpret: bool = False):
    """Window layer ``s`` over one token of each of the first B slots: the
    step's keys and values [B, H_kv, d] written to the rings at ``positions
    % R``, then each query [B, H, d] over its slot's ring
    (``ops/paged_attention.py:ring_decode_attention``, which reads the ring
    in place, where ``kernel``; :func:`_sliding_decode` otherwise). Returns
    the context [B, H, d] and both arrays."""
    B, R = q.shape[0], ring_k.shape[3]
    ring_k = _write_ring(ring_k, s, k, positions % R, active)
    ring_v = _write_ring(ring_v, s, v, positions % R, active)
    if kernel:
        ctx = paged_attention.ring_decode_attention(
            q, ring_k, ring_v, s, positions, window, interpret=interpret)
    else:
        ctx = _sliding_decode(window, q, ring_k[s, :B], ring_v[s, :B],
                              positions)
    return ctx, ring_k, ring_v
