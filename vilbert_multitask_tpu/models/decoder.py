"""What the served decoders share (``models/olmo_hybrid.py``,
``models/laguna.py``): the layout a model states for the sequence-state
manager, and the pieces of a step that do not depend on the kind of layer:
RMSNorm, the bfloat16 product, the streaming softmax, the ``jax.numpy``
attention of a prefill chunk over a sequence's pages and of a decode step
over the pool (the CPU's path and the oracles of ``ops/paged_attention.py``'s
two kernels), the in-place row writes and the head.

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

_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class SlotArray:
    """One array of *slot state*: what every running sequence holds at a
    fixed size. The device array is ``lead + (slots,) + shape``; ``ring``
    says the rows are a window's last keys or values (a ring over
    positions), which ``vmt_seq_ring_bytes_in_use`` counts."""

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


def _head(cfg, params, h_last, logit_ids):
    """The logits of given rows: arg-max token, its logit, the logits of
    ``logit_ids`` [..., n] (all float32)."""
    logits = _mm(_rms(h_last, params["final_norm"], cfg.rms_norm_eps),
                 params["lm_head"])
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
