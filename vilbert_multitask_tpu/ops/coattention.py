"""Pallas flash co-attention: blockwise region×token cross-attention.

The north-star kernel (BASELINE.json: "region-feature×token co-attention as a
Pallas kernel"). One grid program per (batch, head, query-block); keys/values
stream through VMEM in ``block_k`` tiles with the online-softmax recurrence,
so the score matrix never materializes in HBM and the same kernel scales from
the serving shapes (38 text × 101 regions, reference worker.py:408,433) to
long-context region sets without re-tiling.

Layout choices for the TPU memory system:
- head_dim is zero-padded to the 128-lane width (the serving config's
  bi-attention head_dim is exactly 128: 1024/8);
- Q/K/V tiles sized to the fp32 (8, 128) sublane×lane tile;
- scores/accumulator kept in fp32 regardless of input dtype (bf16 inputs are
  fine; the softmax statistics are not);
- additive mask bias rides in as a (B, Nk) row, broadcast across heads —
  identical semantics to :func:`..ops.attention.mask_to_bias`.

The XLA path in :mod:`..ops.attention` is the numerics reference
(tests/test_pallas_coattention.py); the kernel is used when
``ViLBertConfig.use_pallas_coattention`` is set and attention probabilities
are not requested (the reference's ``visualization`` contract needs probs —
that path stays on XLA, reference worker.py:288).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.sharding import Mesh, PartitionSpec as P

_NEG_BIG = -2.0e9  # mask bias for padded KV rows; far below the -10000 mask


def _flash_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, *, block_k: int,
                  scale: float):
    """One (batch, head, q-block) program: online softmax over KV tiles."""
    q = q_ref[0, 0].astype(jnp.float32)  # (block_q, D)
    block_q, depth = q.shape
    nk = k_ref.shape[2]
    n_blocks = nk // block_k

    acc = jnp.zeros((block_q, depth), jnp.float32)
    m = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        bias = b_ref[0, :, pl.ds(j * block_k, block_k)].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale + bias  # (block_q, block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc_new, m_new, l_new

    acc, m, l = jax.lax.fori_loop(0, n_blocks, body, (acc, m, l))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret", "mesh")
)
def flash_cross_attention(
    q: jnp.ndarray,  # (B, Nq, H, D)
    k: jnp.ndarray,  # (B, Nk, H, D)
    v: jnp.ndarray,  # (B, Nk, H, D)
    bias: jnp.ndarray,  # (B, 1, 1, Nk) additive mask bias (mask_to_bias)
    *,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    mesh: Mesh | None = None,
) -> jnp.ndarray:
    """Blockwise cross-attention; returns context (B, Nq, H, D).

    Pads Nq/Nk/D to tile boundaries (masking padded keys via the bias) and
    slices the padding back off — callers keep reference shapes (37+1 text
    tokens, 101 regions).

    ``interpret`` is the caller's explicit choice (CPU tests pass True, or
    set ``ViLBertConfig.pallas_interpret``); it is never inferred from the
    backend. The default compiles under Mosaic, so off-TPU the call raises
    instead of quietly running the Pallas interpreter.

    ``mesh`` is the device mesh of a partitioned program (the engine's
    multi-chip ``batched`` family). XLA cannot partition a Mosaic call by
    itself ("Mosaic kernels cannot be automatically partitioned"), so the
    kernel then runs under ``shard_map``: batch rows split over ``dp`` and
    heads over ``tp`` wherever those axes divide them (the same
    divisibility rule parallel/sharding.py places batches by), replicated
    otherwise. Attention is independent per (row, head), so each shard's
    kernel is the whole computation for its rows.
    """
    if mesh is None:
        return _flash(q, k, v, bias, block_q=block_q, block_k=block_k,
                      interpret=interpret)

    def axis(name: str, dim: int):
        size = mesh.shape.get(name, 1)
        return name if size > 1 and dim % size == 0 else None

    qkv = P(axis("dp", q.shape[0]), None, axis("tp", q.shape[2]), None)
    return jax.shard_map(
        functools.partial(_flash, block_q=block_q, block_k=block_k,
                          interpret=interpret),
        mesh=mesh, in_specs=(qkv, qkv, qkv, P(qkv[0], None, None, None)),
        out_specs=qkv, check_vma=False)(q, k, v, bias)


def _flash(q, k, v, bias, *, block_q: int, block_k: int, interpret: bool):
    """The kernel launch on one device's rows (see flash_cross_attention)."""
    B, Nq, H, D = q.shape
    Nk = k.shape[1]
    out_dtype = q.dtype

    block_q = min(block_q, _round_up(max(Nq, 8), 8))
    block_k = min(block_k, _round_up(max(Nk, 8), 8))
    nq_p = _round_up(Nq, block_q)
    nk_p = _round_up(Nk, block_k)
    d_p = _round_up(D, 128)

    # (B, H, N, D) layout: heads become a grid axis, rows tile the sublanes.
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, nq_p - Nq), (0, d_p - D)))
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, nk_p - Nk), (0, d_p - D)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, nk_p - Nk), (0, d_p - D)))
    brow = jnp.pad(
        bias.reshape(B, 1, Nk).astype(jnp.float32),
        ((0, 0), (0, 0), (0, nk_p - Nk)),
        constant_values=_NEG_BIG,
    )

    grid = (B, H, nq_p // block_q)
    kernel = functools.partial(
        _flash_kernel, block_k=block_k, scale=1.0 / float(np.sqrt(D))
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d_p), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, nk_p, d_p), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, nk_p, d_p), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, nk_p), lambda b, h, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d_p), lambda b, h, i: (b, h, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, nq_p, d_p), out_dtype),
        interpret=interpret,
    )(qt, kt, vt, brow)
    return jnp.transpose(out[:, :, :Nq, :D], (0, 2, 1, 3))
