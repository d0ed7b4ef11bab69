"""Attention primitives shared by both streams and the co-attention bridge.

TPU-first choices:
- fused QKV projection (one MXU matmul instead of three skinny ones),
- einsum-based multi-head attention that XLA fuses into batched MXU ops,
- additive mask bias computed once per call in the compute dtype,
- probabilities optionally returned for the reference's ``visualization`` /
  ``output_all_attention_masks`` contract (reference worker.py:288).

Reference capability: the torch self-attention inside the external ``vilbert``
package (driven from worker.py:286-289); redesigned, not translated.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

if TYPE_CHECKING:  # annotation only — parallel.ring is imported lazily
    from vilbert_multitask_tpu.parallel.ring import RingContext


def mask_to_bias(mask: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """(B, N) {0,1} mask → (B, 1, 1, N) additive bias.

    Uses the BERT-family -10000 penalty (the reference model family's exact
    constant) rather than -inf so bf16 softmax stays finite.
    """
    bias = (1.0 - mask.astype(dtype)) * -10000.0
    return bias[:, None, None, :]


def multi_head_attention(
    q: jnp.ndarray,  # (B, Nq, H, D)
    k: jnp.ndarray,  # (B, Nk, H, D)
    v: jnp.ndarray,  # (B, Nk, H, D)
    bias: Optional[jnp.ndarray],  # broadcastable to (B, H, Nq, Nk)
    *,
    dropout_rate: float = 0.0,
    deterministic: bool = True,
    dropout_rng=None,
    dtype=jnp.float32,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (context (B, Nq, H, D), probs (B, H, Nq, Nk))."""
    depth = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(depth, dtype=dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=dtype)
    scores = scores * scale
    if bias is not None:
        scores = scores + bias.astype(dtype)
    # softmax at >= fp32 for numerical stability under bf16 compute
    # (promote, don't pin: f64 runs — the conversion-oracle tests — keep f64)
    softmax_dtype = jnp.promote_types(scores.dtype, jnp.float32)
    probs = jnp.asarray(
        nn.softmax(scores.astype(softmax_dtype), axis=-1), dtype=dtype
    )
    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_rate, probs.shape)
        probs_dropped = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    else:
        probs_dropped = probs
    context = jnp.einsum("bhqk,bkhd->bqhd", probs_dropped, v, preferred_element_type=dtype)
    return context, probs


class FusedSelfAttention(nn.Module):
    """BERT-style self-attention with a fused QKV matmul.

    ``num_heads * head_dim == hidden`` always holds for both streams
    (768/12 and 1024/8 in the serving config).

    ``ring`` (a :class:`~vilbert_multitask_tpu.parallel.ring.RingContext`)
    opts this layer into sequence-parallel exact attention over the mesh's
    ``sp`` axis when the (static) sequence length clears the context's
    region-count threshold — the long-context path for region sets beyond
    one chip's HBM. Attention-probs collection and dropout keep the dense
    path (the ring never materializes the (Nq, Nk) matrix, same contract
    as the Pallas kernel below).
    """

    hidden_size: int
    num_heads: int
    dropout_rate: float = 0.1
    use_pallas: bool = False
    pallas_interpret: bool = False  # CPU tests only (ops/coattention.py)
    kernel_mesh: Optional[Any] = None  # Mesh of a partitioned program
    ring: Optional["RingContext"] = None  # parallel/ring.py
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask_bias, *, deterministic: bool = True):
        head_dim = self.hidden_size // self.num_heads
        qkv = nn.Dense(3 * self.hidden_size, dtype=self.dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (*x.shape[:-1], self.num_heads, head_dim)
        q, k, v = (t.reshape(shape) for t in (q, k, v))
        use_dropout = not deterministic and self.dropout_rate > 0.0
        if (self.ring is not None and not use_dropout
                and self.ring.engages(x.shape[1], x.shape[0])):
            from vilbert_multitask_tpu.parallel.ring import ring_self_attention

            # Accumulate at >= fp32 (the same promotion the dense softmax
            # uses) — under bf16 compute the online-softmax recurrence is
            # where precision matters most.
            ctx = ring_self_attention(
                self.ring, q, k, v, mask_bias,
                dtype=jnp.promote_types(self.dtype, jnp.float32))
            ctx = ctx.astype(self.dtype)
            return ctx.reshape(*x.shape[:-1], self.hidden_size), None
        # Kernel path: self-attention probs are never surfaced (the encoder
        # discards them, and the reference's attn_data_list carries only the
        # co-attention maps), so only dropout and tile fit gate this.
        if self.use_pallas and not use_dropout and head_dim % 128 == 0:
            from vilbert_multitask_tpu.ops.coattention import (
                flash_cross_attention,
            )

            # The scope says which use of the one kernel this is; the
            # kernel's own events keep their %flash_cross_attention name.
            with jax.named_scope("self_attention_kernel"):
                ctx = flash_cross_attention(q, k, v, mask_bias,
                                            interpret=self.pallas_interpret,
                                            mesh=self.kernel_mesh)
            return ctx.reshape(*x.shape[:-1], self.hidden_size), None
        dropout_rng = self.make_rng("dropout") if use_dropout else None
        ctx, probs = multi_head_attention(
            q, k, v, mask_bias,
            dropout_rate=self.dropout_rate,
            deterministic=deterministic,
            dropout_rng=dropout_rng,
            dtype=self.dtype,
        )
        ctx = ctx.reshape(*x.shape[:-1], self.hidden_size)
        return ctx, probs


class CrossAttention(nn.Module):
    """One direction of co-attention: queries from ``x``, keys/values from ``y``.

    Projects both operands into the shared ``bi_hidden`` space. The connection
    layer instantiates this twice — text→image and image→text — each direction
    with its own independent Q/K/V projections (matching the reference model
    family, whose bi-attention keeps per-stream projection weights).
    """

    bi_hidden_size: int
    num_heads: int
    dropout_rate: float = 0.1
    use_pallas: bool = False
    pallas_interpret: bool = False  # CPU tests only (ops/coattention.py)
    kernel_mesh: Optional[Any] = None  # Mesh of a partitioned program
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, y, y_mask_bias, *, deterministic: bool = True,
                 need_probs: bool = True):
        head_dim = self.bi_hidden_size // self.num_heads
        q = nn.Dense(self.bi_hidden_size, dtype=self.dtype, name="query")(x)
        k = nn.Dense(self.bi_hidden_size, dtype=self.dtype, name="key")(y)
        v = nn.Dense(self.bi_hidden_size, dtype=self.dtype, name="value")(y)
        B, Nq = x.shape[0], x.shape[1]
        Nk = y.shape[1]
        q = q.reshape(B, Nq, self.num_heads, head_dim)
        k = k.reshape(B, Nk, self.num_heads, head_dim)
        v = v.reshape(B, Nk, self.num_heads, head_dim)
        use_dropout = not deterministic and self.dropout_rate > 0.0
        if self.use_pallas and not need_probs and not use_dropout:
            from vilbert_multitask_tpu.ops.coattention import (
                flash_cross_attention,
            )

            with jax.named_scope("coattention_kernel"):
                ctx = flash_cross_attention(q, k, v, y_mask_bias,
                                            interpret=self.pallas_interpret,
                                            mesh=self.kernel_mesh)
            return ctx.reshape(B, Nq, self.bi_hidden_size), None
        dropout_rng = self.make_rng("dropout") if use_dropout else None
        ctx, probs = multi_head_attention(
            q, k, v, y_mask_bias,
            dropout_rate=self.dropout_rate,
            deterministic=deterministic,
            dropout_rng=dropout_rng,
            dtype=self.dtype,
        )
        return ctx.reshape(B, Nq, self.bi_hidden_size), probs
