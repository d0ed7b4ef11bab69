"""Transformer building blocks for both streams.

Post-LayerNorm BERT topology (what the 12-in-1 checkpoint family was trained
with), fused-QKV attention, GELU FFN. Reference capability: the BertLayer /
BertImageLayer / BertConnectionLayer stack inside the external ``vilbert``
package driven from worker.py:286-289 — re-designed as Flax modules.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING, Any, Optional, Tuple

import jax.numpy as jnp
from flax import linen as nn

from vilbert_multitask_tpu.ops.attention import (
    CrossAttention,
    FusedSelfAttention,
)

if TYPE_CHECKING:
    from vilbert_multitask_tpu.parallel.ring import RingContext

# Exact (erf) GELU: the BERT/ViLBERT family is trained with the exact form,
# and flax's default is the tanh approximation — close enough to train, close
# enough to silently flip near-tie answer rankings at serving time. Keep erf.
ACT = {
    "gelu": functools.partial(nn.gelu, approximate=False),
    "relu": nn.relu,
    "swish": nn.swish,
}


class AttentionOutput(nn.Module):
    """Projection + dropout + residual + LayerNorm after an attention block."""

    hidden_size: int
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-12
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, context, residual, *, deterministic: bool = True):
        x = nn.Dense(self.hidden_size, dtype=self.dtype, name="dense")(context)
        x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        x = nn.LayerNorm(epsilon=self.layer_norm_eps, dtype=self.dtype, name="norm")(
            x + residual
        )
        return x


class FeedForward(nn.Module):
    """BERT FFN: expand → activation → contract → dropout → residual → LN.

    The intermediate matmul is the MXU workhorse; kept as one large dense so
    XLA tiles it onto the systolic array and fuses the activation.
    """

    hidden_size: int
    intermediate_size: int
    activation: str = "gelu"
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-12
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, *, deterministic: bool = True):
        h = nn.Dense(self.intermediate_size, dtype=self.dtype, name="intermediate")(x)
        h = ACT[self.activation](h)
        h = nn.Dense(self.hidden_size, dtype=self.dtype, name="output")(h)
        h = nn.Dropout(self.dropout_rate)(h, deterministic=deterministic)
        return nn.LayerNorm(
            epsilon=self.layer_norm_eps, dtype=self.dtype, name="norm"
        )(h + x)


class TransformerLayer(nn.Module):
    """One single-stream encoder layer (text or visual).

    ``ring`` opts the self-attention into the sequence-parallel path (see
    FusedSelfAttention); param structure is identical either way, so dense
    and ring instances share checkpoints.
    """

    hidden_size: int
    num_heads: int
    intermediate_size: int
    activation: str = "gelu"
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    use_pallas: bool = False
    pallas_interpret: bool = False
    kernel_mesh: Optional[Any] = None
    ring: Optional["RingContext"] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, mask_bias, deterministic: bool = True):
        ctx, probs = FusedSelfAttention(
            hidden_size=self.hidden_size,
            num_heads=self.num_heads,
            dropout_rate=self.attention_dropout,
            use_pallas=self.use_pallas,
            pallas_interpret=self.pallas_interpret,
            kernel_mesh=self.kernel_mesh,
            ring=self.ring,
            dtype=self.dtype,
            name="attention",
        )(x, mask_bias, deterministic=deterministic)
        x = AttentionOutput(
            hidden_size=self.hidden_size,
            dropout_rate=self.hidden_dropout,
            layer_norm_eps=self.layer_norm_eps,
            dtype=self.dtype,
            name="attention_output",
        )(ctx, x, deterministic=deterministic)
        x = FeedForward(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            activation=self.activation,
            dropout_rate=self.hidden_dropout,
            layer_norm_eps=self.layer_norm_eps,
            dtype=self.dtype,
            name="ffn",
        )(x, deterministic=deterministic)
        return x, probs


class ConnectionLayer(nn.Module):
    """Co-attention bridge between the streams (the "connect" in
    ``bert_base_6layer_6conect``).

    Bi-directional cross attention in the shared ``bi_hidden`` space:
    text queries attend image keys/values (context for the text stream) and
    image queries attend text keys/values (context for the image stream),
    each followed by its own output projection + residual + LN + FFN.

    This is the module the Pallas kernel (:mod:`..ops.coattention`) replaces on
    TPU; the XLA path here is the numerics reference for the kernel test.
    """

    hidden_size: int  # text stream width
    v_hidden_size: int  # visual stream width
    bi_hidden_size: int
    bi_num_heads: int
    intermediate_size: int  # text FFN width in the connection layer
    v_intermediate_size: int
    activation: str = "gelu"
    v_activation: str = "gelu"
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    use_pallas: bool = False
    pallas_interpret: bool = False
    kernel_mesh: Optional[Any] = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        v_hidden,  # (B, Nv, v_hidden)
        v_mask_bias,  # (B, 1, 1, Nv)
        t_hidden,  # (B, Nt, hidden)
        t_mask_bias,  # (B, 1, 1, Nt)
        deterministic: bool = True,
        need_probs: bool = True,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
        # Text queries over image keys/values → feeds the TEXT stream.
        t_ctx, probs_t2v = CrossAttention(
            bi_hidden_size=self.bi_hidden_size,
            num_heads=self.bi_num_heads,
            dropout_rate=self.attention_dropout,
            use_pallas=self.use_pallas,
            pallas_interpret=self.pallas_interpret,
            kernel_mesh=self.kernel_mesh,
            dtype=self.dtype,
            name="text_attends_image",
        )(t_hidden, v_hidden, v_mask_bias, deterministic=deterministic,
          need_probs=need_probs)
        # Image queries over text keys/values → feeds the IMAGE stream.
        v_ctx, probs_v2t = CrossAttention(
            bi_hidden_size=self.bi_hidden_size,
            num_heads=self.bi_num_heads,
            dropout_rate=self.attention_dropout,
            use_pallas=self.use_pallas,
            pallas_interpret=self.pallas_interpret,
            kernel_mesh=self.kernel_mesh,
            dtype=self.dtype,
            name="image_attends_text",
        )(v_hidden, t_hidden, t_mask_bias, deterministic=deterministic,
          need_probs=need_probs)

        v_hidden = AttentionOutput(
            hidden_size=self.v_hidden_size,
            dropout_rate=self.hidden_dropout,
            layer_norm_eps=self.layer_norm_eps,
            dtype=self.dtype,
            name="v_output",
        )(v_ctx, v_hidden, deterministic=deterministic)
        t_hidden = AttentionOutput(
            hidden_size=self.hidden_size,
            dropout_rate=self.hidden_dropout,
            layer_norm_eps=self.layer_norm_eps,
            dtype=self.dtype,
            name="t_output",
        )(t_ctx, t_hidden, deterministic=deterministic)

        v_hidden = FeedForward(
            hidden_size=self.v_hidden_size,
            intermediate_size=self.v_intermediate_size,
            activation=self.v_activation,
            dropout_rate=self.hidden_dropout,
            layer_norm_eps=self.layer_norm_eps,
            dtype=self.dtype,
            name="v_ffn",
        )(v_hidden, deterministic=deterministic)
        t_hidden = FeedForward(
            hidden_size=self.hidden_size,
            intermediate_size=self.intermediate_size,
            activation=self.activation,
            dropout_rate=self.hidden_dropout,
            layer_norm_eps=self.layer_norm_eps,
            dtype=self.dtype,
            name="t_ffn",
        )(t_hidden, deterministic=deterministic)

        return v_hidden, t_hidden, (probs_t2v, probs_v2t)
