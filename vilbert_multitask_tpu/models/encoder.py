"""Two-stream encoder with interleaved co-attention.

The schedule is derived statically from ``t_biattention_id`` / ``v_biattention_id``
(config name ``bert_base_6layer_6conect``): with t ids (6..11) and v ids (0..5),

    text 0..5 → co-attn 0 → text 6 + vis 0 → co-attn 1 → ... → co-attn 5
    → vis 5 → text 11

i.e. the first six text layers run before the visual stream starts, then each
bridge interleaves one layer per stream, and each stream finishes its tail
after the last bridge. The loop is plain Python over a static schedule — under
``jit`` it traces once into a flat XLA graph (no dynamic control flow).

Reference capability: BertEncoder in the external ``vilbert`` package
(driven from worker.py:286-289); redesigned for XLA.
"""

from __future__ import annotations

from typing import TYPE_CHECKING, Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from vilbert_multitask_tpu.config import ViLBertConfig
from vilbert_multitask_tpu.models.layers import ConnectionLayer, TransformerLayer

if TYPE_CHECKING:
    from vilbert_multitask_tpu.parallel.ring import RingContext


class TwoStreamEncoder(nn.Module):
    """``ring_v`` routes VISUAL-stream self-attention through sequence-
    parallel ring attention (parallel/ring.py) when the region count clears
    the context's threshold — regions are the long axis (video frames,
    tiled detections); the text stream is capped at 38 tokens by the
    pipeline and always stays dense, as does the cross-stream bridge."""

    config: ViLBertConfig
    ring_v: Optional["RingContext"] = None
    # Mesh of a partitioned (multi-chip) program: the Pallas kernels then
    # run under shard_map (ops/coattention.py). Like ring_v, it cannot live
    # in ViLBertConfig — that tree is JSON-serializable checkpoint metadata.
    kernel_mesh: Optional[Any] = None
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        # Per-layer rematerialization: deterministic / need_probs are static
        # (they steer Python control flow inside the layers).
        t_layer_cls = TransformerLayer
        c_layer_cls = ConnectionLayer
        if cfg.remat:
            t_layer_cls = nn.remat(TransformerLayer, static_argnums=(3,))
            c_layer_cls = nn.remat(ConnectionLayer, static_argnums=(5, 6))
        self.t_layers = [
            t_layer_cls(
                hidden_size=cfg.hidden_size,
                num_heads=cfg.num_attention_heads,
                intermediate_size=cfg.intermediate_size,
                activation=cfg.hidden_act,
                hidden_dropout=cfg.hidden_dropout_prob,
                attention_dropout=cfg.attention_probs_dropout_prob,
                layer_norm_eps=cfg.layer_norm_eps,
                use_pallas=cfg.use_pallas_self_attention,
                pallas_interpret=cfg.pallas_interpret,
                kernel_mesh=self.kernel_mesh,
                dtype=self.dtype,
                name=f"t_layer_{i}",
            )
            for i in range(cfg.num_hidden_layers)
        ]
        self.v_layers = [
            t_layer_cls(
                hidden_size=cfg.v_hidden_size,
                num_heads=cfg.v_num_attention_heads,
                intermediate_size=cfg.v_intermediate_size,
                activation=cfg.v_hidden_act,
                hidden_dropout=cfg.v_hidden_dropout_prob,
                attention_dropout=cfg.v_attention_probs_dropout_prob,
                layer_norm_eps=cfg.layer_norm_eps,
                use_pallas=cfg.use_pallas_self_attention,
                pallas_interpret=cfg.pallas_interpret,
                kernel_mesh=self.kernel_mesh,
                ring=self.ring_v,
                dtype=self.dtype,
                name=f"v_layer_{i}",
            )
            for i in range(cfg.v_num_hidden_layers)
        ]
        self.c_layers = [
            c_layer_cls(
                hidden_size=cfg.hidden_size,
                v_hidden_size=cfg.v_hidden_size,
                bi_hidden_size=cfg.bi_hidden_size,
                bi_num_heads=cfg.bi_num_attention_heads,
                intermediate_size=cfg.intermediate_size,
                v_intermediate_size=cfg.v_intermediate_size,
                activation=cfg.hidden_act,
                v_activation=cfg.v_hidden_act,
                hidden_dropout=cfg.hidden_dropout_prob,
                attention_dropout=cfg.attention_probs_dropout_prob,
                layer_norm_eps=cfg.layer_norm_eps,
                use_pallas=cfg.use_pallas_coattention,
                pallas_interpret=cfg.pallas_interpret,
                kernel_mesh=self.kernel_mesh,
                dtype=self.dtype,
                name=f"c_layer_{i}",
            )
            for i in range(cfg.num_connection_layers)
        ]

    def __call__(
        self,
        t_hidden,
        v_hidden,
        t_mask_bias,
        v_mask_bias,
        *,
        deterministic: bool = True,
        collect_attention: bool = False,
    ):
        cfg = self.config
        attn_maps: List[Tuple] = []

        def stream(scope, layers, hidden, mask_bias):
            # jax.named_scope: profile metadata only (op_name prefixes a
            # device trace can be summed by); a run of layers between two
            # bridges shares one scope, each bridge has its own.
            with jax.named_scope(scope):
                for layer in layers:
                    hidden, _ = layer(hidden, mask_bias, deterministic)
            return hidden

        t_ptr = 0
        v_ptr = 0
        for c_idx, (v_stop, t_stop) in enumerate(
            zip(cfg.v_biattention_id, cfg.t_biattention_id)
        ):
            t_hidden = stream("text_stream", self.t_layers[t_ptr:t_stop],
                              t_hidden, t_mask_bias)
            v_hidden = stream("visual_stream", self.v_layers[v_ptr:v_stop],
                              v_hidden, v_mask_bias)
            t_ptr, v_ptr = max(t_ptr, t_stop), max(v_ptr, v_stop)
            with jax.named_scope(f"coattention_bridge_{c_idx}"):
                v_hidden, t_hidden, co_probs = self.c_layers[c_idx](
                    v_hidden, v_mask_bias, t_hidden, t_mask_bias,
                    deterministic, collect_attention,
                )
            if collect_attention:
                attn_maps.append(co_probs)

        v_hidden = stream("visual_stream", self.v_layers[v_ptr:],
                          v_hidden, v_mask_bias)
        t_hidden = stream("text_stream", self.t_layers[t_ptr:],
                          t_hidden, t_mask_bias)

        return t_hidden, v_hidden, attn_maps
