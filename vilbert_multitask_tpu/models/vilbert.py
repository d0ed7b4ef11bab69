"""The flagship model: two-stream ViLBERT trunk + 9 task heads.

Reference capability: ``VILBertForVLTasks`` from the external ``vilbert``
package — constructed at worker.py:530-536, called at worker.py:286-289 with

    model(question, features, spatials, segment_ids, input_mask, image_mask,
          co_attention_mask, task_tokens, output_all_attention_masks=True)

returning the 10-tuple decoded at worker.py:295-386. This module reproduces
that call contract (as a typed :class:`ViLBertOutput`) on a TPU-first stack.
"""

from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from flax import struct

from vilbert_multitask_tpu.config import ViLBertConfig
from vilbert_multitask_tpu.models.embeddings import ImageEmbeddings, TextEmbeddings
from vilbert_multitask_tpu.models.encoder import TwoStreamEncoder
from vilbert_multitask_tpu.models.heads import (
    ImagePredictionHead,
    Pooler,
    SimpleClassifier,
    TextPredictionHead,
    fused_layer_norm,
)
from vilbert_multitask_tpu.models.layers import ACT
from vilbert_multitask_tpu.ops.attention import mask_to_bias


@struct.dataclass
class ViLBertOutput:
    """Typed view of the reference 10-tuple (worker.py:287-289).

    A registered pytree (flax.struct) so it can cross ``jit``/``pjit``
    boundaries and be sharded leaf-wise.
    """

    vil_prediction: jnp.ndarray  # (B, num_labels)        VQA
    vil_prediction_gqa: jnp.ndarray  # (B, gqa_num_labels) GQA
    vil_logit: jnp.ndarray  # (B, 1)                       retrieval alignment
    vil_binary_prediction: Optional[jnp.ndarray]  # (B//2, 2)  NLVR2 pairs
    vil_tri_prediction: jnp.ndarray  # (B, 3)              SNLI-VE
    vision_prediction: Optional[jnp.ndarray]  # (B, Nv, v_target) masked-region
    vision_logit: jnp.ndarray  # (B, Nv, 1)                grounding
    linguisic_prediction: Optional[jnp.ndarray]  # (B, Nt', vocab) masked-LM
    linguisic_logit: jnp.ndarray  # (B, Nt', 1)            token grounding
    attn_data_list: List[Any]  # per-bridge (text→image, image→text) probs

    def to_tuple(self) -> Tuple:
        """Reference positional order."""
        return (
            self.vil_prediction,
            self.vil_prediction_gqa,
            self.vil_logit,
            self.vil_binary_prediction,
            self.vil_tri_prediction,
            self.vision_prediction,
            self.vision_logit,
            self.linguisic_prediction,
            self.linguisic_logit,
            self.attn_data_list,
        )


class ViLBertModel(nn.Module):
    """Trunk: embeddings + two-stream encoder + poolers."""

    config: ViLBertConfig
    ring_v: Optional[Any] = None  # parallel.ring.RingContext — see encoder
    kernel_mesh: Optional[Any] = None  # jax Mesh — see encoder
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        self.embeddings = TextEmbeddings(cfg, dtype=self.dtype)
        self.v_embeddings = ImageEmbeddings(cfg, dtype=self.dtype)
        self.encoder = TwoStreamEncoder(cfg, ring_v=self.ring_v,
                                        kernel_mesh=self.kernel_mesh,
                                        dtype=self.dtype)
        self.t_pooler = Pooler(cfg.bi_hidden_size, dtype=self.dtype)
        self.v_pooler = Pooler(cfg.bi_hidden_size, dtype=self.dtype)

    def __call__(
        self,
        input_ids,  # (B, Nt) int32
        features,  # (B, Nv, v_feature_size)
        spatials,  # (B, Nv, 5)
        segment_ids,  # (B, Nt) int32
        input_mask,  # (B, Nt) {0,1}
        image_mask,  # (B, Nv) {0,1}
        task_ids=None,  # (B, 1) int32 when task_specific_tokens
        *,
        deterministic: bool = True,
        collect_attention: bool = False,
    ):
        cfg = self.config
        t_hidden = self.embeddings(
            input_ids, segment_ids, task_ids, deterministic=deterministic
        )
        if cfg.task_specific_tokens:
            input_mask = TextEmbeddings.extend_mask_for_task_token(input_mask)
        v_hidden = self.v_embeddings(features, spatials, deterministic=deterministic)

        t_bias = mask_to_bias(input_mask, self.dtype)
        v_bias = mask_to_bias(image_mask, self.dtype)

        t_seq, v_seq, attn_maps = self.encoder(
            t_hidden, v_hidden, t_bias, v_bias,
            deterministic=deterministic, collect_attention=collect_attention,
        )
        pooled_t = self.t_pooler(t_seq)
        pooled_v = self.v_pooler(v_seq)
        return t_seq, v_seq, pooled_t, pooled_v, attn_maps, input_mask


class ViLBertForVLTasks(nn.Module):
    """Trunk + all 9 heads; output order matches the reference 10-tuple.

    ``ring_v`` (parallel.ring.RingContext) opts the visual stream into
    sequence-parallel ring attention on the context's mesh — the
    long-context serving/training path. Dense and ring instances have
    identical param trees (checkpoints are interchangeable).
    ``kernel_mesh`` is the device mesh of a partitioned program, under
    which the Pallas kernels run through shard_map.
    """

    config: ViLBertConfig
    ring_v: Optional[Any] = None
    kernel_mesh: Optional[Any] = None
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        cfg = self.config
        self.bert = ViLBertModel(cfg, ring_v=self.ring_v,
                                 kernel_mesh=self.kernel_mesh,
                                 dtype=self.dtype)
        bi = cfg.bi_hidden_size
        self.vil_prediction = SimpleClassifier(
            bi * 2, cfg.num_labels, cfg.layer_norm_eps, dtype=self.dtype
        )
        self.vil_prediction_gqa = SimpleClassifier(
            bi * 2, cfg.gqa_num_labels, cfg.layer_norm_eps, dtype=self.dtype
        )
        self.vil_binary_prediction = SimpleClassifier(
            bi * 2, 2, cfg.layer_norm_eps, dtype=self.dtype
        )
        self.vil_logit = nn.Dense(1, dtype=self.dtype)
        self.vil_tri_prediction = nn.Dense(3, dtype=self.dtype)
        self.vision_logit = nn.Dense(1, dtype=self.dtype)
        self.linguisic_logit = nn.Dense(1, dtype=self.dtype)
        self.cls_text = TextPredictionHead(cfg, dtype=self.dtype)
        self.cls_image = ImagePredictionHead(cfg, dtype=self.dtype)
        self.head_dropout = nn.Dropout(0.1)

    def trunk(
        self,
        input_ids,
        features,
        spatials,
        segment_ids,
        input_mask,
        image_mask,
        co_attention_mask=None,  # accepted for contract parity; zeros in serving
        task_ids=None,
        *,
        deterministic: bool = True,
        output_all_attention_masks: bool = False,
    ):
        """Trunk-only apply target (``model.apply(..., method="trunk")``)
        for the engine's fused-head serving path: same positional contract
        as :meth:`__call__`, but stops at the pooled vectors — the nine
        heads run as ONE batched slab program outside the module (see
        :func:`fused_head_output`), so mixed-task chunks stop paying nine
        sequential small matmuls."""
        return self.bert(
            input_ids, features, spatials, segment_ids, input_mask,
            image_mask, task_ids,
            deterministic=deterministic,
            collect_attention=output_all_attention_masks,
        )

    def __call__(
        self,
        input_ids,
        features,
        spatials,
        segment_ids,
        input_mask,
        image_mask,
        co_attention_mask=None,  # accepted for contract parity; zeros in serving
        task_ids=None,
        *,
        deterministic: bool = True,
        output_all_attention_masks: bool = False,
        compute_pretraining_heads: bool = True,
    ) -> ViLBertOutput:
        """``compute_pretraining_heads=False`` skips the masked-LM and
        masked-region decoders — the widest matmuls in the head stack
        (Nt'×vocab and Nv×v_target) — which no serving decode reads
        (engine/decode.py); the reference computes them unconditionally
        every request (worker.py:287-289). Training keeps the default."""
        cfg = self.config
        t_seq, v_seq, pooled_t, pooled_v, attn_maps, _ = self.bert(
            input_ids, features, spatials, segment_ids, input_mask, image_mask,
            task_ids,
            deterministic=deterministic,
            collect_attention=output_all_attention_masks,
        )

        with jax.named_scope("task_heads"):
            if cfg.fusion_method == "mul":
                pooled = pooled_t * pooled_v
            elif cfg.fusion_method == "sum":
                pooled = pooled_t + pooled_v
            else:
                raise ValueError(f"unknown fusion_method {cfg.fusion_method}")
            pooled = self.head_dropout(pooled, deterministic=deterministic)

            vil_prediction = self.vil_prediction(pooled)
            vil_prediction_gqa = self.vil_prediction_gqa(pooled)
            vil_logit = self.vil_logit(pooled)
            vil_tri_prediction = self.vil_tri_prediction(pooled)

            # NLVR2: adjacent rows are the image pair for one example
            # (repeat-batching at engine/dispatch.py, mirroring worker.py:266-276).
            vil_binary_prediction = None
            if pooled.shape[0] % 2 == 0:
                paired = pooled.reshape(pooled.shape[0] // 2, -1)
                vil_binary_prediction = self.vil_binary_prediction(paired)
            elif self.is_initializing():
                # Materialize the head's params even when init ran with an odd
                # batch, so param existence never depends on the init shapes.
                self.vil_binary_prediction(
                    jnp.zeros((1, 2 * pooled.shape[-1]), self.dtype)
                )

            # Grounding heads: mask penalty keeps padded regions out of the softmax
            # (same -10000 fold-in the reference model applies).
            vision_logit = self.vision_logit(self.head_dropout(
                v_seq, deterministic=deterministic))
            vision_logit = vision_logit + mask_to_bias(image_mask, self.dtype)[:, 0, 0, :, None]
            linguisic_logit = self.linguisic_logit(self.head_dropout(
                t_seq, deterministic=deterministic))

            linguisic_prediction = vision_prediction = None
            if compute_pretraining_heads or self.is_initializing():
                linguisic_prediction = self.cls_text(
                    t_seq, self.bert.embeddings.word_table)
                vision_prediction = self.cls_image(v_seq)

        return ViLBertOutput(
            vil_prediction=vil_prediction,
            vil_prediction_gqa=vil_prediction_gqa,
            vil_logit=vil_logit,
            vil_binary_prediction=vil_binary_prediction,
            vil_tri_prediction=vil_tri_prediction,
            vision_prediction=vision_prediction,
            vision_logit=vision_logit,
            linguisic_prediction=linguisic_prediction,
            linguisic_logit=linguisic_logit,
            attn_data_list=attn_maps,
        )


@jax.named_scope("task_heads")
def fused_head_output(
    cfg: ViLBertConfig, slabs: dict, trunk_out, image_mask, dtype
) -> Tuple[ViLBertOutput, jnp.ndarray]:
    """All nine serving heads from one trunk pass, as batched slab matmuls.

    ``slabs`` is :func:`..models.heads.build_head_slabs` over the served
    tree (already dequantized when params are int8); ``trunk_out`` is the
    :meth:`ViLBertForVLTasks.trunk` 6-tuple. Reproduces the per-head
    ``__call__`` numerics (flax casts every kernel/bias to the compute
    dtype; LayerNorm statistics in f32 — :func:`fused_layer_norm`), so the
    returned :class:`ViLBertOutput` matches the module path to rounding:
    the stacked label logits slice back to each head's real width, the
    concat-fused pooled heads have independent output columns, and head
    dropout is a serving no-op (deterministic).

    Also returns the raw stacked ``(B, 2, max_label_width)`` label logits —
    the engine's decode bundle gathers per-row by task id from them (ONE
    softmax/top-k instead of two full-width passes); padded columns sit at
    ``PAD_LOGIT_BIAS`` and vanish in the softmax.
    """
    t_seq, v_seq, pooled_t, pooled_v, attn_maps, _ = trunk_out
    if cfg.fusion_method == "mul":
        pooled = pooled_t * pooled_v
    elif cfg.fusion_method == "sum":
        pooled = pooled_t + pooled_v
    else:
        raise ValueError(f"unknown fusion_method {cfg.fusion_method}")
    k = lambda name: slabs[name].astype(dtype)  # noqa: E731

    # Wide label pair (VQA + GQA): one batched classifier over a head axis.
    h = jnp.einsum("bi,kio->bko", pooled, k("label_d1_kernel"))
    h = ACT["gelu"](h + k("label_d1_bias")[None])
    h = fused_layer_norm(h, slabs["label_ln_scale"], slabs["label_ln_bias"],
                         cfg.layer_norm_eps)
    label_logits = (jnp.einsum("bko,kow->bkw", h, k("label_d2_kernel"))
                    + k("label_d2_bias")[None])
    vil_prediction = label_logits[:, 0, : cfg.num_labels]
    vil_prediction_gqa = label_logits[:, 1, : cfg.gqa_num_labels]

    # Tiny pooled heads, concat-fused: columns 0 = vil_logit, 1:4 = tri.
    small = pooled @ k("pooled_kernel") + k("pooled_bias")
    vil_logit = small[:, :1]
    vil_tri_prediction = small[:, 1:4]

    # NLVR2 paired head: even batches only (models/vilbert.py pairing).
    vil_binary_prediction = None
    if pooled.shape[0] % 2 == 0:
        paired = pooled.reshape(pooled.shape[0] // 2, -1)
        hb = ACT["gelu"](paired @ k("binary_d1_kernel")
                         + k("binary_d1_bias"))
        hb = fused_layer_norm(hb, slabs["binary_ln_scale"],
                              slabs["binary_ln_bias"], cfg.layer_norm_eps)
        vil_binary_prediction = (hb @ k("binary_d2_kernel")
                                 + k("binary_d2_bias"))

    # Per-token grounding heads, mask penalty folded in as in __call__.
    vision_logit = v_seq @ k("vision_kernel") + k("vision_bias")
    vision_logit = vision_logit + mask_to_bias(
        image_mask, dtype)[:, 0, 0, :, None]
    linguisic_logit = t_seq @ k("ling_kernel") + k("ling_bias")

    out = ViLBertOutput(
        vil_prediction=vil_prediction,
        vil_prediction_gqa=vil_prediction_gqa,
        vil_logit=vil_logit,
        vil_binary_prediction=vil_binary_prediction,
        vil_tri_prediction=vil_tri_prediction,
        vision_prediction=None,
        vision_logit=vision_logit,
        linguisic_prediction=None,
        linguisic_logit=linguisic_logit,
        attn_data_list=attn_maps,
    )
    return out, label_logits
