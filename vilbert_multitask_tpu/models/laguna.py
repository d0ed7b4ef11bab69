"""A causal decoder with sparse experts and windowed layers beside global
ones (``model_type: laguna``): the second served model of the ``generate``
task.

The layer equations (the plain reference ``benchmark/reference/laguna.py``
states the same ones, independently). ``x^ = RMSNorm(x)``, eps
``rms_norm_eps``.

block      pre-norm: ``h = x + Attn_l(RMSNorm(x))``, ``y = h +
           FFN_l(RMSNorm(h))``; a last RMSNorm before the untied head.
attention  of layer ``l``: ``H_l = num_attention_heads_per_layer[l]`` query
           heads (48 in a ``full_attention`` layer, 72 in a
           ``sliding_attention`` one), ``H_kv`` key/value heads of width
           ``d``, group ``H_l / H_kv``: ``q = W_q x^`` [H_l, d], ``k = W_k
           x^``, ``v = W_v x^`` [H_kv, d]; RMSNorm over ``d`` on each head
           of ``q`` and of ``k``; rotary embedding on ``q`` and ``k``;
           ``o_h = g_h softmax(q_h k_{h // group}^T / sqrt(d) + mask)
           v_{h // group}``, ``g = sigmoid(W_g x^)`` [H_l] (``gating:
           per-head``); ``Attn = W_o concat_h(o_h)``. The mask is causal;
           in a sliding layer also ``i - j < sliding_window``.
rotary     by layer type (``rope_parameters``), on the first
           ``partial_rotary_factor * d`` dimensions of a head, paired first
           half with second half (``rotate_half``); the rest pass. Sliding
           layers: ``rope_type: default``, ``1 / theta^(2i / r)``. Full
           layers: YaRN as ``transformers``' ``_compute_yarn_parameters``
           has it: the per-dimension blend of ``1 / theta^(2i / r)`` and
           that over ``factor``, by the linear ramp between the dimensions
           that ``beta_fast`` and ``beta_slow`` rotations in
           ``original_max_position_embeddings`` positions pick out
           (truncated to whole dimensions); cos and sin times
           ``attention_factor`` (``0.1 ln(factor) + 1`` where not given).
dense FFN  (``mlp_layer_types[l] == "dense"``) ``W_d(SiLU(W_g h^) * W_u
           h^)``, width ``intermediate_size``.
sparse FFN ``r = W_r h^`` [num_experts] in float32; ``s = softmax(r)``;
           ``T`` = the ``num_experts_per_tok`` largest of ``s``; ``w_e =
           moe_routed_scaling_factor * s_e / sum_{e' in T} s_e'``; ``FFN =
           E_shared(h^) + sum_{e in T} w_e E_e(h^)``, every ``E`` a
           SiLU-gated MLP. **Under the cut** (``cfg.held``) the sum runs
           over ``e in T`` whose weights are held here; ``T`` and ``w`` are
           taken over all ``num_experts``; what the other experts would
           have added is left out and that partial result goes on to the
           next layer (``ops/moe.py``).
head       logits over the ``vocab_size`` rows held here.

Weights and matmul operands are bfloat16 (``dtype``); the residual stream,
norms, rotary embedding, softmax, the router and the gates are float32.

Two entry points, under the step contract ``models/olmo_hybrid.py`` has.
:func:`prefill_chunk` runs a chunk of one sequence's prompt: a full layer
writes the chunk's keys and values to the sequence's pages and attends over
the pages so far (``ops/paged_attention.py``'s causal kernel where
``use_pallas``); a sliding layer attends over the window's keys before
the chunk, which the slot's *ring* holds, and the chunk itself, then leaves
the chunk's last ``sliding_window`` keys and values in the ring.
:func:`decode_step` runs one token of every running sequence: a full layer
through ``ops/paged_attention.py`` over the whole pool (grouped: a page's
``[H_kv, page, d]`` keys serve all ``H_l`` query heads), a sliding layer
over the slots' rings. The ring of a sliding layer is ``[slots, H_kv,
sliding_window, d]``: position ``p`` lives at row ``p % sliding_window``,
so a row is overwritten exactly when its key leaves the window, and a
sequence holds the same ring bytes whatever its length
(:func:`state_layout`). Both return the head's output at the last real
position and ``moe`` [sparse layers, 3] int32: pairs computed here, experts
touched, the fullest expert's pairs, a layer.

Layers are unrolled in Python for the reason ``models/olmo_hybrid.py``
gives (a pool in a loop's carry is copied whole); here they also differ in
shape layer to layer.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from vilbert_multitask_tpu.config import FULL_ATTENTION, LagunaConfig
from vilbert_multitask_tpu.models.decoder import (
    SlotArray,
    StateLayout,
    _decode_attention,
    _head,
    _mm,
    _prefill_attention,
    _ring_decode,
    _ring_prefill,
    _rms,
    _write_rows,
)
from vilbert_multitask_tpu.ops import moe, paged_attention

__all__ = ["LagunaConfig", "param_shapes", "init_params", "state_layout",
           "kernels_on", "rotary_frequencies", "prefill_chunk", "decode_step"]

# Tokens a prefill bucket must be a multiple of (beside the page size).
PREFILL_GRANULE = 1


def param_shapes(cfg: LagunaConfig) -> dict:
    """The served tree's shapes: ``layers`` a list, one dict a layer (the
    layers differ in heads and in the kind of FFN). The expert matrices
    hold the experts held here only; the router is ``num_experts`` wide."""
    H, V, d = cfg.hidden_size, cfg.vocab_size, cfg.head_dim
    kv, W = cfg.num_key_value_heads, cfg.moe_intermediate_size
    S, I = cfg.shared_expert_intermediate_size, cfg.intermediate_size
    held = cfg.held[1]
    layers = []
    for l in range(cfg.num_hidden_layers):
        n = cfg.num_attention_heads_per_layer[l]
        layer = {"attn_norm": (H,), "wq": (H, n * d), "wk": (H, kv * d),
                 "wv": (H, kv * d), "wg": (H, n), "wo": (n * d, H),
                 "q_norm": (d,), "k_norm": (d,), "mlp_norm": (H,)}
        if cfg.mlp_layer_types[l] == "dense":
            layer.update(mlp_gate=(H, I), mlp_up=(H, I), mlp_down=(I, H))
        else:
            layer.update(router=(H, cfg.num_experts),
                         experts_gate_up=(held, H, 2 * W),
                         experts_down=(held, W, H), shared_gate=(H, S),
                         shared_up=(H, S), shared_down=(S, H))
        layers.append(layer)
    return {"embed": (V, H), "layers": layers, "final_norm": (H,),
            "lm_head": (H, V)}


def init_params(cfg: LagunaConfig, key, dtype=jnp.bfloat16) -> dict:
    """Random weights for tests and weightless boots: matrices N(0, 1/fan
    in), embedding N(0, 1), norm scales 1 + N(0, 0.1)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            leaf = 1.0 + 0.1 * jax.random.normal(k, shape)
        elif name == "embed":
            leaf = jax.random.normal(k, shape)
        else:
            leaf = jax.random.normal(k, shape) / math.sqrt(shape[-2])
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def kernels_on(cfg: LagunaConfig) -> bool:
    """Whether the step programs hold Pallas kernels (the chip's path)."""
    return cfg.use_pallas


def state_layout(cfg: LagunaConfig, param_dtype: str) -> StateLayout:
    """What ``engine/seqstate.py`` allocates for this model: a slot holds
    every sliding layer's last ``sliding_window`` keys and values (a ring);
    the full layers' keys and values are paged."""
    ring = SlotArray((len(cfg.sliding_layers),),
                     (cfg.num_key_value_heads, cfg.sliding_window,
                      cfg.head_dim), param_dtype, ring=True)
    return StateLayout(
        slot_arrays={"ring_k": ring, "ring_v": ring},
        paged_layers=len(cfg.full_layers), kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, dtype=param_dtype,
        query_group=(cfg.num_attention_heads_per_layer[cfg.full_layers[0]]
                     // cfg.num_key_value_heads))


# ------------------------------------------------------------------ rotary
def rotary_frequencies(cfg: LagunaConfig, layer_type: str) -> tuple:
    """(inverse frequencies [r / 2] float32, the factor on cos and sin) of
    one layer type; ``r`` = the rotated dimensions of a head."""
    rope = cfg.rope[layer_type]
    r = int(cfg.head_dim * float(rope.get("partial_rotary_factor", 1.0)))
    base = float(rope["rope_theta"])
    plain = 1.0 / base ** (np.arange(0, r, 2, dtype=np.float64) / r)
    if rope["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0

    def dimension(rotations):
        return (r * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low, high = dimension(float(rope["beta_fast"])), dimension(
        float(rope["beta_slow"]))
    if rope.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, r - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(r // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp           # 1: the plain frequency, 0: interpolated
    blended = plain / factor * (1.0 - keep) + plain * keep
    return blended.astype(np.float32), float(scale)


def _rotate(cfg, layer_type, x, positions):
    """Rotary embedding of ``x`` [N, heads, d] (float32) at ``positions``
    [N]."""
    inv_freq, scale = rotary_frequencies(cfg, layer_type)
    half = inv_freq.shape[0]
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(angle) * scale)[:, None, :]
    sin = (jnp.sin(angle) * scale)[:, None, :]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


# ----------------------------------------------------------- shared pieces
def _gated_mlp(h, gate, up, down):
    return _mm(jax.nn.silu(_mm(h, gate)) * _mm(h, up), down)


def _qkv(cfg, l, xn, lp, positions):
    """Queries [N, H_l, d], keys and values [N, H_kv, d] (compute dtype,
    normed and rotated) and the output gate [N, H_l] (float32) of layer
    ``l`` from the normed input ``xn`` [N, H]."""
    n, kv, d = (cfg.num_attention_heads_per_layer[l],
                cfg.num_key_value_heads, cfg.head_dim)
    N = xn.shape[0]
    kind = cfg.layer_types[l]
    q = _rms(_mm(xn, lp["wq"]).reshape(N, n, d), lp["q_norm"],
             cfg.rms_norm_eps)
    k = _rms(_mm(xn, lp["wk"]).reshape(N, kv, d), lp["k_norm"],
             cfg.rms_norm_eps)
    v = _mm(xn, lp["wv"]).reshape(N, kv, d)
    q, k = _rotate(cfg, kind, q, positions), _rotate(cfg, kind, k, positions)
    gate = jax.nn.sigmoid(_mm(xn, lp["wg"]))
    dtype = lp["wq"].dtype
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), gate


def _attention_out(ctx, gate, lp):
    """``W_o`` of the gated heads: ``ctx`` [N, H_l, d] float32."""
    N = ctx.shape[0]
    return _mm((ctx * gate[..., None]).reshape(N, -1), lp["wo"])


def _ffn(cfg, l, hn, lp, real):
    """(FFN of layer ``l`` over the normed rows ``hn`` [N, H], the expert
    layer's three integers or None). ``real`` [N]: padding rows are routed
    nowhere."""
    if cfg.mlp_layer_types[l] == "dense":
        with jax.named_scope("dense_mlp"):
            return _gated_mlp(hn, lp["mlp_gate"], lp["mlp_up"],
                              lp["mlp_down"]), None
    with jax.named_scope("moe_route"):
        logits = jnp.dot(hn, lp["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        experts, weights = moe.route(logits, cfg.num_experts_per_tok,
                                     cfg.moe_routed_scaling_factor)
    with jax.named_scope("moe_experts"):
        args = (hn, experts, weights, cfg.held, lp["experts_gate_up"],
                lp["experts_down"], real)
        if cfg.use_pallas:
            routed, stats = moe.experts_forward(
                *args, interpret=cfg.pallas_interpret)
        else:
            routed, stats = moe.experts_oracle(*args)
    with jax.named_scope("moe_shared"):
        shared = _gated_mlp(hn, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
    return shared + routed, stats


def _close(out, moe_stats):
    out["moe"] = (jnp.stack(moe_stats) if moe_stats
                  else jnp.zeros((0, 3), jnp.int32))
    return out


# ----------------------------------------------------------------- prefill
def prefill_chunk(cfg: LagunaConfig, params, state, tokens, slot, start,
                  length, page_row, logit_ids, *, attention_block: int = 2):
    """One chunk of one sequence's prompt; arguments as
    ``models/olmo_hybrid.py``'s. Returns the updated state and the head's
    output at row ``length - 1``, with ``moe`` [sparse layers, 3]."""
    T = tokens.shape[0]
    page = state["k"].shape[3]
    trash = state["k"].shape[1] - 1
    real = jnp.arange(T) < length
    positions = start + jnp.arange(T)
    x = params["embed"][tokens].astype(jnp.float32)
    chunk_pages = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([page_row,
                         jnp.full((T // page,), trash, page_row.dtype)]),
        start // page, T // page)
    no_offset = jnp.zeros((T // page,), jnp.int32)

    def by_page(rows):
        return rows.reshape(T // page, page, *rows.shape[1:])

    k_pool, v_pool = state["k"], state["v"]
    ring_k, ring_v = state["ring_k"], state["ring_v"]
    moe_stats = []
    for l, lp in enumerate(params["layers"]):
        kind = cfg.layer_types[l]
        with jax.named_scope(kind):
            q, k, v, gate = _qkv(
                cfg, l, _rms(x, lp["attn_norm"], cfg.rms_norm_eps), lp,
                positions)
            if kind == FULL_ATTENTION:
                p = cfg.full_layers.index(l)
                k_pool = _write_rows(k_pool, p, by_page(k), chunk_pages,
                                     no_offset)
                v_pool = _write_rows(v_pool, p, by_page(v), chunk_pages,
                                     no_offset)
                if cfg.use_pallas:
                    ctx = paged_attention.paged_prefill_attention(
                        q, k_pool, v_pool, p, page_row, start,
                        interpret=cfg.pallas_interpret)
                else:
                    ctx = _prefill_attention(cfg, q, k_pool, v_pool, p,
                                             page_row, start, attention_block)
            else:
                ctx, ring_k, ring_v = _ring_prefill(
                    cfg.sliding_window, q, k, v, ring_k, ring_v,
                    cfg.sliding_layers.index(l), slot, start, length,
                    kernel=cfg.use_pallas, interpret=cfg.pallas_interpret)
            h = x + _attention_out(ctx, gate, lp)
        ffn, stats = _ffn(cfg, l, _rms(h, lp["mlp_norm"], cfg.rms_norm_eps),
                          lp, real)
        x = h + ffn
        if stats is not None:
            moe_stats.append(stats)
    last = jax.lax.dynamic_index_in_dim(x, jnp.maximum(length - 1, 0),
                                        keepdims=False)
    with jax.named_scope("head"):
        out = _head(_rms(last, params["final_norm"], cfg.rms_norm_eps),
                    params["lm_head"], logit_ids)
    state = dict(state, k=k_pool, v=v_pool, ring_k=ring_k, ring_v=ring_v,
                 token=state["token"].at[slot].set(out["token"]))
    return state, _close(out, moe_stats)


# ------------------------------------------------------------------ decode
def decode_step(cfg: LagunaConfig, params, state, active, positions,
                write_page, page_slot, page_pos, pool_blocks, logit_ids, *,
                attention_block: int = 32):
    """One token of every running sequence among the first B slots;
    arguments as ``models/olmo_hybrid.py``'s. Returns the updated state and
    the head's output [B], with ``moe`` [sparse layers, 3]."""
    B = positions.shape[0]
    page = state["k"].shape[3]
    x = params["embed"][state["token"][:B]].astype(jnp.float32)
    offset = positions % page

    k_pool, v_pool = state["k"], state["v"]
    ring_k, ring_v = state["ring_k"], state["ring_v"]
    moe_stats = []
    for l, lp in enumerate(params["layers"]):
        kind = cfg.layer_types[l]
        with jax.named_scope(kind):
            q, k, v, gate = _qkv(
                cfg, l, _rms(x, lp["attn_norm"], cfg.rms_norm_eps), lp,
                positions)
            if kind == FULL_ATTENTION:
                p = cfg.full_layers.index(l)
                k_pool = _write_rows(k_pool, p, k[:, None], write_page,
                                     offset)
                v_pool = _write_rows(v_pool, p, v[:, None], write_page,
                                     offset)
                pool_args = (q, k_pool, v_pool, p, positions, page_slot,
                             page_pos, pool_blocks, attention_block)
                if cfg.use_pallas:
                    ctx = paged_attention.paged_decode_attention(
                        *pool_args, interpret=cfg.pallas_interpret)
                else:
                    ctx = _decode_attention(cfg, *pool_args)
            else:
                ctx, ring_k, ring_v = _ring_decode(
                    cfg.sliding_window, q, k, v, ring_k, ring_v,
                    cfg.sliding_layers.index(l), positions, active,
                    kernel=cfg.use_pallas, interpret=cfg.pallas_interpret)
            h = x + _attention_out(ctx, gate, lp)
        ffn, stats = _ffn(cfg, l, _rms(h, lp["mlp_norm"], cfg.rms_norm_eps),
                          lp, active)
        x = h + ffn
        if stats is not None:
            moe_stats.append(stats)
    with jax.named_scope("head"):
        out = _head(_rms(x, params["final_norm"], cfg.rms_norm_eps),
                    params["lm_head"], logit_ids)
    token = jnp.where(active, out["token"], state["token"][:B])
    state = dict(state, k=k_pool, v=v_pool, ring_k=ring_k, ring_v=ring_v,
                 token=jax.lax.dynamic_update_slice_in_dim(
                     state["token"], token, 0, 0))
    return state, _close(out, moe_stats)
