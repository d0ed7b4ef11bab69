"""A decoder of compressed convolutional attention and top-1 experts behind
an MLP router (``model_type: zaya``; ZAYA1, arXiv:2511.17127): the fourth
served model of the ``generate`` task.

Every layer is an attention sublayer, then an expert sublayer. The layer
equations (the plain reference ``benchmark/reference/zaya.py`` states the
same ones, independently). ``x^ = RMSNorm(h)``, eps ``rms_norm_eps``, with
the sublayer's own scale.

merge      ``scale_residual_merge``: ``h <- s_l * h + u_l * f(RMSNorm(h))``
           for each sublayer ``f``, ``s`` and ``u`` learned vectors of the
           width; a last RMSNorm before the tied head (logits ``= E x^``).
CCA        ``c = [W_q x^ ; W_k x^]`` (``H`` query and ``H_kv`` key heads of
           ``d``), then ``ops/cca.py``: two causal convolutions over time
           (a depthwise one of two taps, then one of two taps that maps each
           head's ``d`` channels by a ``d x d`` matrix), the query-key mean
           of the pre-convolution values, ``q^ = sqrt(d) q / |q|``, ``k^ =
           tau k / |k|``, the rotary embedding on the first
           ``partial_rotary_factor * d`` channels of a head (``rope_theta``
           of ``rope_parameters["hybrid"]``). Values are *shifted*: ``v_t =
           [W_v1 x^_t ; W_v2 x^_{t-1}]``, key/value head 0 from this token,
           head 1 from the one before (``x^_{-1} = 0``). ``o_h =
           softmax_causal(q^_h k^_{h // (H / H_kv)}^T / sqrt(d)) v``;
           ``Attn = W_o concat_h(o_h)``.
experts    ``r_l = W_r x^``; depth averaging ``r~_l = r_l + gamma_l
           r~_{l-1}`` (the first layer has no ``r~_{l-1}``); ``z = W_3
           GELU(W_2 GELU(W_1 r~_l))`` over ``num_experts + 1`` choices, the
           last "no expert"; ``e = argmax(z + b)`` (``b`` the balancing
           bias: it picks, it does not weigh); ``w = softmax(z)_e``, not
           renormalised; ``y = w E_e(x^)``, ``E_e(x) = W_down(SiLU(W_gate
           x) * W_up x)``, and ``y = 0`` where ``e`` is "no expert". The
           router runs in float32 (``HIGHEST``), GELU exact.

Weights and matmul operands are bfloat16 (``dtype``); the residual stream,
norms, CCA's latent rows, the softmax and the router are float32.

Two entry points, under the step contract ``models/olmo_hybrid.py`` has.
Every layer's keys and values are paged (``[L, pages + 1, H_kv, page,
d]``: two heads of 128, a query group of four, 1 KB a token a layer in
bfloat16); what a sequence carries besides is its slot's (``state_layout``):
``cca_conv`` the last two rows of ``c`` (float32; they are all the
convolutions read of the past) and ``cca_prev`` the last normed input
``x^_{t-1}`` (float32; the value shift's). :func:`prefill_chunk` runs a
chunk of one sequence's prompt: ``ops/cca.py``'s kernel over the chunk's
window where ``use_pallas``, the keys and values written to the pages,
``ops/paged_attention.py``'s causal kernel over the pages so far, the
expert layer of ``ops/moe.py`` over the chunk. :func:`decode_step` runs
one token of every running sequence: each row with its own carried rows,
attention over the whole pool. Both return the head's output and ``moe``
[layers, 3] int32 (pairs computed, experts touched, the fullest expert's
pairs) with ``moe_skipped`` [layers] int32, the real token-layers routed
to "no expert".

Layers are unrolled in Python for the reason ``models/olmo_hybrid.py``
gives (a pool in a loop's carry is copied whole).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from vilbert_multitask_tpu.config import ZayaConfig
from vilbert_multitask_tpu.models.decoder import (
    SlotArray,
    StateLayout,
    _decode_attention,
    _head,
    _mm,
    _prefill_attention,
    _rms,
    _write_rows,
)
from vilbert_multitask_tpu.ops import cca, moe, paged_attention

__all__ = ["ZayaConfig", "param_shapes", "init_params", "state_layout",
           "kernels_on", "cca_layers", "prefill_chunk", "decode_step"]

# Tokens a prefill bucket must be a multiple of (beside the page size).
PREFILL_GRANULE = 1
HIGHEST = jax.lax.Precision.HIGHEST


def param_shapes(cfg: ZayaConfig) -> dict:
    """The served tree's shapes: ``layers`` a list, one dict a layer.
    ``wqk`` is ``W_q | W_k``, ``wv`` is ``W_v1 | W_v2``; ``conv0`` [tap,
    channel] (tap 0 reads this row), ``conv1`` [tap, head, d, d]; the
    router's last column is "no expert". The head is the embedding."""
    H, V, d = cfg.hidden_size, cfg.vocab_size, cfg.head_dim
    n, kv, C = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.channels
    E, W, R = cfg.num_experts, cfg.moe_intermediate_size, \
        cfg.router_hidden_size
    layer = {"attn_norm": (H,), "wqk": (H, C), "wv": (H, 2 * d),
             "conv0": (2, C), "conv1": (2, n + kv, d, d), "tau": (kv,),
             "wo": (n * d, H), "attn_res_scale": (H,),
             "attn_out_scale": (H,),
             "mlp_norm": (H,), "router": (H, R), "router_eda": (1,),
             "router_mlp1": (R, R), "router_mlp2": (R, R),
             "router_out": (R, E + 1), "router_bias": (E + 1,),
             "experts_gate_up": (E, H, 2 * W), "experts_down": (E, W, H),
             "moe_res_scale": (H,), "moe_out_scale": (H,)}
    return {"embed": (V, H),
            "layers": [dict(layer) for _ in range(cfg.num_hidden_layers)],
            "final_norm": (H,)}


def init_params(cfg: ZayaConfig, key, dtype=jnp.bfloat16) -> dict:
    """Random weights for tests and weightless boots: matrices N(0, 1/fan
    in) (the router's last map 2/fan in, so its choices differ token to
    token), the embedding N(0, 1/hidden) (it is the head too), norm and
    merge scales 1 + N(0, 0.1), ``conv0`` N(0, 0.5), the temperatures
    ``sqrt(d)`` (1 + N(0, 0.1)), ``gamma`` 0.5 + N(0, 0.1), the balancing
    bias N(0, 0.1)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        normal = jax.random.normal(k, shape)
        if name.endswith(("norm", "_scale")):
            leaf = 1.0 + 0.1 * normal
        elif name == "tau":
            leaf = math.sqrt(cfg.head_dim) * (1.0 + 0.1 * normal)
        elif name == "router_eda":
            leaf = 0.5 + 0.1 * normal
        elif name in ("router_bias",):
            leaf = 0.1 * normal
        elif name == "conv0":
            leaf = 0.5 * normal
        elif name == "embed":
            leaf = normal / math.sqrt(shape[-1])
        else:
            gain = 2.0 if name == "router_out" else 1.0
            leaf = normal * gain / math.sqrt(shape[-2])
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def kernels_on(cfg: ZayaConfig) -> bool:
    """Whether the step programs hold Pallas kernels (the chip's path)."""
    return cfg.use_pallas


def cca_layers(cfg: ZayaConfig) -> int:
    """Layers whose CCA preparation a token runs: what the engine's
    ``vmt_cca_*`` counters are reckoned from."""
    return cfg.num_hidden_layers


def state_layout(cfg: ZayaConfig, param_dtype: str) -> StateLayout:
    """What ``engine/seqstate.py`` allocates for this model: a slot holds
    every layer's two carried rows of CCA's latent channels, side by side
    in one row (``[c_{t-2} | c_{t-1}]``: a pair of rows would be laid in a
    tile of eight and re-laid every step), and its last normed input (both
    float32); every layer's keys and values are paged."""
    L = (cfg.num_hidden_layers,)
    return StateLayout(
        slot_arrays={"cca_conv": SlotArray(L, (2 * cfg.channels,),
                                           "float32"),
                     "cca_prev": SlotArray(L, (cfg.hidden_size,),
                                           "float32")},
        paged_layers=cfg.num_hidden_layers, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, dtype=param_dtype,
        query_group=cfg.num_attention_heads // cfg.num_key_value_heads)


# ----------------------------------------------------------- shared pieces
def _merge(h, f, lp, name):
    return (lp[name + "_res_scale"].astype(jnp.float32) * h
            + lp[name + "_out_scale"].astype(jnp.float32) * f)


def _prepare(cfg, lp, cos, sin, *, rows=None, tail=None, c=None):
    """``ops/cca.py`` over a chunk's latent rows ``c`` [T, C] after its
    carried ``tail`` [2, C], or over a decode step's ``rows`` (``c_t``,
    ``c_{t-1}``, ``c_{t-2}``, each [B, C]); the kernel where
    ``use_pallas``. Queries [N, H, d] and keys [N, H_kv, d] in the compute
    dtype."""
    dims = dict(heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, rotary_dim=cfg.rotary_dim)
    args = (lp["conv0"], lp["conv1"], lp["tau"], cos, sin)
    if not cfg.use_pallas:
        q, k = cca.cca_prep_jnp(*(rows or cca.prefill_rows(tail, c)), *args,
                                **dims)
    elif rows is None:
        q, k = cca.cca_prep_prefill(tail, c, *args, **dims,
                                    interpret=cfg.pallas_interpret)
    else:
        q, k = cca.cca_prep_rows(*rows, *args, **dims,
                                 interpret=cfg.pallas_interpret)
    N, d = q.shape[0], cfg.head_dim
    return q.reshape(N, -1, d), k.reshape(N, -1, d)


def _values(cfg, xn, prev, lp):
    """Shifted values [N, 2, d]: head 0 of each row's own normed input,
    head 1 of the row before's (``prev`` [N, H] float32 for each row)."""
    d = cfg.head_dim
    now = _mm(xn, lp["wv"][:, :d])
    before = _mm(prev, lp["wv"][:, d:])
    return jnp.stack([now, before], axis=1).astype(lp["wv"].dtype)


def _router(l, xn, lp, r_prev):
    """The router over normed rows ``xn`` [N, H] in float32: (choices [N,
    1] int32, ``num_experts`` being "no expert", their weights [N, 1], the
    state ``r~_l`` the next layer's router reads)."""
    def dense(x, name):
        return jnp.dot(x, lp[name].astype(jnp.float32), precision=HIGHEST)

    def gelu(x):
        return jax.nn.gelu(x, approximate=False)

    r = dense(xn, "router")
    if l > 0:
        r = r + lp["router_eda"].astype(jnp.float32)[0] * r_prev
    z = dense(gelu(dense(gelu(dense(r, "router_mlp1")), "router_mlp2")),
              "router_out")
    experts, weights = moe.route(
        z, 1, bias=lp["router_bias"].astype(jnp.float32), renormalize=False)
    return experts, weights, r


def _experts(cfg, l, h, lp, r_prev, real):
    """The expert sublayer over rows ``h`` [N, H]: (its output, the
    router's state ``r~_l`` for the next layer, the expert layer's three
    integers, the real rows routed to "no expert")."""
    xn = _rms(h, lp["mlp_norm"], cfg.rms_norm_eps)
    with jax.named_scope("zaya_router"):
        experts, weights, r = _router(l, xn, lp, r_prev)
        skipped = jnp.sum(real & (experts[:, 0] == cfg.num_experts)
                          ).astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        args = (xn, experts, weights, cfg.held, lp["experts_gate_up"],
                lp["experts_down"], real)
        if cfg.use_pallas:
            y, stats = moe.experts_forward(*args,
                                           interpret=cfg.pallas_interpret)
        else:
            y, stats = moe.experts_oracle(*args)
    return _merge(h, y, lp, "moe"), r, stats, skipped


def _close(out, stats, skipped):
    out["moe"] = jnp.stack(stats)
    out["moe_skipped"] = jnp.stack(skipped)
    return out


# ----------------------------------------------------------------- prefill
def prefill_chunk(cfg: ZayaConfig, params, state, tokens, slot, start,
                  length, page_row, logit_ids, *, attention_block: int = 2):
    """One chunk of one sequence's prompt; arguments as
    ``models/olmo_hybrid.py``'s. Returns the updated state and the head's
    output at row ``length - 1``, with ``moe`` and ``moe_skipped``."""
    T = tokens.shape[0]
    page = state["k"].shape[3]
    trash = state["k"].shape[1] - 1
    real = jnp.arange(T) < length
    fresh = start == 0
    x = params["embed"][tokens].astype(jnp.float32)
    cos, sin = cca.rotary_tables(start + jnp.arange(T), cfg.head_dim,
                                 cfg.rotary_dim, cfg.rope_theta)
    chunk_pages = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([page_row,
                         jnp.full((T // page,), trash, page_row.dtype)]),
        start // page, T // page)
    no_offset = jnp.zeros((T // page,), jnp.int32)

    def by_page(rows):
        return rows.reshape(T // page, page, *rows.shape[1:])

    def mine(whole):
        return jax.lax.dynamic_index_in_dim(whole, slot, 1, keepdims=False)

    L, C = cfg.num_hidden_layers, cfg.channels
    tails = jnp.where(fresh, 0.0, mine(state["cca_conv"])).reshape(L, 2, C)
    prevs = jnp.where(fresh, 0.0, mine(state["cca_prev"]))      # [L, H]
    k_pool, v_pool = state["k"], state["v"]
    new_tails, new_prevs, stats, skipped = [], [], [], []
    r = None
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("cca"):
            xn = _rms(x, lp["attn_norm"], cfg.rms_norm_eps)
            c = _mm(xn, lp["wqk"])                              # [T, C]
            q, k = _prepare(cfg, lp, cos, sin, tail=tails[l], c=c)
            v = _values(cfg, xn, jnp.concatenate([prevs[l][None], xn[:-1]]),
                        lp)
            window = jnp.concatenate([tails[l], c], axis=0)
            new_tails.append(jax.lax.dynamic_slice_in_dim(window, length, 2))
            last = jax.lax.dynamic_index_in_dim(
                xn, jnp.maximum(length - 1, 0), keepdims=False)
            new_prevs.append(jnp.where(length > 0, last, prevs[l]))
            k_pool = _write_rows(k_pool, l, by_page(k), chunk_pages,
                                 no_offset)
            v_pool = _write_rows(v_pool, l, by_page(v), chunk_pages,
                                 no_offset)
            if cfg.use_pallas:
                ctx = paged_attention.paged_prefill_attention(
                    q, k_pool, v_pool, l, page_row, start,
                    interpret=cfg.pallas_interpret)
            else:
                ctx = _prefill_attention(cfg, q, k_pool, v_pool, l, page_row,
                                         start, attention_block)
            x = _merge(x, _mm(ctx.reshape(T, -1), lp["wo"]), lp, "attn")
        x, r, s, n = _experts(cfg, l, x, lp, r, real)
        stats.append(s)
        skipped.append(n)
    last = jax.lax.dynamic_index_in_dim(x, jnp.maximum(length - 1, 0),
                                        keepdims=False)
    with jax.named_scope("head"):
        out = _head(_rms(last, params["final_norm"], cfg.rms_norm_eps),
                    params["embed"], logit_ids, tied=True)

    def put(whole, ones):
        return jax.lax.dynamic_update_index_in_dim(
            whole, jnp.stack(ones).astype(whole.dtype), slot, 1)

    state = dict(state, k=k_pool, v=v_pool,
                 cca_conv=put(state["cca_conv"],
                              [t.reshape(2 * C) for t in new_tails]),
                 cca_prev=put(state["cca_prev"], new_prevs),
                 token=state["token"].at[slot].set(out["token"]))
    return state, _close(out, stats, skipped)


# ------------------------------------------------------------------ decode
def decode_step(cfg: ZayaConfig, params, state, active, positions,
                write_page, page_slot, page_pos, pool_blocks, logit_ids, *,
                attention_block: int = 32):
    """One token of every running sequence among the first B slots;
    arguments as ``models/olmo_hybrid.py``'s. Returns the updated state and
    the head's output [B], with ``moe`` and ``moe_skipped``."""
    B = positions.shape[0]
    page = state["k"].shape[3]
    x = params["embed"][state["token"][:B]].astype(jnp.float32)
    offset = positions % page
    cos, sin = cca.rotary_tables(positions, cfg.head_dim, cfg.rotary_dim,
                                 cfg.rope_theta)
    k_pool, v_pool = state["k"], state["v"]
    conv, prev = state["cca_conv"], state["cca_prev"]
    stats, skipped = [], []
    r = None
    for l, lp in enumerate(params["layers"]):
        with jax.named_scope("cca"):
            # The slices are taken before the arrays go on to be updated
            # (``models/phi4flash.py`` says why the barrier is there).
            tail, old_x, conv, prev = jax.lax.optimization_barrier(
                (conv[l, :B], prev[l, :B], conv, prev))
            tail = tail.reshape(B, 2, cfg.channels)
            xn = _rms(x, lp["attn_norm"], cfg.rms_norm_eps)
            c = _mm(xn, lp["wqk"])                              # [B, C]
            q, k = _prepare(cfg, lp, cos, sin,
                            rows=(c, tail[:, 1], tail[:, 0]))
            v = _values(cfg, xn, old_x, lp)
            new_tail = jnp.where(active[:, None, None],
                                 jnp.stack([tail[:, 1], c], axis=1), tail)
            new_x = jnp.where(active[:, None], xn, old_x)
            conv = jax.lax.dynamic_update_slice(
                conv, new_tail.reshape(1, B, -1), (l, 0, 0))
            prev = jax.lax.dynamic_update_slice(prev, new_x[None], (l, 0, 0))
            k_pool = _write_rows(k_pool, l, k[:, None], write_page, offset)
            v_pool = _write_rows(v_pool, l, v[:, None], write_page, offset)
            pool_args = (q, k_pool, v_pool, l, positions, page_slot,
                         page_pos, pool_blocks, attention_block)
            if cfg.use_pallas:
                ctx = paged_attention.paged_decode_attention(
                    *pool_args, interpret=cfg.pallas_interpret)
            else:
                ctx = _decode_attention(cfg, *pool_args)
            x = _merge(x, _mm(ctx.reshape(B, -1), lp["wo"]), lp, "attn")
        x, r, s, n = _experts(cfg, l, x, lp, r, active)
        stats.append(s)
        skipped.append(n)
    with jax.named_scope("head"):
        out = _head(_rms(x, params["final_norm"], cfg.rms_norm_eps),
                    params["embed"], logit_ids, tied=True)
    token = jnp.where(active, out["token"], state["token"][:B])
    state = dict(state, k=k_pool, v=v_pool, cca_conv=conv, cca_prev=prev,
                 token=jax.lax.dynamic_update_slice_in_dim(
                     state["token"], token, 0, 0))
    return state, _close(out, stats, skipped)
