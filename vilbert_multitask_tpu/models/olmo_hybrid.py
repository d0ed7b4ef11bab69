"""A causal decoder whose layers alternate gated linear attention and full
attention (``model_type: olmo_hybrid``): the served model of the
``generate`` task.

The layer equations (the plain reference ``benchmark/reference/
olmo_hybrid.py`` states the same ones, independently):

block        ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``,
             ``MLP(h) = W_down(SiLU(W_gate h) * W_up h)``; a last RMSNorm
             before the untied head. The mixer reads the raw residual.
full layer   ``q, k, v = W_q x, W_k x, W_v x``; RMSNorm over the whole
             width of ``q`` and of ``k``; heads; causal softmax(``q k^T /
             sqrt(d)``) ``v``; ``W_o``. No rotary embedding (the source's
             ``rope_theta`` is null).
linear layer ``q~, k~, v~ = W_q x, W_k x, W_v x`` through a depthwise causal
             convolution of ``linear_conv_kernel_dim`` taps, then SiLU;
             ``q = l2norm(q~)/sqrt(d_k)``, ``k = l2norm(k~)``; ``beta = 2
             sigmoid(W_b x)`` (the 2 is ``linear_allow_neg_eigval``);
             ``log alpha = -exp(A_log) softplus(W_a x + dt_bias)``; the
             gated delta rule of ``ops/gated_delta.py``; output ``W_o(
             RMSNorm_head(o) * SiLU(W_g x))``.

Weights and matmul operands are bfloat16 (``dtype``); the residual stream,
norms, softmax, the gates and the recurrent state are float32.

Two entry points share every layer function. :func:`prefill_chunk` runs a
chunk of one sequence's prompt: the recurrent state through the chunked
scan, keys and values written to the sequence's pages, attention over the
pages written so far. :func:`decode_step` runs one token of every running
sequence: the rank-1 state update, one key/value row written, attention
over the whole pool under an ownership mask. Both attentions are the Pallas
kernels of ``ops/paged_attention.py`` where ``use_pallas_scan`` says
kernels are on, ``models/decoder.py``'s ``jax.numpy`` forms
(``_prefill_attention``, ``_decode_attention``) otherwise. Both work on the
*sequence state* of ``engine/seqstate.py`` (a dict of device arrays, donated
and updated in place; the key/value pools are ``[P, pages + 1, H, page,
D]``) and return the logits of the last real position only, as the arg-max
token, its logit and the logits of the ids asked for.

Layers of one period (``k`` linear layers, then a full one) are stacked on
a leading axis ``[periods, ...]``. The periods are walked in Python, not
``lax.scan``-ned: with the key/value pool in a loop's carry the compiler
copies the whole pool into and out of the loop on every call (2 GB each
way at the served size, seen in the v5e compiler's memory report), and a
``fori_loop`` of row writes does the same; unrolled, with one dynamic-
update-slice a row, every update is in place. The price is a program whose
size grows with depth.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from vilbert_multitask_tpu.config import OlmoHybridConfig
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
from vilbert_multitask_tpu.ops import gated_delta, paged_attention

__all__ = ["OlmoHybridConfig", "param_shapes", "init_params",
           "state_layout", "kernels_on", "prefill_chunk", "decode_step"]

# Tokens a prefill bucket must be a multiple of (beside the page size).
PREFILL_GRANULE = gated_delta.CHUNK


def kernels_on(cfg: OlmoHybridConfig) -> bool:
    """Whether the step programs hold Pallas kernels (the chip's path)."""
    return cfg.use_pallas_scan


def state_layout(cfg: OlmoHybridConfig, param_dtype: str) -> StateLayout:
    """What ``engine/seqstate.py`` allocates for this model: a slot holds
    every linear layer's recurrent state (float32) and convolution tail;
    the full layers' keys and values are paged."""
    lead = (cfg.periods, cfg.period - 1)
    return StateLayout(
        slot_arrays={
            "rec": SlotArray(lead, (cfg.linear_num_value_heads,
                                    cfg.linear_key_head_dim,
                                    cfg.linear_value_head_dim), "float32"),
            "conv": SlotArray(lead, (cfg.linear_conv_kernel_dim - 1,
                                     cfg.conv_width), param_dtype)},
        paged_layers=cfg.periods, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, dtype=param_dtype,
        query_group=cfg.num_attention_heads // cfg.num_key_value_heads)


def param_shapes(cfg: OlmoHybridConfig) -> dict:
    """The served tree's shapes; linear layers stacked ``[periods, linear
    layers a period, ...]``, full layers ``[periods, ...]``."""
    H, I, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    n, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    mlp = {"mlp_gate": (H, I), "mlp_up": (H, I), "mlp_down": (I, H),
           "mixer_norm": (H,), "mlp_norm": (H,)}
    linear = {"wq": (H, n * dk), "wk": (H, n * dk), "wv": (H, n * dv),
              "conv": (cfg.linear_conv_kernel_dim, cfg.conv_width),
              "wa": (H, n), "wb": (H, n), "A_log": (n,), "dt_bias": (n,),
              "wg": (H, n * dv), "o_norm": (dv,), "wo": (n * dv, H), **mlp}
    full = {"wq": (H, H), "wk": (H, H), "wv": (H, H), "wo": (H, H),
            "q_norm": (H,), "k_norm": (H,), **mlp}
    lead_l, lead_f = (cfg.periods, cfg.period - 1), (cfg.periods,)
    return {"embed": (V, H),
            "linear": {k: lead_l + s for k, s in linear.items()},
            "full": {k: lead_f + s for k, s in full.items()},
            "final_norm": (H,), "lm_head": (H, V)}


def init_params(cfg: OlmoHybridConfig, key, dtype=jnp.bfloat16) -> dict:
    """Random weights for tests and weightless boots: matrices N(0, 1/fan
    in), embedding N(0, 1), norm scales 1 + N(0, 0.1); the gate's ``A_log``
    and ``dt_bias`` drawn so that alpha spans about 0.9 to 0.999."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name.endswith("norm"):
            leaf = 1.0 + 0.1 * jax.random.normal(k, shape)
        elif name == "A_log":
            leaf = jnp.log(jax.random.uniform(k, shape, minval=1.0,
                                              maxval=4.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=math.log(1e-3), maxval=math.log(2.5e-2)))
            leaf = jnp.log(jnp.expm1(dt))  # softplus^-1
        elif name == "embed":
            leaf = jax.random.normal(k, shape)
        elif name == "conv":
            leaf = jax.random.normal(k, shape) * 0.5
        else:
            scale = 0.1 if name == "wa" else 1.0
            leaf = jax.random.normal(k, shape) * scale / math.sqrt(shape[-2])
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ----------------------------------------------------------- shared pieces
def _mlp(h, lp):
    gate = jax.nn.silu(_mm(h, lp["mlp_gate"])) * _mm(h, lp["mlp_up"])
    return _mm(gate, lp["mlp_down"])


def _close_block(cfg, x, mixed, lp):
    h = x + _rms(mixed, lp["mixer_norm"], cfg.rms_norm_eps)
    return h + _rms(_mlp(h, lp), lp["mlp_norm"], cfg.rms_norm_eps)


def _linear_inputs(cfg, x, lp):
    """What both paths compute of a linear layer before the convolution:
    the pre-convolution channels ``q~ | k~ | v~`` (compute dtype), log
    alpha and beta (float32, per head)."""
    pre = jnp.concatenate(
        [_mm(x, lp[w]) for w in ("wq", "wk", "wv")], axis=-1
    ).astype(lp["wq"].dtype)
    dt = jax.nn.softplus(_mm(x, lp["wa"]) + lp["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(lp["A_log"].astype(jnp.float32)) * dt
    beta = jax.nn.sigmoid(_mm(x, lp["wb"]))
    if cfg.linear_allow_neg_eigval:
        beta = 2.0 * beta
    return pre, g, beta


def _linear_heads(cfg, conved):
    """After the convolution: SiLU, split into heads, normalise q and k."""
    n, dk, dv = (cfg.linear_num_key_heads, cfg.linear_key_head_dim,
                 cfg.linear_value_head_dim)
    act = jax.nn.silu(conved.astype(jnp.float32))
    q, k, v = jnp.split(act, [n * dk, 2 * n * dk], axis=-1)
    lead = act.shape[:-1]
    q, k = q.reshape(*lead, n, dk), k.reshape(*lead, n, dk)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    return l2(q) / math.sqrt(dk), l2(k), v.reshape(*lead, n, dv)


def _linear_output(cfg, x, o, lp):
    n, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    gate = jax.nn.silu(_mm(x, lp["wg"])).reshape(*o.shape[:-2], n, dv)
    o = _rms(o, lp["o_norm"], cfg.rms_norm_eps) * gate
    return _mm(o.reshape(*o.shape[:-2], n * dv), lp["wo"])


def _full_qkv(cfg, x, fp):
    n, d = cfg.num_attention_heads, cfg.head_dim
    q = _rms(_mm(x, fp["wq"]), fp["q_norm"], cfg.rms_norm_eps)
    k = _rms(_mm(x, fp["wk"]), fp["k_norm"], cfg.rms_norm_eps)
    v = _mm(x, fp["wv"])
    dtype = fp["wq"].dtype
    return tuple(t.reshape(*x.shape[:-1], n, d).astype(dtype)
                 for t in (q, k, v))


def _period_layers(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


# ----------------------------------------------------------------- prefill
def _prefill_linear(cfg, x, lp, rec, tail, real, length):
    """One linear layer over a chunk [T, H]. ``rec`` [n, dk, dv], ``tail``
    [K-1, C] the last pre-convolution rows of the sequence so far."""
    pre, g, beta = _linear_inputs(cfg, x, lp)
    taps = cfg.linear_conv_kernel_dim
    window = jnp.concatenate([tail, pre], axis=0)
    T = x.shape[0]
    conved = sum(window[j:j + T].astype(jnp.float32)
                 * lp["conv"][j].astype(jnp.float32) for j in range(taps))
    # The rows of the last real tokens: row t of ``pre`` is row t + K-1 of
    # the window, so they start at ``length``.
    tail = jax.lax.dynamic_slice_in_dim(window, length, taps - 1, axis=0)
    q, k, v = _linear_heads(cfg, conved)
    # A padded token writes nothing and decays nothing.
    g = jnp.where(real[:, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    o, rec = gated_delta.gated_delta_chunked(
        q, k, v, g, beta, rec, use_pallas=cfg.use_pallas_scan,
        interpret=cfg.pallas_interpret)
    return _linear_output(cfg, x, o, lp), rec, tail


def prefill_chunk(cfg: OlmoHybridConfig, params, state, tokens, slot, start,
                  length, page_row, logit_ids, *, attention_block: int = 2):
    """One chunk of one sequence's prompt. ``tokens`` [T] (T a multiple of
    the page size and of the scan's chunk; rows from ``length`` on are
    padding), ``slot`` the sequence's state slot, ``start`` how many tokens
    went before (a multiple of the page size; 0 starts from a zero state),
    ``page_row`` [max pages] the sequence's pages (entries it has not
    reserved name the pool's last page, which belongs to nobody: padding
    is written there), ``logit_ids`` [n]. Returns the updated state and the
    head's output at row ``length - 1``."""
    T = tokens.shape[0]
    page = state["k"].shape[3]
    trash = state["k"].shape[1] - 1
    real = jnp.arange(T) < length
    x = params["embed"][tokens].astype(jnp.float32)
    fresh = start == 0
    rec = jax.lax.dynamic_index_in_dim(state["rec"], slot, 2, keepdims=False)
    conv = jax.lax.dynamic_index_in_dim(state["conv"], slot, 2,
                                        keepdims=False)
    rec = jnp.where(fresh, 0.0, rec)
    conv = jnp.where(fresh, 0, conv)
    chunk_pages = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([page_row,
                         jnp.full((T // page,), trash, page_row.dtype)]),
        start // page, T // page)
    no_offset = jnp.zeros((T // page,), jnp.int32)

    def by_page(rows):
        return rows.reshape(T // page, page, *rows.shape[1:])

    k_pool, v_pool = state["k"], state["v"]
    recs, convs = [], []
    for p in range(cfg.periods):
        for i in range(cfg.period - 1):
            lp = _period_layers(params["linear"], (p, i))
            mixed, r, c = _prefill_linear(cfg, x, lp, rec[p, i], conv[p, i],
                                          real, length)
            x = _close_block(cfg, x, mixed, lp)
            recs.append(r)
            convs.append(c)
        full = _period_layers(params["full"], p)
        q, k, v = _full_qkv(cfg, x, full)
        k_pool = _write_rows(k_pool, p, by_page(k), chunk_pages, no_offset)
        v_pool = _write_rows(v_pool, p, by_page(v), chunk_pages, no_offset)
        if cfg.use_pallas_scan:
            ctx = paged_attention.paged_prefill_attention(
                q, k_pool, v_pool, p, page_row, start,
                interpret=cfg.pallas_interpret)
        else:
            ctx = _prefill_attention(cfg, q, k_pool, v_pool, p, page_row,
                                     start, attention_block)
        x = _close_block(cfg, x, _mm(ctx.reshape(T, -1), full["wo"]), full)
    rec = jnp.stack(recs).reshape(rec.shape)
    conv = jnp.stack(convs).reshape(conv.shape)
    last = jax.lax.dynamic_index_in_dim(x, jnp.maximum(length - 1, 0),
                                        keepdims=False)
    out = _head(_rms(last, params["final_norm"], cfg.rms_norm_eps),
                params["lm_head"], logit_ids)

    def put(whole, one):
        return jax.lax.dynamic_update_index_in_dim(
            whole, one.astype(whole.dtype), slot, 2)

    state = dict(state, k=k_pool, v=v_pool, rec=put(state["rec"], rec),
                 conv=put(state["conv"], conv),
                 token=state["token"].at[slot].set(out["token"]))
    return state, out


# ------------------------------------------------------------------ decode
def _decode_linear(cfg, x, lp, rec, tail):
    """One linear layer, one token of each of B sequences. ``rec`` [B, n,
    dk, dv], ``tail`` [B, K-1, C]."""
    pre, g, beta = _linear_inputs(cfg, x, lp)
    window = jnp.concatenate([tail, pre[:, None]], axis=1)
    conved = jnp.einsum("bjc,jc->bc", window.astype(jnp.float32),
                        lp["conv"].astype(jnp.float32))
    q, k, v = _linear_heads(cfg, conved)
    o, rec = gated_delta.recurrent_step(rec, q, k, v, g, beta)
    return _linear_output(cfg, x, o, lp), rec, window[:, 1:]


def decode_step(cfg: OlmoHybridConfig, params, state, active, positions,
                write_page, page_slot, page_pos, pool_blocks, logit_ids, *,
                attention_block: int = 32):
    """One token of every running sequence among the first B slots: row b
    *is* slot b (slots are handed out lowest first, so a small B covers a
    small load), fed ``state["token"][b]`` at position ``positions[b]``.
    Where ``active[b]`` is false the slot's state is left as it was and
    ``write_page[b]`` names the pool's last page, which belongs to nobody.
    Nothing is gathered or scattered: the slots' state is a static slice,
    updated under the mask. ``write_page`` [B] is the page each new
    key/value row goes to, ``page_slot`` / ``page_pos`` [pages] say whose
    each page of the pool is (-1: nobody's) and which of its sequence's
    pages it is, ``pool_blocks`` how many blocks of ``attention_block``
    pages reach past the last page in use. Returns the updated state and
    the head's output [B]."""
    B = positions.shape[0]
    page = state["k"].shape[3]
    x = params["embed"][state["token"][:B]].astype(jnp.float32)
    offset = positions % page

    k_pool, v_pool = state["k"], state["v"]
    rec, conv = state["rec"], state["conv"]
    for p in range(cfg.periods):
        for i in range(cfg.period - 1):
            lp = _period_layers(params["linear"], (p, i))
            old_r, old_c = rec[p, i, :B], conv[p, i, :B]
            mixed, r, c = _decode_linear(cfg, x, lp, old_r, old_c)
            x = _close_block(cfg, x, mixed, lp)
            r = jnp.where(active[:, None, None, None], r, old_r)
            c = jnp.where(active[:, None, None], c.astype(conv.dtype), old_c)
            rec = jax.lax.dynamic_update_slice(
                rec, r[None, None], (p, i, 0, 0, 0, 0))
            conv = jax.lax.dynamic_update_slice(
                conv, c[None, None], (p, i, 0, 0, 0))
        full = _period_layers(params["full"], p)
        q, k, v = _full_qkv(cfg, x, full)
        k_pool = _write_rows(k_pool, p, k[:, None], write_page, offset)
        v_pool = _write_rows(v_pool, p, v[:, None], write_page, offset)
        pool_args = (q, k_pool, v_pool, p, positions, page_slot, page_pos,
                     pool_blocks, attention_block)
        if cfg.use_pallas_scan:
            ctx = paged_attention.paged_decode_attention(
                *pool_args, interpret=cfg.pallas_interpret)
        else:
            ctx = _decode_attention(cfg, *pool_args)
        x = _close_block(cfg, x, _mm(ctx.reshape(B, -1), full["wo"]), full)
    out = _head(_rms(x, params["final_norm"], cfg.rms_norm_eps),
                params["lm_head"], logit_ids)
    token = jnp.where(active, out["token"], state["token"][:B])
    state = dict(state, k=k_pool, v=v_pool, rec=rec, conv=conv,
                 token=jax.lax.dynamic_update_slice_in_dim(
                     state["token"], token, 0, 0))
    return state, out
