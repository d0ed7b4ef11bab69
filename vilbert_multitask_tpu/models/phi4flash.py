"""A decoder-hybrid-decoder (``model_type: phi4flash``; "SambaY",
arXiv:2507.06607): the third served model of the ``generate`` task.

Layers ``0 .. L/2 + 1`` are the *self-decoder*: Mamba mixers alternate with
differential attention, windowed up to layer ``L/2 - 1``; layer ``L/2`` is
a Mamba mixer whose scan output is the *memory*, layer ``L/2 + 1`` a
differential attention over the whole sequence, the only layer with a paged
cache. Layers ``L/2 + 2 .. L - 1`` are the *cross-decoder*: gated memory
units alternate with differential cross-attention, which has queries of its
own and reads the keys and values of layer ``L/2 + 1``. Nothing in the
cross-decoder leaves state behind.

The layer equations (the plain reference ``benchmark/reference/
phi4flash.py`` states the same ones, independently). ``x^ = LayerNorm(x)``
with weight and bias, eps ``layer_norm_eps``.

block      pre-norm: ``h = x + Mixer_l(LN(x))``, ``y = h + MLP(LN(h))``,
           ``MLP(u) = W_down(SiLU(g) * v)``, ``[g, v] = W_gate_up u``, no
           bias; a last LayerNorm; logits ``= E x^`` with ``E`` the
           embedding (tied), no head bias.
mamba      ``d_inner = expand * hidden``, ``d_state``, ``d_conv``,
           ``dt_rank``: ``[u, z] = W_in x^``; ``c_t = SiLU(b_c + sum_k
           w_c[k] * u_{t - d_conv + 1 + k})`` (causal, depthwise, rows
           before the sequence are 0); ``[delta, B_t, C_t] = W_x c_t``;
           ``dt_t = softplus(W_dt delta + b_dt)``; ``A = -exp(A_log)``; the
           selective scan of ``ops/selective_scan.py`` gives ``m_t``;
           ``Mixer = W_out(m_t * SiLU(z_t))``. **The last Mamba layer of
           the self-decoder also hands ``m_t`` on** to every gated memory
           unit of the same token.
gmu        ``Mixer = W_2(m_t * SiLU(W_1 x^_t))``, ``m_t`` the memory.
diff attn  ``[q, k, v] = W_qkv x^ + b`` (``n``, ``kv``, ``kv`` heads of
           ``d``). Heads pair, even with odd: query pair ``i`` is
           ``(q_2i, q_2i+1)``, key pair ``j`` is ``(k_2j, k_2j+1)`` and
           ``V_j = [v_2j | v_2j+1]`` (``2d`` wide); pair ``i`` reads ``j = i
           // (n / kv)``. ``A^s = softmax(q^s k^s^T / sqrt(d) + mask)``;
           ``o_i = (A^1 - lambda A^2) V_j``; ``lambda = exp(l_q1 . l_k1) -
           exp(l_q2 . l_k2) + lambda_init(l)``, ``lambda_init(l) = 0.8 - 0.6
           exp(-0.3 l)``; ``o~_i = (1 - lambda_init(l)) RMSNorm_2d(o_i) *
           gamma``; ``Mixer = W_o concat_i(o~_i) + b_o``. Mask: causal; in
           a window layer also ``i_pos - j_pos < sliding_window``. No
           positional embedding.
cross      ``q = W_q x^ + b`` only; keys and values are the full layer's,
           for the same sequence, up to the query's position; the layer's
           own lambda vectors, ``lambda_init(l)``, ``gamma``, ``W_o``.

**A pair shares a row.** A pair's two keys are kept side by side, ``K_j =
[k_2j | k_2j+1]``, so keys and values of a page are one shape, ``[kv / 2,
page, 2d]`` (128 lanes at the served size, where a lone 64-wide head would
fill half a vreg and be padded to a whole one in HBM), and the two queries
of a pair go in as ``[q^1 | 0]`` and ``[0 | q^2]``, each times ``sqrt 2``
because the shared attention code scales by the row's width: the zeros add
exact zeros to a score, so each row's softmax is its own map's, both maps
of a pair are computed against one fetch of a page, and differential
attention is grouped attention with ``2 n / kv`` query rows a key/value
head, through ``ops/paged_attention.py`` and ``models/decoder.py``'s ring as
they are; :func:`_differential` subtracts the maps' contexts afterwards.

Weights and matmul operands are bfloat16 (``dtype``); the residual stream,
norms, softmax, lambda, ``dt``, the scan and its state are float32.

Two entry points, under the step contract ``models/olmo_hybrid.py`` has.
:func:`prefill_chunk` runs a chunk of one sequence's prompt **through the
self-decoder only**: Mamba layers through the scan (the Pallas kernel where
``use_pallas``) from the slot's state, window layers over the slot's ring
and the chunk, the full layer writes the chunk's pages and attends over the
pages so far. Where the chunk ends the prompt (``final``) the cross-decoder
and the head run over its last real row alone, with that row's memory and
the full layer's pages, under a ``lax.cond`` (a chunk that yields no token
does not read their weights); that is exact, since no later position reads
a cross-decoder activation of an earlier one. :func:`decode_step` runs one
token of every running sequence through all the layers; the full layer and
every cross layer walk the one pool.

Layers are unrolled in Python for the reason ``models/olmo_hybrid.py``
gives (a pool in a loop's carry is copied whole).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from vilbert_multitask_tpu.config import Phi4FlashConfig
from vilbert_multitask_tpu.models.decoder import (
    SlotArray,
    StateLayout,
    _decode_attention,
    _head,
    _layer_norm,
    _mm,
    _prefill_attention,
    _ring_decode,
    _ring_prefill,
    _rms,
    _write_rows,
)
from vilbert_multitask_tpu.ops import paged_attention, selective_scan

__all__ = ["Phi4FlashConfig", "param_shapes", "init_params", "state_layout",
           "kernels_on", "step_work", "prefill_chunk", "decode_step"]

# Tokens a prefill bucket must be a multiple of (beside the page size).
PREFILL_GRANULE = 1
# ``prefill_chunk`` is told whether its chunk ends the prompt (``final``).
PREFILL_SPLIT = True


def param_shapes(cfg: Phi4FlashConfig) -> dict:
    """The served tree's shapes: ``layers`` a list, one dict a layer (the
    kinds differ). ``A_log`` is ``[d_state, d_inner]``, channels last. The
    head is the embedding."""
    H, I, V, d = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.head_dim)
    n, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    Ci, N, K, R = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_d_conv, cfg.dt_rank
    block = {"mixer_norm": (H,), "mixer_norm_bias": (H,), "mlp_norm": (H,),
             "mlp_norm_bias": (H,), "mlp_gate_up": (H, 2 * I),
             "mlp_down": (I, H)}
    out = {"wo": (n * d, H), "bo": (H,), "lambda_q1": (d,),
           "lambda_k1": (d,), "lambda_q2": (d,), "lambda_k2": (d,),
           "sub_norm": (2 * d,)}
    attention = {"wqkv": (H, (n + 2 * kv) * d), "bqkv": ((n + 2 * kv) * d,),
                 **out}
    mixers = {
        "mamba": {"in_proj": (H, 2 * Ci), "conv_w": (K, Ci), "conv_b": (Ci,),
                  "x_proj": (Ci, R + 2 * N), "dt_proj": (R, Ci),
                  "dt_bias": (Ci,), "A_log": (N, Ci), "D": (Ci,),
                  "out_proj": (Ci, H)},
        "window": attention, "full": attention,
        "gmu": {"gmu_in": (H, Ci), "gmu_out": (Ci, H)},
        "cross": {"wq": (H, n * d), "bq": (n * d,), **out}}
    return {"embed": (V, H),
            "layers": [{**mixers[kind], **block} for kind in cfg.layer_kinds],
            "final_norm": (H,), "final_norm_bias": (H,)}


def init_params(cfg: Phi4FlashConfig, key, dtype=jnp.bfloat16) -> dict:
    """Random weights for tests and weightless boots: matrices N(0, 1/fan
    in), the embedding N(0, 1/hidden) (it is the head too: logits of unit
    spread), norm scales and ``D`` 1 + N(0, 0.1), biases N(0, 0.02), the
    lambda vectors N(0, 0.1); ``A_log`` the logarithm of 1 .. d_state a
    channel and ``dt_bias`` such that ``dt`` lies in 0.001 .. 0.1 (as Mamba
    initialises both)."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        k = jax.random.fold_in(key, i)
        if name.endswith("norm") or name == "D":
            leaf = 1.0 + 0.1 * jax.random.normal(k, shape)
        elif name == "A_log":
            leaf = jnp.broadcast_to(
                jnp.log(jnp.arange(1.0, shape[0] + 1))[:, None], shape)
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=math.log(1e-3), maxval=math.log(1e-1)))
            leaf = jnp.log(jnp.expm1(dt))  # softplus^-1
        elif name.startswith("lambda"):
            leaf = 0.1 * jax.random.normal(k, shape)
        elif len(shape) == 1:
            leaf = 0.02 * jax.random.normal(k, shape)
        elif name == "embed":
            leaf = jax.random.normal(k, shape) / math.sqrt(shape[-1])
        else:
            leaf = jax.random.normal(k, shape) / math.sqrt(shape[-2])
        out.append(leaf.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def kernels_on(cfg: Phi4FlashConfig) -> bool:
    """Whether the step programs hold Pallas kernels (the chip's path)."""
    return cfg.use_pallas


def state_layout(cfg: Phi4FlashConfig, param_dtype: str) -> StateLayout:
    """What ``engine/seqstate.py`` allocates for this model: a slot holds
    every Mamba layer's state ``[d_state, d_inner]`` (float32) with the last
    ``d_conv - 1`` rows into its convolution, and every window layer's last
    ``sliding_window`` key pairs and values (a ring); one layer is paged,
    whatever the depth, in pairs: ``kv / 2`` rows of ``2 d``, each read by
    ``2 n / kv`` query rows (both maps of the ``n / kv`` query pairs)."""
    pairs, width = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
    mamba = (len(cfg.layers_of("mamba")),)
    ring = SlotArray((len(cfg.layers_of("window")),),
                     (pairs, cfg.sliding_window, width), param_dtype,
                     ring=True)
    return StateLayout(
        slot_arrays={
            "ssm": SlotArray(mamba, (cfg.mamba_d_state, cfg.d_inner),
                             "float32"),
            "conv": SlotArray(mamba, (cfg.mamba_d_conv - 1, cfg.d_inner),
                              param_dtype),
            "ring_k": ring, "ring_v": ring},
        paged_layers=1, kv_heads=pairs, head_dim=width, dtype=param_dtype,
        query_group=cfg.num_attention_heads // pairs)


def step_work(cfg: Phi4FlashConfig) -> dict:
    """What the engine's counters reckon a step's work from: the layers a
    token's scan (or step) runs, and the layers that walk the one pool in a
    decode step (the full layer and every cross layer)."""
    return {"ssm_layers": len(cfg.layers_of("mamba")),
            "pool_readers": len(cfg.layers_of("full", "cross"))}


# ----------------------------------------------------------- shared pieces
def _ln(cfg, x, lp, name):
    return _layer_norm(x, lp[name], lp[name + "_bias"], cfg.layer_norm_eps)


def _close_block(cfg, x, mixed, lp):
    """``h = x + mixed``, ``y = h + MLP(LN(h))``."""
    h = x + mixed
    with jax.named_scope("mlp"):
        gu = _mm(_ln(cfg, h, lp, "mlp_norm"), lp["mlp_gate_up"])
        I = gu.shape[-1] // 2
        return h + _mm(jax.nn.silu(gu[..., :I]) * gu[..., I:],
                       lp["mlp_down"])


def _bias(lp, name):
    return lp[name].astype(jnp.float32)


def _pair_queries(cfg, q, dtype):
    """Queries ``q`` [N, n * d] (float32) as the rows the shared attention
    code wants: [N, n, 2 d], key pair ``j``'s ``2 n / kv`` rows together,
    first map ``[q^1 | 0]`` of its query pairs, then second map ``[0 |
    q^2]``; times ``sqrt 2`` (module text)."""
    N, d = q.shape[0], cfg.head_dim
    pairs = cfg.num_key_value_heads // 2
    group = cfg.num_attention_heads // cfg.num_key_value_heads
    q = q.reshape(N, pairs, group, 2, d) * math.sqrt(2.0)
    zero = jnp.zeros((N, pairs, group, d), q.dtype)
    first = jnp.concatenate([q[:, :, :, 0], zero], axis=-1)
    second = jnp.concatenate([zero, q[:, :, :, 1]], axis=-1)
    return jnp.stack([first, second], axis=2).reshape(
        N, cfg.num_attention_heads, 2 * d).astype(dtype)


def _lambda(l, lp):
    """(lambda, lambda_init) of layer ``l``."""
    init = 0.8 - 0.6 * math.exp(-0.3 * l)
    dot = lambda a, b: jnp.sum(lp[a].astype(jnp.float32)
                               * lp[b].astype(jnp.float32))
    return (jnp.exp(dot("lambda_q1", "lambda_k1"))
            - jnp.exp(dot("lambda_q2", "lambda_k2")) + init), init


def _differential(cfg, l, lp, ctx):
    """The mixer's output from both maps' contexts ``ctx`` [N, n, 2 d]
    (float32, rows as :func:`_pair_queries` laid them)."""
    N = ctx.shape[0]
    lam, init = _lambda(l, lp)
    ctx = ctx.reshape(N, cfg.num_key_value_heads // 2, 2, -1,
                      2 * cfg.head_dim)
    o = ctx[:, :, 0] - lam * ctx[:, :, 1]               # [N, pairs, group, 2d]
    o = _rms(o, lp["sub_norm"], cfg.layer_norm_eps) * (1.0 - init)
    return _mm(o.reshape(N, -1), lp["wo"]) + _bias(lp, "bo")


def _qkv(cfg, xn, lp):
    """A self-attention layer's query rows [N, n, 2 d], key pairs and values
    [N, kv / 2, 2 d] (compute dtype)."""
    N, d = xn.shape[0], cfg.head_dim
    n, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    dtype = lp["wqkv"].dtype
    qkv = _mm(xn, lp["wqkv"]) + _bias(lp, "bqkv")
    q, k, v = jnp.split(qkv, [n * d, (n + kv) * d], axis=-1)
    return (_pair_queries(cfg, q, dtype),
            k.reshape(N, kv // 2, 2 * d).astype(dtype),
            v.reshape(N, kv // 2, 2 * d).astype(dtype))


def _cross_queries(cfg, xn, lp):
    return _pair_queries(cfg, _mm(xn, lp["wq"]) + _bias(lp, "bq"),
                         lp["wq"].dtype)


def _mamba_in(xn, lp):
    """``u`` (compute dtype: what the convolution reads and its tail
    keeps) and ``z`` (float32) of the normed rows."""
    uz = _mm(xn, lp["in_proj"])
    Ci = uz.shape[-1] // 2
    return uz[..., :Ci].astype(lp["in_proj"].dtype), uz[..., Ci:]


def _scan_inputs(cfg, conved, lp):
    """``c``, ``dt``, ``B``, ``C``, ``A``, ``D`` (float32) from the
    convolution's output (before its bias)."""
    N = cfg.mamba_d_state
    c = jax.nn.silu(conved + _bias(lp, "conv_b"))
    dbc = _mm(c, lp["x_proj"])
    delta, B, C = jnp.split(dbc, [cfg.dt_rank, cfg.dt_rank + N], axis=-1)
    dt = jax.nn.softplus(_mm(delta, lp["dt_proj"]) + _bias(lp, "dt_bias"))
    return c, dt, B, C, -jnp.exp(_bias(lp, "A_log")), _bias(lp, "D")


def _mamba_out(m, z, lp):
    return _mm(m * jax.nn.silu(z), lp["out_proj"])


def _gmu(xn, memory, lp):
    return _mm(memory * jax.nn.silu(_mm(xn, lp["gmu_in"])), lp["gmu_out"])


# ----------------------------------------------------------------- prefill
def _mamba_prefill(cfg, xn, lp, h, tail, real, length):
    """A Mamba layer over a chunk [T, H] from the slot's state ``h`` [N, Ci]
    and ``tail`` [K - 1, Ci]. Returns the mixer's output, the scan's output
    ``m`` [T, Ci] and the state and tail after the last real row."""
    u, z = _mamba_in(xn, lp)
    T, taps = xn.shape[0], cfg.mamba_d_conv
    window = jnp.concatenate([tail, u], axis=0)
    conved = sum(window[j:j + T].astype(jnp.float32)
                 * lp["conv_w"][j].astype(jnp.float32) for j in range(taps))
    # Row t of ``u`` is row t + K - 1 of the window: the last real rows
    # start at ``length``.
    tail = jax.lax.dynamic_slice_in_dim(window, length, taps - 1, axis=0)
    c, dt, B, C, A, D = _scan_inputs(cfg, conved, lp)
    # A padded token writes nothing and decays nothing.
    dt = jnp.where(real[:, None], dt, 0.0)
    if cfg.use_pallas:
        m, h = selective_scan.selective_scan(
            c, dt, B, C, A, D, h, interpret=cfg.pallas_interpret)
    else:
        m, h = selective_scan.selective_scan_jnp(c, dt, B, C, A, D, h)
    return _mamba_out(m, z, lp), m, h, tail


def _cross_decoder(cfg, params, x, memory, k_pool, v_pool, page_row,
                   position, logit_ids, attention_block):
    """The cross-decoder and the head over one row ``x`` [1, H] at
    ``position``, with its ``memory`` [1, Ci] and the full layer's pages."""
    for l in range(cfg.self_decoder_layers, cfg.num_hidden_layers):
        lp = params["layers"][l]
        xn = _ln(cfg, x, lp, "mixer_norm")
        if cfg.layer_kinds[l] == "gmu":
            with jax.named_scope("gmu"):
                mixed = _gmu(xn, memory, lp)
        else:
            with jax.named_scope("diff_attention_cross"):
                ctx = _prefill_attention(
                    cfg, _cross_queries(cfg, xn, lp), k_pool, v_pool, 0,
                    page_row, position, attention_block)
                mixed = _differential(cfg, l, lp, ctx)
        x = _close_block(cfg, x, mixed, lp)
    with jax.named_scope("head"):
        return _head(_layer_norm(x[0], params["final_norm"],
                                 params["final_norm_bias"],
                                 cfg.layer_norm_eps),
                     params["embed"], logit_ids, tied=True)


def prefill_chunk(cfg: Phi4FlashConfig, params, state, tokens, slot, start,
                  length, page_row, logit_ids, *, final,
                  attention_block: int = 2):
    """One chunk of one sequence's prompt; arguments as
    ``models/olmo_hybrid.py``'s, and ``final``: whether the chunk ends the
    prompt. Returns the updated state and, where ``final``, the head's
    output at row ``length - 1`` (zeros otherwise: nobody reads them)."""
    T = tokens.shape[0]
    page = state["k"].shape[3]
    trash = state["k"].shape[1] - 1
    real = jnp.arange(T) < length
    fresh = start == 0
    x = params["embed"][tokens].astype(jnp.float32)
    chunk_pages = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([page_row,
                         jnp.full((T // page,), trash, page_row.dtype)]),
        start // page, T // page)
    no_offset = jnp.zeros((T // page,), jnp.int32)

    def by_page(rows):
        return rows.reshape(T // page, page, *rows.shape[1:])

    def mine(whole):
        return jax.lax.dynamic_index_in_dim(whole, slot, 1, keepdims=False)

    ssm = jnp.where(fresh, 0.0, mine(state["ssm"]))
    conv = jnp.where(fresh, 0, mine(state["conv"]))
    k_pool, v_pool = state["k"], state["v"]
    ring_k, ring_v = state["ring_k"], state["ring_v"]
    ssms, convs, memory = [], [], None
    for l in range(cfg.self_decoder_layers):
        lp, kind = params["layers"][l], cfg.layer_kinds[l]
        xn = _ln(cfg, x, lp, "mixer_norm")
        if kind == "mamba":
            with jax.named_scope("mamba"):
                i = len(ssms)
                mixed, memory, h, tail = _mamba_prefill(
                    cfg, xn, lp, ssm[i], conv[i], real, length)
                ssms.append(h)
                convs.append(tail)
        elif kind == "window":
            with jax.named_scope("diff_attention_window"):
                q, k, v = _qkv(cfg, xn, lp)
                ctx, ring_k, ring_v = _ring_prefill(
                    cfg.sliding_window, q, k, v, ring_k, ring_v,
                    cfg.layers_of("window").index(l), slot, start, length,
                    kernel=cfg.use_pallas, interpret=cfg.pallas_interpret)
                mixed = _differential(cfg, l, lp, ctx)
        else:
            with jax.named_scope("diff_attention_full"):
                q, k, v = _qkv(cfg, xn, lp)
                k_pool = _write_rows(k_pool, 0, by_page(k), chunk_pages,
                                     no_offset)
                v_pool = _write_rows(v_pool, 0, by_page(v), chunk_pages,
                                     no_offset)
                if cfg.use_pallas:
                    ctx = paged_attention.paged_prefill_attention(
                        q, k_pool, v_pool, 0, page_row, start,
                        interpret=cfg.pallas_interpret)
                else:
                    ctx = _prefill_attention(cfg, q, k_pool, v_pool, 0,
                                             page_row, start, attention_block)
                mixed = _differential(cfg, l, lp, ctx)
        x = _close_block(cfg, x, mixed, lp)

    row = jnp.maximum(length - 1, 0)
    last = jax.lax.dynamic_slice_in_dim(x, row, 1)
    memory = jax.lax.dynamic_slice_in_dim(memory, row, 1)
    n = logit_ids.shape[0]
    out = jax.lax.cond(
        final,
        lambda: _cross_decoder(cfg, params, last, memory, k_pool, v_pool,
                               page_row, start + row, logit_ids,
                               attention_block),
        lambda: {"token": jnp.zeros((), jnp.int32),
                 "token_logit": jnp.zeros((), jnp.float32),
                 "logits": jnp.zeros((n,), jnp.float32)})

    def put(whole, ones):
        return jax.lax.dynamic_update_index_in_dim(
            whole, jnp.stack(ones).astype(whole.dtype), slot, 1)

    state = dict(state, k=k_pool, v=v_pool, ring_k=ring_k, ring_v=ring_v,
                 ssm=put(state["ssm"], ssms), conv=put(state["conv"], convs),
                 token=state["token"].at[slot].set(out["token"]))
    return state, out


# ------------------------------------------------------------------ decode
def _mamba_decode(cfg, xn, lp, h, tail):
    """A Mamba layer, one token of each of B sequences. ``h`` [B, N, Ci],
    ``tail`` [B, K - 1, Ci]."""
    u, z = _mamba_in(xn, lp)
    window = jnp.concatenate([tail, u[:, None]], axis=1)
    conved = jnp.einsum("bjc,jc->bc", window.astype(jnp.float32),
                        lp["conv_w"].astype(jnp.float32))
    c, dt, B, C, A, D = _scan_inputs(cfg, conved, lp)
    m, h = selective_scan.selective_step(h, c, dt, B, C, A, D)
    return _mamba_out(m, z, lp), m, h, window[:, 1:]


def decode_step(cfg: Phi4FlashConfig, params, state, active, positions,
                write_page, page_slot, page_pos, pool_blocks, logit_ids, *,
                attention_block: int = 32):
    """One token of every running sequence among the first B slots;
    arguments as ``models/olmo_hybrid.py``'s. Returns the updated state and
    the head's output [B]."""
    B = positions.shape[0]
    page = state["k"].shape[3]
    x = params["embed"][state["token"][:B]].astype(jnp.float32)
    offset = positions % page

    def over_pool(q):
        pool_args = (q, k_pool, v_pool, 0, positions, page_slot, page_pos,
                     pool_blocks, attention_block)
        if cfg.use_pallas:
            return paged_attention.paged_decode_attention(
                *pool_args, interpret=cfg.pallas_interpret)
        return _decode_attention(cfg, *pool_args)

    k_pool, v_pool = state["k"], state["v"]
    ring_k, ring_v = state["ring_k"], state["ring_v"]
    ssm, conv = state["ssm"], state["conv"]
    i, memory = 0, None
    for l, lp in enumerate(params["layers"]):
        kind = cfg.layer_kinds[l]
        xn = _ln(cfg, x, lp, "mixer_norm")
        if kind == "mamba":
            with jax.named_scope("mamba"):
                # The slices are taken before the arrays go on to be
                # updated (the barrier says so): where the compiler fused a
                # slice into a later reader of the whole updated array, it
                # rematerialised the update of the layer before and, in
                # place, applied it twice (PERF.md section 6).
                old_h, old_c, ssm, conv = jax.lax.optimization_barrier(
                    (ssm[i, :B], conv[i, :B], ssm, conv))
                mixed, memory, h, tail = _mamba_decode(cfg, xn, lp, old_h,
                                                       old_c)
                h = jnp.where(active[:, None, None], h, old_h)
                tail = jnp.where(active[:, None, None],
                                 tail.astype(conv.dtype), old_c)
                ssm = jax.lax.dynamic_update_slice(ssm, h[None], (i, 0, 0, 0))
                conv = jax.lax.dynamic_update_slice(conv, tail[None],
                                                    (i, 0, 0, 0))
                i += 1
        elif kind == "window":
            with jax.named_scope("diff_attention_window"):
                q, k, v = _qkv(cfg, xn, lp)
                ctx, ring_k, ring_v = _ring_decode(
                    cfg.sliding_window, q, k, v, ring_k, ring_v,
                    cfg.layers_of("window").index(l), positions, active,
                    kernel=cfg.use_pallas, interpret=cfg.pallas_interpret)
                mixed = _differential(cfg, l, lp, ctx)
        elif kind == "full":
            with jax.named_scope("diff_attention_full"):
                q, k, v = _qkv(cfg, xn, lp)
                k_pool = _write_rows(k_pool, 0, k[:, None], write_page,
                                     offset)
                v_pool = _write_rows(v_pool, 0, v[:, None], write_page,
                                     offset)
                mixed = _differential(cfg, l, lp, over_pool(q))
        elif kind == "gmu":
            with jax.named_scope("gmu"):
                mixed = _gmu(xn, memory, lp)
        else:
            with jax.named_scope("diff_attention_cross"):
                mixed = _differential(
                    cfg, l, lp, over_pool(_cross_queries(cfg, xn, lp)))
        x = _close_block(cfg, x, mixed, lp)
    with jax.named_scope("head"):
        out = _head(_layer_norm(x, params["final_norm"],
                                params["final_norm_bias"],
                                cfg.layer_norm_eps),
                    params["embed"], logit_ids, tied=True)
    token = jnp.where(active, out["token"], state["token"][:B])
    state = dict(state, k=k_pool, v=v_pool, ring_k=ring_k, ring_v=ring_v,
                 ssm=ssm, conv=conv,
                 token=jax.lax.dynamic_update_slice_in_dim(
                     state["token"], token, 0, 0))
    return state, out
