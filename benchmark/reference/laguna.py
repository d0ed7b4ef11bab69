"""Plain reference of the sparse-expert decoder the ``laguna`` cells serve
(``model_type: laguna``: 256 routed experts behind a softmax router with one
shared expert, grouped attention that is windowed in three layers of four
with another count of query heads than in the full ones, a per-head output
gate, YaRN in the full layers and plain rotary embedding in the sliding
ones).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: the full
causal forward over one whole sequence; no kernel, no cache, no ring, no
batching, nothing imported from the program. What it shares with the
program is the *names and shapes* of the parameter tree
(:func:`param_shapes`), because the benchmark makes the weights from the
seed and hands the same tree to both sides.

``model`` is the source ``config.json``'s keys under their own names
(``num_experts`` the router's width, the lists one entry a layer that is
run) and ``experts_held`` = [first, count] of the routed experts whose
weights the tree holds (absent or null: all).

The equations, ``x^ = RMSNorm(x)`` with ``rms_norm_eps``:

block      ``h = x + Attn_l(x^)``, ``y = h + FFN_l(h^)``; a final RMSNorm,
           then the untied head over the ``vocab_size`` rows held.
attention  ``H_l = num_attention_heads_per_layer[l]`` query heads, ``H_kv =
           num_key_value_heads``, width ``d = head_dim``: ``q = W_q x^``,
           ``k = W_k x^``, ``v = W_v x^``; RMSNorm over ``d`` on every head
           of ``q`` and ``k``; rotary embedding on both; query head ``h``
           reads key/value head ``h // (H_l / H_kv)``; ``o_h = g_h
           softmax(q_h k^T / sqrt(d) + mask) v`` with ``g = sigmoid(W_g
           x^)``; ``W_o`` over the concatenated heads. Mask: ``j <= i``, and
           in a ``sliding_attention`` layer ``i - j < sliding_window``.
rotary     on the first ``r = partial_rotary_factor * d`` dimensions of a
           head, dimension ``i < r/2`` paired with ``i + r/2``: ``(a, b) ->
           (a cos - b sin, b cos + a sin)``, angle = position x frequency.
           ``rope_type: default``: frequency ``theta^(-2i/r)``. ``yarn``:
           ``f_i = theta^(-2i/r)``; ``c(n) = r ln(original / (2 pi n)) / (2
           ln theta)``; ``low = floor(c(beta_fast))``, ``high =
           ceil(c(beta_slow))``, clipped to [0, r - 1]; ``ramp_i =
           clip((i - low) / (high - low), 0, 1)``; frequency ``f_i (1 -
           ramp_i) + f_i / factor * ramp_i``; cos and sin times
           ``attention_factor`` (0.1 ln(factor) + 1 where the source gives
           none).
dense FFN  ``W_d(SiLU(W_g h^) * W_u h^)`` where ``mlp_layer_types[l]`` is
           ``dense``.
sparse FFN ``s = softmax(W_r h^)`` over all ``num_experts`` (float32);
           ``T`` = the ``num_experts_per_tok`` largest; ``w_e =
           moe_routed_scaling_factor s_e / sum_{T} s``; ``E_shared(h^) +
           sum_{e in T, e held} w_e E_e(h^)``: **only the held experts'
           terms are summed** (``experts_held``), exactly as the chip's
           share of the deployment computes them; with all held it is the
           whole layer.

Assumed where the source's ``config.json`` is silent (the configuration
file lists the same under ``assumed``): the pre-norm block, the per-head
RMSNorm on ``q`` and ``k``, SiLU, softmax router scores, the shared expert
added ungated, the gate read from the normed input and applied before
``W_o``, the ``rotate_half`` pairing, YaRN's ramp and truncation as
``transformers`` has them.

Departures from a literal reading, none of which changes a number that is
compared: (1) the sequence is padded at its end to one of a few lengths, so
that two dozen prompts compile a handful of programs (the model is causal:
no row before the padding sees it); (2) the forward runs layer by layer,
one layer's bfloat16 weights cast to float32 at a time, attention by blocks
of query rows (a sliding layer's block against the keys its window can
reach: the masked-out rest contributes exact zeros), and the head only at
the rows asked for (``rows``), so that it fits beside the served tree; (3)
an expert's term is computed over the rows routed to it only, expert by
expert (:func:`routed_experts`): the terms left out carry weight 0 in the
masked sum; the rows are padded to a power of two (at least
:data:`EXPERT_ROWS`) under weight 0 for the same reason as (1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FULL, SLIDING = "full_attention", "sliding_attention"
QUERY_BLOCK = 128
ROW_BLOCK = 512
EXPERT_ROWS = 256


def _to_fp8(x):
    """Per-tensor scaled float8 (e4m3: 3 bits of mantissa)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# The precision below the configuration's bfloat16, for the control of the
# comparison that decides ``correct``: both operands of every dense layer,
# of both attention products and of every expert's products are rounded to
# the type; sums stay float32, and so does the router, which the
# configuration states in float32.
LOWER = {None: lambda x: x, "fp8": _to_fp8}


def held_of(model: dict) -> tuple:
    held = model.get("experts_held")
    return (0, model["num_experts"]) if held is None else tuple(held)


def param_shapes(model: dict) -> dict:
    """``layers`` a list, one dict a layer; the expert matrices hold the
    held experts only, gate and up side by side."""
    H, V, d = model["hidden_size"], model["vocab_size"], model["head_dim"]
    kv, W = model["num_key_value_heads"], model["moe_intermediate_size"]
    S, I = (model["shared_expert_intermediate_size"],
            model["intermediate_size"])
    held = held_of(model)[1]
    layers = []
    for l in range(model["num_hidden_layers"]):
        n = model["num_attention_heads_per_layer"][l]
        layer = {"attn_norm": (H,), "wq": (H, n * d), "wk": (H, kv * d),
                 "wv": (H, kv * d), "wg": (H, n), "wo": (n * d, H),
                 "q_norm": (d,), "k_norm": (d,), "mlp_norm": (H,)}
        if model["mlp_layer_types"][l] == "dense":
            layer.update(mlp_gate=(H, I), mlp_up=(H, I), mlp_down=(I, H))
        else:
            layer.update(router=(H, model["num_experts"]),
                         experts_gate_up=(held, H, 2 * W),
                         experts_down=(held, W, H), shared_gate=(H, S),
                         shared_up=(H, S), shared_down=(S, H))
        layers.append(layer)
    return {"embed": (V, H), "layers": layers, "final_norm": (H,),
            "lm_head": (H, V)}


def padded_length(n: int) -> int:
    """The first of 72, 144, 288, ... (nine eighths of a power of two) at
    or above ``n``: a deck of prompt lengths that are powers of two, each
    cut by up to an eighth and followed by up to a quarter of a thousand
    generated tokens, lands on one length a kind, whatever the seed, so the
    programs of a run are those of the run before."""
    length = 72
    while length < n:
        length *= 2
    return length


def rotary_tables(model: dict, layer_type: str, positions) -> tuple:
    """(cos, sin) [len(positions), r / 2] of one layer type, float32."""
    rope = model["rope_parameters"][layer_type]
    r = int(model["head_dim"] * float(rope.get("partial_rotary_factor", 1)))
    i = np.arange(r // 2, dtype=np.float64)
    freq = float(rope["rope_theta"]) ** (-2.0 * i / r)
    scale = 1.0
    if rope["rope_type"] == "yarn":
        theta, factor = float(rope["rope_theta"]), float(rope["factor"])
        original = float(rope["original_max_position_embeddings"])

        def c(n):
            return (r * math.log(original / (2 * math.pi * n))
                    / (2 * math.log(theta)))

        low, high = c(float(rope["beta_fast"])), c(float(rope["beta_slow"]))
        if rope.get("truncate", True):
            low, high = math.floor(low), math.ceil(high)
        low, high = max(low, 0), min(high, r - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        freq = freq * (1.0 - ramp) + freq / factor * ramp
        scale = rope.get("attention_factor")
        if scale is None:
            scale = 0.1 * math.log(factor) + 1.0
    elif rope["rope_type"] != "default":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    angle = (jnp.asarray(positions, jnp.float32)[:, None]
             * jnp.asarray(freq, jnp.float32)[None, :])
    return jnp.cos(angle) * scale, jnp.sin(angle) * scale


def _rotate(x, cos, sin):
    """``x`` [T, heads, d]; the tables [T, r / 2]."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _matmul(rnd):
    def mm(x, w):
        return jnp.matmul(rnd(x), rnd(w), precision=HIGHEST)
    return mm


def _gated_mlp(mm, h, gate, up, down):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _attention(x, lp, cos, sin, *, dims, lower):
    """``x + Attn(RMSNorm(x))`` of one layer. ``dims`` = (query heads,
    key/value heads, d, window or 0, eps). Keys and values are made for
    the whole sequence; queries, their softmax and ``W_o`` block of rows by
    block of rows (departure 2)."""
    n, kv, d, window, eps = dims
    rnd, lp = LOWER[lower], _f32(lp)
    mm = _matmul(rnd)
    T = x.shape[0]
    xn = _rms(x, lp["attn_norm"], eps)
    k = _rotate(_rms(mm(xn, lp["wk"]).reshape(T, kv, d), lp["k_norm"], eps),
                cos, sin)
    v = mm(xn, lp["wv"]).reshape(T, kv, d)
    block = math.gcd(QUERY_BLOCK, T)
    # A sliding layer's block of rows reaches back ``window - 1`` keys.
    reach = T if not window else min(T, block + window)
    front = reach - block if window else 0
    k_r = jnp.pad(rnd(k), ((front, 0), (0, 0), (0, 0)))
    v_r = jnp.pad(rnd(v), ((front, 0), (0, 0), (0, 0)))

    def rows(start):
        def mine(a):
            return jax.lax.dynamic_slice_in_dim(a, start, block, 0)

        xn_b = mine(xn)
        q_b = _rotate(_rms(mm(xn_b, lp["wq"]).reshape(block, n, d),
                           lp["q_norm"], eps), mine(cos), mine(sin))
        # Query head h = j * group + g beside its key/value head j.
        q_b = q_b.reshape(block, kv, n // kv, d)
        gate = jax.nn.sigmoid(mm(xn_b, lp["wg"]))        # [block, n]
        first = start if window else 0       # in the front-padded arrays
        k_b = jax.lax.dynamic_slice_in_dim(k_r, first, reach, 0)
        v_b = jax.lax.dynamic_slice_in_dim(v_r, first, reach, 0)
        i = (start + jnp.arange(block))[:, None]
        j = (first - front + jnp.arange(reach))[None, :]
        seen = (j <= i) & (j >= 0)
        if window:
            seen = seen & (i - j < window)
        scores = jnp.einsum("qjgd,kjd->jgqk", rnd(q_b), k_b,
                            precision=HIGHEST) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf),
                               -1)
        ctx = jnp.einsum("jgqk,kjd->qjgd", rnd(probs), v_b,
                         precision=HIGHEST).reshape(block, n, d)
        return mm((ctx * gate[..., None]).reshape(block, n * d), lp["wo"])

    out = jax.lax.map(rows, jnp.arange(0, T, block))
    return x + out.reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _dense_ffn(h, lp, *, eps, lower):
    lp = _f32(lp)
    mm = _matmul(LOWER[lower])
    block = math.gcd(ROW_BLOCK, h.shape[0])

    def rows(h_b):
        return h_b + _gated_mlp(mm, _rms(h_b, lp["mlp_norm"], eps),
                                lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])

    return jax.lax.map(rows, h.reshape(-1, block, h.shape[1])
                       ).reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("k", "scale", "eps", "lower"))
def _route_and_shared(h, lp, *, k, scale, eps, lower):
    """(normed rows, the shared expert's term, the top-k experts [T, k] and
    their weights [T, k]); the router in float32 whatever ``lower``."""
    lp = _f32(lp)
    hn = _rms(h, lp["mlp_norm"], eps)
    s = jax.nn.softmax(jnp.matmul(hn, lp["router"], precision=HIGHEST), -1)
    top, experts = jax.lax.top_k(s, k)
    weights = scale * top / jnp.sum(top, -1, keepdims=True)
    shared = _gated_mlp(_matmul(LOWER[lower]), hn, lp["shared_gate"],
                        lp["shared_up"], lp["shared_down"])
    return hn, shared, experts, weights


@functools.partial(jax.jit, static_argnames=("lower",), donate_argnums=(0,))
def _expert_term(total, hn, idx, w, gate_up, down, *, lower):
    """``total`` with ``w * E(hn[idx])`` added at rows ``idx`` (padding
    rows carry weight 0)."""
    mm = _matmul(LOWER[lower])
    W = down.shape[0]
    gu = mm(hn[idx], gate_up.astype(jnp.float32))
    y = mm(jax.nn.silu(gu[:, :W]) * gu[:, W:], down.astype(jnp.float32))
    return total.at[idx].add(w[:, None] * y)


def routed_experts(hn, experts, weights, held, gate_up, down, lower=None):
    """``sum over e in top-k(t), e held, of w[t, e] E_e(hn[t])`` [T, H],
    expert by expert over the rows routed to each."""
    first, count = held
    chosen, w_host = np.asarray(experts), np.asarray(weights)
    total = jnp.zeros_like(hn)
    for e in range(count):
        rows, which = np.nonzero(chosen == first + e)
        if rows.size == 0:
            continue
        pad = max(EXPERT_ROWS, 1 << (rows.size - 1).bit_length()) - rows.size
        idx = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        w = np.concatenate([w_host[rows, which], np.zeros(pad, np.float32)])
        total = _expert_term(total, hn, jnp.asarray(idx, jnp.int32),
                             jnp.asarray(w, jnp.float32), gate_up[e],
                             down[e], lower=lower)
    return total


def sparse_ffn(h, lp, model: dict, lower=None) -> tuple:
    """(the shared expert's term, the held routed experts' term) of a
    sparse layer's FFN over the residual rows ``h`` [T, H]; the layer's
    output is ``h`` plus both."""
    small = {k: v for k, v in lp.items() if not k.startswith("experts_")}
    hn, shared, experts, weights = _route_and_shared(
        h, small, k=int(model["num_experts_per_tok"]),
        scale=float(model["moe_routed_scaling_factor"]),
        eps=float(model["rms_norm_eps"]), lower=lower)
    routed = routed_experts(hn, experts, weights, held_of(model),
                            lp["experts_gate_up"], lp["experts_down"], lower)
    return shared, routed


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, lm_head, *, eps, lower):
    mm = _matmul(LOWER[lower])
    return mm(_rms(x, norm.astype(jnp.float32), eps),
              lm_head.astype(jnp.float32))


def forward(params: dict, model: dict, token_ids, lower=None, rows=None):
    """Logits [len(rows), vocabulary held] (float32) of the full causal
    forward over ``token_ids`` (one sequence); ``rows`` the positions
    wanted (default: every one). ``lower`` names a precision of
    :data:`LOWER` for the control."""
    n_tokens = len(token_ids)
    rows = jnp.arange(n_tokens) if rows is None else jnp.asarray(rows)
    pad = padded_length(n_tokens) - n_tokens
    ids = jnp.pad(jnp.asarray(token_ids, jnp.int32), (0, pad))
    eps = float(model["rms_norm_eps"])
    positions = np.arange(n_tokens + pad)
    tables = {t: rotary_tables(model, t, positions) for t in (FULL, SLIDING)}
    x = params["embed"][ids].astype(jnp.float32)
    for l, lp in enumerate(params["layers"]):
        kind = model["layer_types"][l]
        dims = (model["num_attention_heads_per_layer"][l],
                model["num_key_value_heads"], model["head_dim"],
                model["sliding_window"] if kind == SLIDING else 0, eps)
        attn = {k: lp[k] for k in ("attn_norm", "wq", "wk", "wv", "wg", "wo",
                                   "q_norm", "k_norm")}
        h = _attention(x, attn, *tables[kind], dims=dims, lower=lower)
        if model["mlp_layer_types"][l] == "dense":
            x = _dense_ffn(h, {k: lp[k] for k in ("mlp_norm", "mlp_gate",
                                                  "mlp_up", "mlp_down")},
                           eps=eps, lower=lower)
        else:
            shared, routed = sparse_ffn(h, lp, model, lower)
            x = h + shared + routed
    return _head(x[rows], params["final_norm"], params["lm_head"],
                 eps=eps, lower=lower)
