"""Plain reference of the decoder-hybrid-decoder the ``phi4flash`` cells
serve (``model_type: phi4flash``, "SambaY", arXiv:2507.06607: Mamba mixers
and differential attention in a self-decoder, gated memory units and
differential cross-attention in a cross-decoder that reads the
self-decoder's last scan output and its one full layer's keys and values).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: the full
causal forward over one whole sequence, **every layer over every
position** (it does not know that a served prefill stops half-way down the
stack), the recurrence one token at a time, attention as a masked product;
no kernel, no cache, no ring, no batching, nothing imported from the
program. What it shares with the program is the *names and shapes* of the
parameter tree (:func:`param_shapes`), because the benchmark makes the
weights from the seed and hands the same tree to both sides.

``model`` is the source ``config.json``'s keys under their own names, and
the Mamba mixer's sizes, which the source does not give, under
``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank``
("auto": ``ceil(hidden_size / 16)``).

Which layer is which (0-based ``l``, ``L = num_hidden_layers``, ``mb_per_layer``
2): below ``L/2``, even ``l`` is a Mamba mixer and odd ``l`` differential
attention inside a window; ``L/2`` is a Mamba mixer whose scan output ``m``
is the *memory*; ``L/2 + 1`` is differential attention over the whole
sequence; above, even ``l`` is a gated memory unit and odd ``l``
differential cross-attention.

The equations, ``x^ = LayerNorm(x)`` (weight and bias, ``layer_norm_eps``):

block   ``h = x + Mixer_l(x^)``, ``y = h + W_down(SiLU(g) * v)`` with ``[g,
        v] = W_gate_up h^``; a final LayerNorm; logits ``= E x^``, ``E`` the
        embedding (``tie_word_embeddings``).
mamba   ``[u, z] = W_in x^`` (``d_inner`` each); ``c_t = SiLU(b_c + sum_{k <
        d_conv} w_c[k] * u_{t - d_conv + 1 + k})``, rows before the sequence
        0; ``[delta, B_t, C_t] = W_x c_t`` (``dt_rank``, ``d_state``,
        ``d_state``); ``dt_t = softplus(W_dt delta + b_dt)``; ``A =
        -exp(A_log)``; ``h_t = exp(dt_t A) * h_{t-1} + (dt_t c_t) B_t^T``
        (``[d_inner, d_state]``, ``h_{-1} = 0``); ``m_t = h_t C_t + D *
        c_t``; ``Mixer = W_out(m_t * SiLU(z_t))``.
gmu     ``Mixer = W_2(m_t * SiLU(W_1 x^_t))``, ``m_t`` layer ``L/2``'s.
attn    ``[q, k, v] = W_qkv x^ + b`` (``n``, ``kv``, ``kv`` heads of ``d =
        hidden_size / n``). Query pair ``i`` is heads ``(2i, 2i + 1)``, key
        pair ``j`` heads ``(2j, 2j + 1)``, ``V_j = [v_2j | v_2j+1]``; pair
        ``i`` reads ``j = i // (n / kv)``. ``A^s_i = softmax(q^s_i k^s_j^T /
        sqrt(d) + mask)``; ``o_i = (A^1_i - lambda A^2_i) V_j``; ``lambda =
        exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init(l)``,
        ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)``; ``o~_i = (1 -
        lambda_init(l)) RMSNorm(o_i) * gamma`` over the ``2d`` of a pair
        (eps ``layer_norm_eps``); ``Mixer = W_o concat_i(o~_i) + b_o``.
        Mask: ``j <= i``; below ``L/2`` also ``i - j < sliding_window``.
cross   ``q = W_q x^ + b``; ``k`` and ``v`` are layer ``L/2 + 1``'s;
        otherwise as ``attn`` with the layer's own lambda vectors,
        ``lambda_init(l)``, ``gamma``, ``W_o``, ``b_o``.

Assumed where the source's ``config.json`` is silent: the configuration
file lists each under ``assumed`` with its origin.

Departures from a literal reading, none of which changes a number that is
compared: (1) the sequence is padded at its end to one of a few lengths, so
that two dozen prompts compile a handful of programs (the model is causal:
no row before the padding sees it); (2) the forward runs layer by layer,
one layer's bfloat16 weights cast to float32 at a time, attention and the
MLP by blocks of rows (a window layer's block against the keys its window
can reach: the masked-out rest contributes exact zeros), and the head only
at the rows asked for (``rows``), so that it fits beside the served tree;
(3) ``A_log`` lies ``[d_state, d_inner]`` in the tree and is transposed
here.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128
ROW_BLOCK = 512


def _to_fp8(x):
    """Per-tensor scaled float8 (e4m3: 3 bits of mantissa)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# The precision below the configuration's bfloat16, for the control of the
# comparison that decides ``correct``: both operands of every dense product
# and of both attention products are rounded to the type; sums stay
# float32, and so does the recurrence, which the configuration states in
# float32.
LOWER = {None: lambda x: x, "fp8": _to_fp8}


def dt_rank(model: dict) -> int:
    rank = model.get("mamba_dt_rank", "auto")
    return -(-model["hidden_size"] // 16) if rank == "auto" else int(rank)


def layer_kinds(model: dict) -> list:
    L = model["num_hidden_layers"]
    if model["mb_per_layer"] != 2 or L % 4 or L < 8:
        raise ValueError("mb_per_layer 2 and a depth that is a multiple of "
                         "4, at least 8")
    kinds = []
    for l in range(L):
        if l <= L // 2:
            kinds.append("mamba" if l % 2 == 0 else "window")
        elif l == L // 2 + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if l % 2 == 0 else "cross")
    return kinds


def param_shapes(model: dict) -> dict:
    H, I, V = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    n, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = H // n
    Ci = model["mamba_expand"] * H
    N, K, R = model["mamba_d_state"], model["mamba_d_conv"], dt_rank(model)
    layers = []
    for kind in layer_kinds(model):
        layer = {"mixer_norm": (H,), "mixer_norm_bias": (H,),
                 "mlp_norm": (H,), "mlp_norm_bias": (H,),
                 "mlp_gate_up": (H, 2 * I), "mlp_down": (I, H)}
        if kind == "mamba":
            layer.update(in_proj=(H, 2 * Ci), conv_w=(K, Ci), conv_b=(Ci,),
                         x_proj=(Ci, R + 2 * N), dt_proj=(R, Ci),
                         dt_bias=(Ci,), A_log=(N, Ci), D=(Ci,),
                         out_proj=(Ci, H))
        elif kind == "gmu":
            layer.update(gmu_in=(H, Ci), gmu_out=(Ci, H))
        else:
            if kind == "cross":
                layer.update(wq=(H, n * d), bq=(n * d,))
            else:
                layer.update(wqkv=(H, (n + 2 * kv) * d),
                             bqkv=((n + 2 * kv) * d,))
            layer.update(wo=(n * d, H), bo=(H,), lambda_q1=(d,),
                         lambda_k1=(d,), lambda_q2=(d,), lambda_k2=(d,),
                         sub_norm=(2 * d,))
        layers.append(layer)
    return {"embed": (V, H), "layers": layers, "final_norm": (H,),
            "final_norm_bias": (H,)}


def padded_length(n: int) -> int:
    """The first of 72, 144, 288, ... (nine eighths of a power of two) at
    or above ``n``: a deck of prompt lengths that are powers of two, each
    cut by up to an eighth and followed by up to a quarter of a thousand
    generated tokens, lands on few lengths, whatever the seed."""
    length = 72
    while length < n:
        length *= 2
    return length


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _matmul(rnd):
    def mm(x, w):
        return jnp.matmul(rnd(x), rnd(w), precision=HIGHEST)
    return mm


def _layer_norm(x, scale, bias, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale + bias


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _mamba(x, lp, *, dims, lower):
    """(``x + Mixer(LN(x))``, the scan's output ``m`` [T, d_inner]) of a
    Mamba layer; ``dims`` = (d_state, d_conv, dt_rank, eps)."""
    N, K, R, eps = dims
    lp = _f32(lp)
    mm = _matmul(LOWER[lower])
    T = x.shape[0]
    uz = mm(_layer_norm(x, lp["mixer_norm"], lp["mixer_norm_bias"], eps),
            lp["in_proj"])
    Ci = uz.shape[1] // 2
    u, z = uz[:, :Ci], uz[:, Ci:]
    before = jnp.pad(u, ((K - 1, 0), (0, 0)))
    c = jax.nn.silu(lp["conv_b"] + sum(before[k:k + T] * lp["conv_w"][k]
                                       for k in range(K)))
    dbc = mm(c, lp["x_proj"])
    delta, B, C = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    dt = jax.nn.softplus(mm(delta, lp["dt_proj"]) + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"]).T                              # [Ci, N]

    def token(h, xs):
        c_t, dt_t, B_t, C_t = xs
        h = (jnp.exp(dt_t[:, None] * A) * h
             + (dt_t * c_t)[:, None] * B_t[None, :])
        return h, h @ C_t + lp["D"] * c_t

    _, m = jax.lax.scan(token, jnp.zeros((Ci, N), jnp.float32),
                        (c, dt, B, C))
    return x + mm(m * jax.nn.silu(z), lp["out_proj"]), m


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _gmu(x, memory, lp, *, eps, lower):
    lp = _f32(lp)
    mm = _matmul(LOWER[lower])
    xn = _layer_norm(x, lp["mixer_norm"], lp["mixer_norm_bias"], eps)
    return x + mm(memory * jax.nn.silu(mm(xn, lp["gmu_in"])), lp["gmu_out"])


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _keys_values(x, lp, *, dims, lower):
    """Keys [T, kv, d] and values [T, kv, d] of a self-attention layer."""
    n, kv, d, eps = dims
    lp = _f32(lp)
    xn = _layer_norm(x, lp["mixer_norm"], lp["mixer_norm_bias"], eps)
    w, b = lp["wqkv"][:, n * d:], lp["bqkv"][n * d:]
    kv_rows = _matmul(LOWER[lower])(xn, w) + b
    T = x.shape[0]
    return (kv_rows[:, :kv * d].reshape(T, kv, d),
            kv_rows[:, kv * d:].reshape(T, kv, d))


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _diff_attention(x, k, v, lp, *, dims, lower):
    """``x + Mixer(LN(x))`` of a differential attention layer whose queries
    are made here (``wq`` [H, n d], ``bq``) and whose keys and values [T,
    kv, d] are given; ``dims`` = (n, kv, d, window or 0, layer, eps).
    Queries, their softmaxes and ``W_o`` block of rows by block of rows
    (departure 2)."""
    n, kv, d, window, layer, eps = dims
    rnd, lp = LOWER[lower], _f32(lp)
    mm = _matmul(rnd)
    T = x.shape[0]
    init = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"])) + init)
    xn = _layer_norm(x, lp["mixer_norm"], lp["mixer_norm_bias"], eps)
    per = n // kv                       # query pairs a key pair
    block = math.gcd(QUERY_BLOCK, T)
    reach = T if not window else min(T, block + window)
    front = reach - block if window else 0
    # [T, key pair j, map s, d] and [T, j, 2 d].
    k_r = jnp.pad(rnd(k.reshape(T, kv // 2, 2, d)),
                  ((front, 0), (0, 0), (0, 0), (0, 0)))
    v_r = jnp.pad(rnd(v.reshape(T, kv // 2, 2 * d)),
                  ((front, 0), (0, 0), (0, 0)))

    def rows(start):
        def mine(a):
            return jax.lax.dynamic_slice_in_dim(a, start, block, 0)

        q_b = mm(mine(xn), lp["wq"]) + lp["bq"]
        # Query pair i = j * per + g, map s.
        q_b = q_b.reshape(block, kv // 2, per, 2, d)
        first = start if window else 0       # in the front-padded arrays
        k_b = jax.lax.dynamic_slice_in_dim(k_r, first, reach, 0)
        v_b = jax.lax.dynamic_slice_in_dim(v_r, first, reach, 0)
        i = (start + jnp.arange(block))[:, None]
        j = (first - front + jnp.arange(reach))[None, :]
        seen = (j <= i) & (j >= 0)
        if window:
            seen = seen & (i - j < window)
        scores = jnp.einsum("qjgsd,kjsd->jgsqk", rnd(q_b), k_b,
                            precision=HIGHEST) / math.sqrt(d)
        maps = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        ctx = jnp.einsum("jgsqk,kjd->qjgsd", rnd(maps), v_b,
                         precision=HIGHEST)  # [block, j, g, s, 2 d]
        o = ctx[:, :, :, 0] - lam * ctx[:, :, :, 1]
        o = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
             * lp["sub_norm"] * (1.0 - init))
        return mm(o.reshape(block, n * d), lp["wo"]) + lp["bo"]

    out = jax.lax.map(rows, jnp.arange(0, T, block))
    return x + out.reshape(T, -1)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _mlp(h, lp, *, eps, lower):
    lp = _f32(lp)
    mm = _matmul(LOWER[lower])
    block = math.gcd(ROW_BLOCK, h.shape[0])
    I = lp["mlp_down"].shape[0]

    def rows(h_b):
        gu = mm(_layer_norm(h_b, lp["mlp_norm"], lp["mlp_norm_bias"], eps),
                lp["mlp_gate_up"])
        return h_b + mm(jax.nn.silu(gu[:, :I]) * gu[:, I:], lp["mlp_down"])

    return jax.lax.map(rows, h.reshape(-1, block, h.shape[1])
                       ).reshape(h.shape)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, bias, embed, *, eps, lower):
    f = jnp.float32
    return _matmul(LOWER[lower])(
        _layer_norm(x, norm.astype(f), bias.astype(f), eps),
        embed.astype(f).T)


def _only(lp, *names):
    return {k: lp[k] for k in names}


NORM = ("mixer_norm", "mixer_norm_bias")
OUT = ("wo", "bo", "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2",
       "sub_norm")


def forward(params: dict, model: dict, token_ids, lower=None, rows=None):
    """Logits [len(rows), vocab_size] (float32) of the full causal forward
    over ``token_ids`` (one sequence); ``rows`` the positions wanted
    (default: every one). ``lower`` names a precision of :data:`LOWER` for
    the control."""
    n_tokens = len(token_ids)
    rows = jnp.arange(n_tokens) if rows is None else jnp.asarray(rows)
    pad = padded_length(n_tokens) - n_tokens
    ids = jnp.pad(jnp.asarray(token_ids, jnp.int32), (0, pad))
    eps = float(model["layer_norm_eps"])
    n, kv = model["num_attention_heads"], model["num_key_value_heads"]
    d = model["hidden_size"] // n
    L = model["num_hidden_layers"]
    mamba = (model["mamba_d_state"], model["mamba_d_conv"], dt_rank(model),
             eps)
    x = params["embed"][ids].astype(jnp.float32)
    memory = shared = None
    for l, (kind, lp) in enumerate(zip(layer_kinds(model),
                                       params["layers"])):
        if kind == "mamba":
            h, m = _mamba(x, _only(lp, *NORM, "in_proj", "conv_w", "conv_b",
                                   "x_proj", "dt_proj", "dt_bias", "A_log",
                                   "D", "out_proj"), dims=mamba, lower=lower)
            if l == L // 2:
                memory = m
        elif kind == "gmu":
            h = _gmu(x, memory, _only(lp, *NORM, "gmu_in", "gmu_out"),
                     eps=eps, lower=lower)
        else:
            window = model["sliding_window"] if kind == "window" else 0
            if kind == "cross":
                k, v = shared
                queries = {"wq": lp["wq"], "bq": lp["bq"]}
            else:
                k, v = _keys_values(x, _only(lp, *NORM, "wqkv", "bqkv"),
                                    dims=(n, kv, d, eps), lower=lower)
                queries = {"wq": lp["wqkv"][:, :n * d],
                           "bq": lp["bqkv"][:n * d]}
                if kind == "full":
                    shared = (k, v)
            h = _diff_attention(x, k, v, {**_only(lp, *NORM, *OUT),
                                          **queries},
                                dims=(n, kv, d, window, l, eps), lower=lower)
        x = _mlp(h, _only(lp, "mlp_norm", "mlp_norm_bias", "mlp_gate_up",
                          "mlp_down"), eps=eps, lower=lower)
    return _head(x[rows], params["final_norm"], params["final_norm_bias"],
                 params["embed"], eps=eps, lower=lower)
