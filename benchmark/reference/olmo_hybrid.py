"""Plain reference of the hybrid decoder the ``olmo_hybrid`` cells serve
(``model_type: olmo_hybrid``: gated linear attention in ``k`` layers of
``k + 1``, full attention in the last of each period).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: the full
causal forward over one whole sequence, the linear layer as a ``lax.scan``
of the recurrence one token at a time; no kernel, no cache, no chunked scan,
no batching, nothing imported from the program. What it shares with the
program is the *names and shapes* of the parameter tree
(:func:`param_shapes`), because the benchmark makes the weights from the
seed and hands the same tree to both sides.

The equations, token ``t``, ``x`` the residual stream:

block        ``h = x + RMSNorm(Mixer(x))``, ``y = h + RMSNorm(MLP(h))``,
             ``MLP(h) = W_down(SiLU(W_gate h) * W_up h)``; a final RMSNorm,
             then the untied head.
full layer   ``q, k, v = W_q x, W_k x, W_v x``; RMSNorm over the whole width
             of ``q`` and of ``k``; ``num_attention_heads`` heads; causal
             softmax(``q k^T / sqrt(d)``) ``v``; ``W_o``. No rotary
             embedding.
linear layer per head (``d_k``, ``d_v``): ``q~, k~, v~ = W_q x, W_k x, W_v
             x``, each channel through a causal convolution of
             ``linear_conv_kernel_dim`` taps, then SiLU; ``q_t =
             l2norm(q~_t)/sqrt(d_k)``, ``k_t = l2norm(k~_t)``; ``beta_t = 2
             sigmoid(W_b x_t)``; ``alpha_t = exp(-exp(A_log) softplus(W_a x_t
             + dt_bias))``; state ``S`` [d_v, d_k]:
             ``S_t = alpha_t S_{t-1} + beta_t (v_t - alpha_t S_{t-1} k_t)
             k_t^T``; ``o_t = S_t q_t``; output ``W_o(RMSNorm_head(o_t) *
             SiLU(W_g x_t))``.

Assumed where the source's ``config.json`` is silent (the configuration file
lists the same under ``assumed``): the post-norm block and the whole-width
QK-norm are the OLMo 2/3 family's; ``rope_theta: null`` is read as no rotary
embedding; the output gate and per-head RMSNorm on the linear mixer and the
float32 state are the gated-delta family's; l2norm adds 1e-6 under the root.

Departures from a literal reading, none of which changes a number that is
compared: (1) the sequence is padded at its end to one of a few lengths, so
that two dozen prompts compile a handful of programs (the model is causal:
no row before the padding sees it); (2) the forward runs layer by layer,
one layer's bfloat16 weights cast to float32 at a time, attention by blocks
of query rows, and the head only at the rows asked for (``rows``), so that
it fits beside the served tree; (3) the depth is walked in Python over the
stacked leaves ``[periods, ...]``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LINEAR, FULL = "linear_attention", "full_attention"
QUERY_BLOCK = 256


def _to_fp8(x):
    """Per-tensor scaled float8 (e4m3: 3 bits of mantissa)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# The precision below the configuration's bfloat16, for the control of the
# comparison that decides ``correct``: both operands of every dense layer,
# of both attention products and of the recurrence's products are rounded
# to the type; sums and the state stay float32.
LOWER = {None: lambda x: x, "fp8": _to_fp8}


def _period(model: dict) -> int:
    return list(model["layer_types"]).index(FULL) + 1


def param_shapes(model: dict) -> dict:
    """Linear layers stacked ``[periods, linear layers a period, ...]``,
    full layers ``[periods, ...]``."""
    H, I, V = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    n, dk, dv = (model["linear_num_key_heads"], model["linear_key_head_dim"],
                 model["linear_value_head_dim"])
    period = _period(model)
    periods = model["num_hidden_layers"] // period
    mlp = {"mlp_gate": (H, I), "mlp_up": (H, I), "mlp_down": (I, H),
           "mixer_norm": (H,), "mlp_norm": (H,)}
    linear = {"wq": (H, n * dk), "wk": (H, n * dk), "wv": (H, n * dv),
              "conv": (model["linear_conv_kernel_dim"],
                       n * (2 * dk + dv)),
              "wa": (H, n), "wb": (H, n), "A_log": (n,), "dt_bias": (n,),
              "wg": (H, n * dv), "o_norm": (dv,), "wo": (n * dv, H), **mlp}
    full = {"wq": (H, H), "wk": (H, H), "wv": (H, H), "wo": (H, H),
            "q_norm": (H,), "k_norm": (H,), **mlp}
    return {"embed": (V, H),
            "linear": {k: (periods, period - 1) + s
                       for k, s in linear.items()},
            "full": {k: (periods,) + s for k, s in full.items()},
            "final_norm": (H,), "lm_head": (H, V)}


def padded_length(n: int) -> int:
    """The next multiple of an eighth of the power of two at or below
    ``n`` (at least 64): a few lengths for many prompts."""
    step = max(64, (1 << (max(n, 1).bit_length() - 1)) // 8)
    return -(-n // step) * step


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _close_block(x, mixed, lp, eps, mm):
    h = x + _rms(mixed, lp["mixer_norm"], eps)
    mlp = mm(jax.nn.silu(mm(h, lp["mlp_gate"])) * mm(h, lp["mlp_up"]),
             lp["mlp_down"])
    return h + _rms(mlp, lp["mlp_norm"], eps)


def _matmul(rnd):
    def mm(x, w):
        return jnp.matmul(rnd(x), rnd(w), precision=HIGHEST)
    return mm


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _linear_layer(x, lp, *, dims, lower):
    n, dk, dv, taps, eps, neg = dims
    rnd, lp = LOWER[lower], _f32(lp)
    mm = _matmul(rnd)
    T = x.shape[0]
    pre = jnp.concatenate([mm(x, lp[w]) for w in ("wq", "wk", "wv")], -1)
    padded = jnp.pad(pre, ((taps - 1, 0), (0, 0)))
    conved = sum(padded[j:j + T] * lp["conv"][j] for j in range(taps))
    act = jax.nn.silu(conved)
    q, k, v = jnp.split(act, [n * dk, 2 * n * dk], -1)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q = l2(q.reshape(T, n, dk)) / math.sqrt(dk)
    k = l2(k.reshape(T, n, dk))
    v = v.reshape(T, n, dv)
    beta = jax.nn.sigmoid(mm(x, lp["wb"])) * (2.0 if neg else 1.0)
    alpha = jnp.exp(-jnp.exp(lp["A_log"])
                    * jax.nn.softplus(mm(x, lp["wa"]) + lp["dt_bias"]))

    def token(S, xs):                       # S [n, dv, dk]
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, None, None] * S
        read = jnp.einsum("nvk,nk->nv", rnd(S), rnd(k_t), precision=HIGHEST)
        S = S + (b_t[:, None] * (v_t - read))[:, :, None] * k_t[:, None, :]
        return S, jnp.einsum("nvk,nk->nv", rnd(S), rnd(q_t),
                             precision=HIGHEST)

    # ``unroll`` only groups the loop's steps for the compiler; each is
    # still one token's update.
    _, o = jax.lax.scan(token, jnp.zeros((n, dv, dk), jnp.float32),
                        (q, k, v, alpha, beta), unroll=16)
    gate = jax.nn.silu(mm(x, lp["wg"])).reshape(T, n, dv)
    mixed = mm((_rms(o, lp["o_norm"], eps) * gate).reshape(T, n * dv),
               lp["wo"])
    return _close_block(x, mixed, lp, eps, mm)


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _full_layer(x, fp, *, dims, lower):
    heads, eps = dims
    rnd, fp = LOWER[lower], _f32(fp)
    mm = _matmul(rnd)
    T, H = x.shape
    d = H // heads
    q = _rms(mm(x, fp["wq"]), fp["q_norm"], eps).reshape(T, heads, d)
    k = _rms(mm(x, fp["wk"]), fp["k_norm"], eps).reshape(T, heads, d)
    v = mm(x, fp["wv"]).reshape(T, heads, d)
    k_r, v_r = rnd(k), rnd(v)
    block = math.gcd(QUERY_BLOCK, T)

    def rows(start):
        q_b = jax.lax.dynamic_slice_in_dim(q, start, block, 0)
        scores = jnp.einsum("qhd,khd->hqk", rnd(q_b), k_r,
                            precision=HIGHEST) / math.sqrt(d)
        seen = (jnp.arange(T)[None, :]
                <= (start + jnp.arange(block))[:, None])
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", rnd(probs), v_r,
                          precision=HIGHEST)

    ctx = jax.lax.map(rows, jnp.arange(0, T, block)).reshape(T, H)
    return _close_block(x, mm(ctx, fp["wo"]), fp, eps, mm)


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, lm_head, *, eps, lower):
    mm = _matmul(LOWER[lower])
    return mm(_rms(x, norm.astype(jnp.float32), eps),
              lm_head.astype(jnp.float32))


def forward(params: dict, model: dict, token_ids, lower=None, rows=None):
    """Logits [len(rows), vocabulary] (float32) of the full causal forward
    over ``token_ids`` (one sequence); ``rows`` the positions wanted
    (default: every one). ``lower`` names a precision of :data:`LOWER` for
    the control."""
    n_tokens = len(token_ids)
    rows = jnp.arange(n_tokens) if rows is None else jnp.asarray(rows)
    pad = padded_length(n_tokens) - n_tokens
    ids = jnp.pad(jnp.asarray(token_ids, jnp.int32), (0, pad))
    eps = float(model["rms_norm_eps"])
    period = _period(model)
    lin_dims = (model["linear_num_key_heads"], model["linear_key_head_dim"],
                model["linear_value_head_dim"],
                model["linear_conv_kernel_dim"], eps,
                bool(model["linear_allow_neg_eigval"]))
    full_dims = (model["num_attention_heads"], eps)
    x = params["embed"][ids].astype(jnp.float32)
    for p in range(model["num_hidden_layers"] // period):
        for i in range(period - 1):
            lp = {k: a[p, i] for k, a in params["linear"].items()}
            x = _linear_layer(x, lp, dims=lin_dims, lower=lower)
        fp = {k: a[p] for k, a in params["full"].items()}
        x = _full_layer(x, fp, dims=full_dims, lower=lower)
    return _head(x[rows], params["final_norm"], params["lm_head"],
                 eps=eps, lower=lower)
