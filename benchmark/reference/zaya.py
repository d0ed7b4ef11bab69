"""Plain reference of the decoder the ``zaya`` cells serve (``model_type:
zaya``, ZAYA1: every layer compressed convolutional attention over few
key/value heads, then one of ``num_experts`` experts or none, chosen by an
MLP router whose state is averaged from layer to layer).

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision: the full
causal forward over one whole sequence; no kernel, no cache, no carried
rows, no batching, nothing imported from the program. What it shares with
the program is the *names and shapes* of the parameter tree
(:func:`param_shapes`), because the benchmark makes the weights from the
seed and hands the same tree to both sides.

``model`` is the source ``config.json``'s keys under their own names (the
lists one entry a layer that is run). The equations, ``RMSNorm`` with
``rms_norm_eps`` and a learned scale, ``d = head_dim``, ``H`` query and
``H_kv`` key/value heads, ``g(h) = h // (H / H_kv)``:

merge      ``h <- s_l * h + u_l * f_l(RMSNorm(h))`` for the attention
           sublayer and then the expert sublayer of every layer; a final
           RMSNorm, then the tied head: logits ``= E x``.
CCA        ``x`` the normed row. ``q~ = W_q x`` [H d], ``k~ = W_k x`` [H_kv
           d]; ``c_t = [q~_t ; k~_t]``, rows before the start 0.
           ``a_t = w0[0] c_t + w0[1] c_{t-1}`` (each channel its own two
           taps); ``b_t = W1[0] a_t + W1[1] a_{t-1}``, a ``d x d`` map for
           each head of ``c``. ``q_h = b^q_h + (q~_h + k~_{g(h)}) / 2``;
           ``k_j = b^k_j + (mean_{g(h) = j} q~_h + k~_j) / 2``. ``q^ = sqrt(d)
           q / |q|``, ``k^_j = tau_j k_j / |k_j|``, ``|x| = sqrt(sum x^2 +
           1e-6)``. Rotary embedding on the first ``r =
           partial_rotary_factor d`` channels of every head of ``q^`` and
           ``k^``: channel ``i < r / 2`` paired with ``i + r / 2``, ``(a, b)
           -> (a cos - b sin, b cos + a sin)``, angle = position x
           ``theta^(-2i / r)`` (``rope_parameters["hybrid"]``).
           ``v_t = [W_v1 x_t ; W_v2 x_{t-1}]`` (``x_{-1} = 0``), key/value
           head 0 and 1. ``o_h = softmax_{s <= t}(q^_h . k^_{g(h), s} /
           sqrt(d)) v_{g(h)}``; ``W_o`` over the concatenated heads.
experts    ``r_l = W_r x``; ``r~_l = r_l + gamma_l r~_{l-1}`` (not in the
           first layer); ``z = W_3 GELU(W_2 GELU(W_1 r~_l))`` (exact GELU;
           ``num_experts + 1`` choices, the last none); ``e = argmax(z +
           b)``; ``w = softmax(z)_e``; ``f = w E_e(x)``, ``E_e(x) =
           W_down(SiLU(W_gate x) * W_up x)``, and ``f = 0`` where ``e`` is
           none. All of it in float32 whatever ``lower``.

Departures from a literal reading, none of which changes a number that is
compared: (1) the sequence is padded at its end to one of a few lengths
(:func:`padded_length`), so that a deck of prompt lengths compiles a
handful of programs (the model is causal, and a padded row is never a key:
no real row sees it); (2) the forward runs layer by layer, one layer's
bfloat16 weights cast to float32 at a time; attention goes by blocks of
query rows, each walking the key blocks up to its own and no further with
a streamed softmax (exact: the running maximum is subtracted before the
exponent and the sums are rescaled by it), and the head runs only at the
rows asked for (``rows``), so that a 127k-token request fits beside the
served tree; (3) an expert's term is computed over the real rows routed to
it only, expert by expert (:func:`routed_experts`): every other row's term
is 0; the rows are padded to a power of two (at least :data:`EXPERT_ROWS`)
under weight 0 for the same reason as (1).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 512
EXPERT_ROWS = 256
EPS = 1e-6
_NEG = -1e30


def _to_fp8(x):
    """Per-tensor scaled float8 (e4m3: 3 bits of mantissa)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _to_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


# Precisions below float32 for the control of the comparison that decides
# ``correct`` (``fp8``: the one below the configuration's bfloat16) and for
# the program's own operand precision (``bf16``, which reads how often a
# router's choice turns on it): both operands of every product of the
# attention sublayer, of both attention products and of every expert's
# products are rounded to the type; sums stay float32, and so does the
# router, which the configuration states in float32.
LOWER = {None: lambda x: x, "bf16": _to_bf16, "fp8": _to_fp8}


def param_shapes(model: dict) -> dict:
    """``layers`` a list, one dict a layer; ``wqk`` = ``W_q | W_k``, ``wv``
    = ``W_v1 | W_v2``, ``conv0`` [tap, channel], ``conv1`` [tap, head, d,
    d] (tap 0 the row itself, tap 1 the row before), the router's last
    column the "none" choice; gate and up side by side."""
    H, V, d = model["hidden_size"], model["vocab_size"], model["head_dim"]
    n, kv = model["num_attention_heads"], model["num_key_value_heads"]
    E, W = model["num_experts"], model["moe_intermediate_size"]
    R, C = model["router_hidden_size"], (n + kv) * d
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
            "layers": [dict(layer) for _ in range(model["num_hidden_layers"])],
            "final_norm": (H,)}


def padded_length(n: int) -> int:
    """The first of 72, 144, 288, ... (nine eighths of a power of two) at
    or above ``n``: a deck of prompt lengths that are powers of two, each
    cut by up to an eighth and followed by some hundred generated tokens,
    lands on one length a kind, whatever the seed."""
    length = 72
    while length < n:
        length *= 2
    return length


def rotary_tables(model: dict, positions) -> tuple:
    """(cos, sin) [len(positions), r / 2] float32, the angles in float64."""
    rope = model["rope_parameters"]["hybrid"]
    d = model["head_dim"]
    r = int(d * float(rope.get("partial_rotary_factor",
                               model["partial_rotary_factor"])))
    freq = float(rope["rope_theta"]) ** (-2.0 * np.arange(r // 2) / r)
    angle = np.asarray(positions, np.float64)[:, None] * freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def _rotate(x, cos, sin):
    """``x`` [T, heads, d]; the tables [T, r / 2]."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + EPS)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _matmul(rnd):
    def mm(x, w):
        return jnp.matmul(rnd(x), rnd(w), precision=HIGHEST)
    return mm


def _before(x):
    """Row ``t`` of the result is row ``t - 1`` of ``x``; row 0 is 0."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _attend(q, k, v, real, rnd):
    """Causal attention, query block by query block, each over the key
    blocks up to its own (departure 2). ``q`` [P, H, d], ``k``, ``v`` [P,
    H_kv, d]; keys at or past ``real`` are never seen."""
    P, n, d = q.shape
    kv = k.shape[1]
    block = math.gcd(P, BLOCK)
    qg = rnd(q).reshape(P, kv, n // kv, d)
    k, v = rnd(k), rnd(v)
    reach = (real + block - 1) // block

    def rows(i):
        q_b = jax.lax.dynamic_slice_in_dim(qg, i * block, block, 0)
        q_pos = i * block + jnp.arange(block)

        def body(j, carry):
            m, l, acc = carry
            k_b = jax.lax.dynamic_slice_in_dim(k, j * block, block, 0)
            v_b = jax.lax.dynamic_slice_in_dim(v, j * block, block, 0)
            k_pos = j * block + jnp.arange(block)
            seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos < real)[None]
            s = jnp.einsum("qjgd,kjd->jgqk", q_b, k_b,
                           precision=HIGHEST) / math.sqrt(d)
            s = jnp.where(seen[None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            fade = jnp.exp(m - m_new)
            acc = acc * fade[..., None] + jnp.einsum(
                "jgqk,kjd->jgqd", rnd(p), v_b, precision=HIGHEST)
            return m_new, l * fade + p.sum(-1), acc

        shape = (kv, n // kv, block)
        init = (jnp.full(shape, _NEG), jnp.zeros(shape),
                jnp.zeros(shape + (d,)))
        last = jnp.where(i * block < real, jnp.minimum(i + 1, reach), 0)
        _, l, acc = jax.lax.fori_loop(0, last, body, init)
        # A block wholly past the real rows walks nothing: its rows are 0.
        ctx = acc / jnp.maximum(l, 1e-30)[..., None]     # [kv, G, block, d]
        return jnp.transpose(ctx, (2, 0, 1, 3)).reshape(block, n, d)

    return jax.lax.map(rows, jnp.arange(P // block)).reshape(P, n, d)


@functools.partial(jax.jit, static_argnames=("dims", "lower"))
def _attention(x, lp, cos, sin, real, *, dims, lower):
    """``s x + u Attn(RMSNorm(x))`` of one layer over the padded rows ``x``
    [P, hidden]. ``dims`` = (H, H_kv, d, eps)."""
    n, kv, d, eps = dims
    rnd, lp = LOWER[lower], _f32(lp)
    mm = _matmul(rnd)
    P = x.shape[0]
    xn = _rms(x, lp["attn_norm"], eps)
    c = mm(xn, lp["wqk"])                                # [P, (H + H_kv) d]
    a = lp["conv0"][0] * c + lp["conv0"][1] * _before(c)
    heads = lambda t: t.reshape(P, n + kv, d)
    b = (jnp.einsum("tgi,gio->tgo", rnd(heads(a)), rnd(lp["conv1"][0]),
                    precision=HIGHEST)
         + jnp.einsum("tgi,gio->tgo", rnd(heads(_before(a))),
                      rnd(lp["conv1"][1]), precision=HIGHEST))
    q_pre, k_pre = heads(c)[:, :n], heads(c)[:, n:]
    group = n // kv
    q = b[:, :n] + 0.5 * (q_pre + jnp.repeat(k_pre, group, axis=1))
    k = b[:, n:] + 0.5 * (q_pre.reshape(P, kv, group, d).mean(2) + k_pre)
    q = _rotate(math.sqrt(d) * _unit(q), cos, sin)
    k = _rotate(lp["tau"][None, :, None] * _unit(k), cos, sin)
    v = jnp.stack([mm(xn, lp["wv"][:, :d]), mm(_before(xn), lp["wv"][:, d:])],
                  axis=1)
    ctx = _attend(q, k, v, real, rnd)
    o = mm(ctx.reshape(P, n * d), lp["wo"])
    return lp["attn_res_scale"] * x + lp["attn_out_scale"] * o


@functools.partial(jax.jit, static_argnames=("eps", "first"))
def _route(x, lp, r_prev, *, eps, first):
    """(normed rows, ``r~_l``, the choice [P] and its weight [P]) of a
    layer's router, in float32 whatever the precision asked for."""
    lp = _f32(lp)
    dense = _matmul(lambda t: t)
    xn = _rms(x, lp["mlp_norm"], eps)
    r = dense(xn, lp["router"])
    if not first:
        r = r + lp["router_eda"][0] * r_prev
    z = dense(jax.nn.gelu(dense(jax.nn.gelu(dense(r, lp["router_mlp1"]),
                                            approximate=False),
                                lp["router_mlp2"]), approximate=False),
              lp["router_out"])
    choice = jnp.argmax(z + lp["router_bias"], axis=-1)
    weight = jnp.take_along_axis(jax.nn.softmax(z, -1), choice[:, None],
                                 -1)[:, 0]
    return xn, r, choice, weight


@functools.partial(jax.jit, static_argnames=("lower",), donate_argnums=(0,))
def _expert_term(total, xn, idx, w, gate_up, down, *, lower):
    """``total`` with ``w * E(xn[idx])`` added at rows ``idx`` (padding
    rows carry weight 0)."""
    mm = _matmul(LOWER[lower])
    W = down.shape[0]
    gu = mm(xn[idx], gate_up.astype(jnp.float32))
    y = mm(jax.nn.silu(gu[:, :W]) * gu[:, W:], down.astype(jnp.float32))
    return total.at[idx].add(w[:, None] * y)


def routed_experts(xn, choice, weight, real, gate_up, down, lower=None):
    """``w[t] E_{e(t)}(xn[t])`` [P, hidden] over the real rows, expert by
    expert over the rows that chose it; rows that chose none, and padding
    rows, get 0."""
    chosen, w_host = np.asarray(choice)[:real], np.asarray(weight)
    total = jnp.zeros_like(xn)
    for e in range(gate_up.shape[0]):
        rows = np.nonzero(chosen == e)[0]
        if rows.size == 0:
            continue
        pad = max(EXPERT_ROWS, 1 << (rows.size - 1).bit_length()) - rows.size
        idx = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        w = np.concatenate([w_host[rows], np.zeros(pad, np.float32)])
        total = _expert_term(total, xn, jnp.asarray(idx, jnp.int32),
                             jnp.asarray(w, jnp.float32), gate_up[e],
                             down[e], lower=lower)
    return total


@jax.jit
def _merge(x, y, res, out):
    return res.astype(jnp.float32) * x + out.astype(jnp.float32) * y


@functools.partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, norm, embed, *, eps, lower):
    rnd = LOWER[lower]
    return jnp.matmul(rnd(_rms(x, norm.astype(jnp.float32), eps)),
                      rnd(embed.astype(jnp.float32)).T, precision=HIGHEST)


ATTENTION_KEYS = ("attn_norm", "wqk", "wv", "conv0", "conv1", "tau", "wo",
                  "attn_res_scale", "attn_out_scale")
ROUTER_KEYS = ("mlp_norm", "router", "router_eda", "router_mlp1",
               "router_mlp2", "router_out", "router_bias")


def forward(params: dict, model: dict, token_ids, lower=None, rows=None,
            choices: bool = False):
    """Logits [len(rows), vocabulary] (float32) of the full causal forward
    over ``token_ids`` (one sequence); ``rows`` the positions wanted
    (default: every one). ``lower`` names a precision of :data:`LOWER`.
    With ``choices`` also the router's choice of every real token at every
    layer [layers, tokens]."""
    real = len(token_ids)
    rows = jnp.arange(real) if rows is None else jnp.asarray(rows)
    P = padded_length(real)
    ids = jnp.pad(jnp.asarray(token_ids, jnp.int32), (0, P - real))
    eps = float(model["rms_norm_eps"])
    cos, sin = rotary_tables(model, np.arange(P))
    dims = (model["num_attention_heads"], model["num_key_value_heads"],
            model["head_dim"], eps)
    x = params["embed"][ids].astype(jnp.float32)
    r, picked = None, []
    for l, lp in enumerate(params["layers"]):
        x = _attention(x, {k: lp[k] for k in ATTENTION_KEYS}, cos, sin,
                       jnp.int32(real), dims=dims, lower=lower)
        xn, r, choice, weight = _route(
            x, {k: lp[k] for k in ROUTER_KEYS},
            jnp.zeros_like(x[:, :1]) if r is None else r, eps=eps,
            first=l == 0)
        y = routed_experts(xn, choice, weight, real, lp["experts_gate_up"],
                           lp["experts_down"], lower)
        x = _merge(x, y, lp["moe_res_scale"], lp["moe_out_scale"])
        if choices:
            picked.append(np.asarray(choice)[:real])
    logits = _head(x[rows], params["final_norm"], params["embed"], eps=eps,
                   lower=lower)
    return (logits, np.stack(picked)) if choices else logits
