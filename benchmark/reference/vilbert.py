"""Plain reference of the two-stream ViLBERT that the cells serve.

Straight ``jax.numpy`` in float32 with ``highest`` matmul precision: no
kernels, no cache, no batching tricks, nothing imported from the program. It
follows the published 12-in-1 ViLBERT (Lu et al. 2020): a BERT text stream,
a visual stream over region features, co-attention bridges between listed
layer pairs, first-token poolers, and the task heads that the served task
families decode. The one thing it shares with the program is the *names* of
the parameter tree (:func:`param_shapes`), because the benchmark makes the
weights from the seed and hands the same tree to both sides.

Departures from the paper, all of them the served deployment's: a task token
is inserted after [CLS] (``task_specific_tokens``); padding is masked with
the BERT family's additive -10000; GELU is the exact (erf) form.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MASK_PENALTY = -10000.0


def _to_fp8(x):
    """Per-tensor scaled float8 (e4m3: 3 bits of mantissa)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


# The precision below the configurations' bfloat16, for the control of the
# comparison that decides ``correct``: both operands of every dense layer
# and of both attention products are rounded to the type; sums stay float32.
LOWER = {None: lambda x: x, "fp8": _to_fp8}


# ------------------------------------------------------------------ shapes
def _dense(d_in, d_out):
    return {"kernel": (d_in, d_out), "bias": (d_out,)}


def _norm(d):
    return {"scale": (d,), "bias": (d,)}


def _self_layer(hidden, inter):
    return {
        "attention": {"qkv": _dense(hidden, 3 * hidden)},
        "attention_output": {"dense": _dense(hidden, hidden),
                             "norm": _norm(hidden)},
        "ffn": {"intermediate": _dense(hidden, inter),
                "output": _dense(inter, hidden), "norm": _norm(hidden)},
    }


def _cross(d_query, d_other, bi):
    return {"query": _dense(d_query, bi), "key": _dense(d_other, bi),
            "value": _dense(d_other, bi)}


def _bridge(m):
    h, hv, bi = m["hidden_size"], m["v_hidden_size"], m["bi_hidden_size"]
    return {
        "text_attends_image": _cross(h, hv, bi),
        "image_attends_text": _cross(hv, h, bi),
        "t_output": {"dense": _dense(bi, h), "norm": _norm(h)},
        "v_output": {"dense": _dense(bi, hv), "norm": _norm(hv)},
        "t_ffn": {"intermediate": _dense(h, m["intermediate_size"]),
                  "output": _dense(m["intermediate_size"], h),
                  "norm": _norm(h)},
        "v_ffn": {"intermediate": _dense(hv, m["v_intermediate_size"]),
                  "output": _dense(m["v_intermediate_size"], hv),
                  "norm": _norm(hv)},
    }


def _classifier(d_in, hidden, d_out):
    return {"dense1": _dense(d_in, hidden), "norm": _norm(hidden),
            "dense2": _dense(hidden, d_out)}


def param_shapes(m: dict) -> dict:
    """The parameter tree (leaf = shape tuple) for a model configuration
    ``m`` (the ``model`` object of a ``benchmark/configs`` file)."""
    h, hv, bi = m["hidden_size"], m["v_hidden_size"], m["bi_hidden_size"]
    encoder = {}
    for i in range(m["num_hidden_layers"]):
        encoder[f"t_layer_{i}"] = _self_layer(h, m["intermediate_size"])
    for i in range(m["v_num_hidden_layers"]):
        encoder[f"v_layer_{i}"] = _self_layer(hv, m["v_intermediate_size"])
    for i in range(len(m["t_biattention_id"])):
        encoder[f"c_layer_{i}"] = _bridge(m)
    return {
        "bert": {
            "embeddings": {
                "word_embeddings": {"embedding": (m["vocab_size"], h)},
                "position_embeddings": {
                    "embedding": (m["max_position_embeddings"], h)},
                "token_type_embeddings": {
                    "embedding": (m["type_vocab_size"], h)},
                "task_embeddings": {"embedding": (m["num_task_tokens"], h)},
                "norm": _norm(h),
            },
            "v_embeddings": {
                "image_embeddings": _dense(m["v_feature_size"], hv),
                "image_location_embeddings": _dense(5, hv),
                "norm": _norm(hv),
            },
            "encoder": encoder,
            "t_pooler": {"dense": _dense(h, bi)},
            "v_pooler": {"dense": _dense(hv, bi)},
        },
        "vil_prediction": _classifier(bi, 2 * bi, m["num_labels"]),
        "vil_prediction_gqa": _classifier(bi, 2 * bi, m["gqa_num_labels"]),
        "vil_binary_prediction": _classifier(2 * bi, 2 * bi, 2),
        "vil_logit": _dense(bi, 1),
        "vil_tri_prediction": _dense(bi, 3),
        "vision_logit": _dense(hv, 1),
        "linguisic_logit": _dense(h, 1),
        # Masked-modelling heads: part of the published model and of the
        # served tree, read by no served task (and by nothing below).
        "cls_text": {"transform_dense": _dense(h, h),
                     "transform_norm": _norm(h),
                     "decoder_bias": (m["vocab_size"],)},
        "cls_image": {"transform_dense": _dense(hv, hv),
                      "transform_norm": _norm(hv),
                      "decoder": _dense(hv, m["v_target_size"])},
    }


# ----------------------------------------------------------------- forward
def _lin(p, x, rnd):
    return (jnp.matmul(rnd(x), rnd(p["kernel"]), precision=HIGHEST)
            + p["bias"])


def _layer_norm(p, x, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def _attend(q, k, v, bias, heads, rnd):
    """q (B,Nq,H*D), k/v (B,Nk,H*D), bias (B,Nk) additive → (B,Nq,H*D)."""
    b, nq, width = q.shape
    d = width // heads
    q = q.reshape(b, nq, heads, d)
    k = k.reshape(b, k.shape[1], heads, d)
    v = v.reshape(b, v.shape[1], heads, d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k), precision=HIGHEST)
    scores = scores / jnp.sqrt(jnp.float32(d)) + bias[:, None, None, :]
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", rnd(probs), rnd(v),
                     precision=HIGHEST)
    return ctx.reshape(b, nq, width)


def _out(p, ctx, residual, eps, rnd):
    return _layer_norm(p["norm"], _lin(p["dense"], ctx, rnd) + residual,
                       eps)


def _ffn(p, x, eps, rnd):
    h = _lin(p["output"], _gelu(_lin(p["intermediate"], x, rnd)), rnd)
    return _layer_norm(p["norm"], h + x, eps)


def _self_block(p, x, bias, heads, eps, rnd):
    q, k, v = jnp.split(_lin(p["attention"]["qkv"], x, rnd), 3, axis=-1)
    x = _out(p["attention_output"], _attend(q, k, v, bias, heads, rnd), x,
             eps, rnd)
    return _ffn(p["ffn"], x, eps, rnd)


def _bridge_block(p, v_h, v_bias, t_h, t_bias, heads, eps, rnd):
    ta, va = p["text_attends_image"], p["image_attends_text"]
    t_ctx = _attend(_lin(ta["query"], t_h, rnd), _lin(ta["key"], v_h, rnd),
                    _lin(ta["value"], v_h, rnd), v_bias, heads, rnd)
    v_ctx = _attend(_lin(va["query"], v_h, rnd), _lin(va["key"], t_h, rnd),
                    _lin(va["value"], t_h, rnd), t_bias, heads, rnd)
    v_h = _ffn(p["v_ffn"], _out(p["v_output"], v_ctx, v_h, eps, rnd), eps,
               rnd)
    t_h = _ffn(p["t_ffn"], _out(p["t_output"], t_ctx, t_h, eps, rnd), eps,
               rnd)
    return v_h, t_h


def _classify(p, x, eps, rnd):
    h = _layer_norm(p["norm"], _gelu(_lin(p["dense1"], x, rnd)), eps)
    return _lin(p["dense2"], h, rnd)


def forward(params: dict, m: dict, batch: dict, lower=None) -> dict:
    """One request's rows through the model.

    ``batch``: ``input_ids`` (R,Nt) int32, ``input_mask`` (R,Nt),
    ``features`` (R,Nv,F) f32, ``spatials`` (R,Nv,5), ``image_mask`` (R,Nv),
    ``task_ids`` (R,) int32. Returns float32 head outputs per row; the paired
    head (``binary``) reads rows 0 and 1 as one example's two images.
    ``lower`` names a precision of :data:`LOWER` for the control.
    """
    rnd = LOWER[lower]
    eps = m["layer_norm_eps"]
    bert = params["bert"]
    emb = bert["embeddings"]
    ids = batch["input_ids"]
    n_t = ids.shape[1]
    x = (emb["word_embeddings"]["embedding"][ids]
         + emb["position_embeddings"]["embedding"][jnp.arange(n_t)][None]
         + emb["token_type_embeddings"]["embedding"][jnp.zeros_like(ids)])
    task = emb["task_embeddings"]["embedding"][batch["task_ids"]][:, None, :]
    t_h = _layer_norm(emb["norm"],
                      jnp.concatenate([x[:, :1], task, x[:, 1:]], axis=1), eps)
    t_mask = batch["input_mask"].astype(jnp.float32)
    t_mask = jnp.concatenate(
        [t_mask[:, :1], jnp.ones_like(t_mask[:, :1]), t_mask[:, 1:]], axis=1)
    v_emb = bert["v_embeddings"]
    v_h = _layer_norm(
        v_emb["norm"],
        _lin(v_emb["image_embeddings"], batch["features"], rnd)
        + _lin(v_emb["image_location_embeddings"], batch["spatials"], rnd),
        eps)
    v_mask = batch["image_mask"].astype(jnp.float32)
    t_bias = (1.0 - t_mask) * MASK_PENALTY
    v_bias = (1.0 - v_mask) * MASK_PENALTY

    enc = bert["encoder"]
    t_heads, v_heads = m["num_attention_heads"], m["v_num_attention_heads"]
    t_ptr = v_ptr = 0
    for c, (v_stop, t_stop) in enumerate(
            zip(m["v_biattention_id"], m["t_biattention_id"])):
        while t_ptr < t_stop:
            t_h = _self_block(enc[f"t_layer_{t_ptr}"], t_h, t_bias, t_heads,
                              eps, rnd)
            t_ptr += 1
        while v_ptr < v_stop:
            v_h = _self_block(enc[f"v_layer_{v_ptr}"], v_h, v_bias, v_heads,
                              eps, rnd)
            v_ptr += 1
        v_h, t_h = _bridge_block(enc[f"c_layer_{c}"], v_h, v_bias, t_h,
                                 t_bias, m["bi_num_attention_heads"], eps,
                                 rnd)
    while v_ptr < m["v_num_hidden_layers"]:
        v_h = _self_block(enc[f"v_layer_{v_ptr}"], v_h, v_bias, v_heads, eps,
                          rnd)
        v_ptr += 1
    while t_ptr < m["num_hidden_layers"]:
        t_h = _self_block(enc[f"t_layer_{t_ptr}"], t_h, t_bias, t_heads, eps,
                          rnd)
        t_ptr += 1

    pooled_t = jax.nn.relu(_lin(bert["t_pooler"]["dense"], t_h[:, 0], rnd))
    pooled_v = jax.nn.relu(_lin(bert["v_pooler"]["dense"], v_h[:, 0], rnd))
    if m["fusion_method"] != "mul":
        raise ValueError("the reference knows the served fusion 'mul' only")
    pooled = pooled_t * pooled_v
    pair = jnp.concatenate([pooled[0], pooled[1]])[None]
    return {
        "vqa": _classify(params["vil_prediction"], pooled, eps, rnd),
        "gqa": _classify(params["vil_prediction_gqa"], pooled, eps, rnd),
        "binary": _classify(params["vil_binary_prediction"], pair, eps,
                            rnd)[0],
        "trinary": _lin(params["vil_tri_prediction"], pooled, rnd),
        "ranking": _lin(params["vil_logit"], pooled, rnd)[:, 0],
        "grounding": (_lin(params["vision_logit"], v_h, rnd)[..., 0]
                      + v_bias),
    }
