"""The sparse-expert decoder against its plain reference (ISSUE 32): seeded
random weights, a tiny size (hidden 64, 5 layers in the published pattern
full / sliding x 3 / full, 2 key/value heads of 16 with 4 and 6 query heads,
16 experts of which the first 8 are held, 4 a token, window 16, page 16,
vocabulary 384), float32 storage and compute on the CPU.

Tolerances. The engine and ``benchmark/reference/laguna.py`` compute the
same function two ways (paged and streamed attention against one softmax, a
ring against a masked full product, sorted pairs against expert-by-expert
rows, a cache against a full forward), both in float32: what is left is
summation order, read here at 2e-5 on logits of unit spread. ``ATOL``
allows 2e-4. A router tie broken the other way would move a logit by far
more: at this size and these seeds none occurs.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import laguna as reference
from vilbert_multitask_tpu.config import (
    FULL_ATTENTION,
    SLIDING_ATTENTION,
    FrameworkConfig,
    GenerateConfig,
    LagunaConfig,
)
from vilbert_multitask_tpu.engine.generate import GenerateEngine
from vilbert_multitask_tpu.models import laguna as model_lib
from vilbert_multitask_tpu.ops import moe

ATOL = 2e-4
LOGIT_IDS = [1, 2, 3, 300]
NEW = 6

MODEL = LagunaConfig().tiny()


def generate_cfg(model=MODEL, **over) -> FrameworkConfig:
    gen = GenerateConfig(
        model=model, param_dtype="float32", prefill_buckets=(32, 64),
        decode_buckets=(2, 4), slots=4, kv_pages=32, page_size=16,
        decode_attention_pages=4)
    return FrameworkConfig(generate=dataclasses.replace(gen, **over))


def model_dict(model=MODEL) -> dict:
    """The configuration as the source's ``config.json`` would say it."""
    return dict(dataclasses.asdict(model), rope_parameters=model.rope)


@pytest.fixture(scope="module", params=["jnp", "pallas_interpret"])
def engine(request):
    """The engine the CPU serves with, and the one the chip does with its
    kernels (the grouped expert product, the grouped paged decode
    attention) in the interpreter."""
    model = MODEL if request.param == "jnp" else dataclasses.replace(
        MODEL, use_pallas=True, pallas_interpret=True)
    eng = GenerateEngine(generate_cfg(model))
    eng.warmup()
    return eng


def run_to_end(eng, reqs):
    """Drive the engine as the scheduler does (``tests/test_olmo_hybrid.py``
    has the same loop): admit what fits, one prefill chunk, one decode step
    over every running sequence, release at the last dispatch."""
    waiting, running, done = list(reqs), [], []
    while len(done) < len(reqs):
        for r in list(waiting):
            if eng.admit(r):
                waiting.remove(r)
                running.append(r)
        prefilling = [r for r in running if r.seq.prefilling]
        if prefilling:
            eng.prefill_next(prefilling[0])
        decoding = [r for r in running
                    if not r.seq.prefilling and not r.seq.done]
        if decoding:
            eng.decode(decoding)
        for r in [r for r in running if r.seq.done]:
            eng.release(r)
            running.remove(r)
        done += eng.collect(drain=not prefilling and not decoding)


def request(eng, rng, length, new=NEW):
    return eng.prepare_generate({
        "prompt_ids": rng.integers(0, MODEL.vocab_size, length).tolist(),
        "max_new_tokens": new, "logit_ids": LOGIT_IDS})


def worst_difference(params, req):
    """The served logits (the chosen token's and the ids asked for, at every
    generated position) against the reference's full forward over prompt +
    the served tokens."""
    n = len(req.prompt)
    rows = np.arange(n - 1, n - 1 + req.max_new_tokens)
    ref = np.asarray(reference.forward(
        params, model_dict(), list(req.prompt) + req.tokens, rows=rows))
    assert (ref.argmax(-1) == np.asarray(req.tokens)).all()
    return max(np.abs(ref.max(-1) - np.asarray(req.token_logits)).max(),
               np.abs(ref[:, LOGIT_IDS] - np.asarray(req.logits)).max())


@pytest.mark.parametrize("length,what", [
    (11, "shorter than the window"),
    (100, "several windows long, several chunks"),
    (72, "ends mid-page"),
])
def test_prefill_in_chunks_then_decode_equals_reference(engine, length,
                                                        what):
    """Prefill in chunks of at most 64 then decode through the state
    manager, against the reference's one full forward: logits, not
    tokens."""
    rng = np.random.default_rng(length)
    req = request(engine, rng, length)
    run_to_end(engine, [req])
    assert worst_difference(engine.params, req) < ATOL
    assert engine.seqstate.bytes_in_use == 0


def test_sequences_side_by_side_do_not_touch_each_other(engine):
    """Four sequences of different lengths resident at once, a fifth
    waiting for a slot: every one equals its own reference (rings and pages
    are a slot's own; an inactive slot's ring is left as it was)."""
    rng = np.random.default_rng(5)
    reqs = [request(engine, rng, n) for n in (9, 70, 33, 120, 17)]
    run_to_end(engine, reqs)
    for req in reqs:
        assert worst_difference(engine.params, req) < ATOL


def test_prefill_attention_is_counted_at_dispatch(engine):
    """A prompt of 100 tokens is two chunks in the 64 bucket. With the
    kernels on, each is one tile of 64 positions x 2 query heads a
    key/value head, which walks the pages up to its last position, in both
    full layers: 2 x (4 + 8) page steps; the ``jax.numpy`` path walks none
    of the kernel's."""
    from vilbert_multitask_tpu import obs

    def counted(name):
        return {key: v for inst in obs.REGISTRY.instruments()
                if inst.name == name for key, v in inst.collect().items()}

    def moved():
        chunks = counted("vmt_prefill_attention_chunks_total")
        pages = counted("vmt_prefill_attention_pages_total")
        return (chunks.get(("kernel",), 0), chunks.get(("xla",), 0),
                sum(pages.values()))

    before = moved()
    run_to_end(engine, [request(engine, np.random.default_rng(1), 100)])
    delta = tuple(b - a for a, b in zip(before, moved()))
    assert delta == ((2, 0, 24) if engine.pallas_enabled else (0, 2, 0))


def test_prefill_chunk_with_kernels_equals_without():
    """``prefill_chunk`` twice over one prompt (a whole bucket, then a
    chunk shorter than its bucket, its pages scattered) with the kernels on
    (interpreted: the causal paged attention of ISSUE 33 and the expert
    product) and off: the head's logits and every page written agree."""
    from vilbert_multitask_tpu.engine.seqstate import SequenceState

    gen = generate_cfg().generate
    params = model_lib.init_params(MODEL, jax.random.PRNGKey(3), jnp.float32)
    tokens = np.random.default_rng(3).integers(0, MODEL.vocab_size, 64 + 41)
    row = np.full((32,), 32, np.int32)
    row[:7] = [9, 2, 30, 4, 17, 0, 11]
    step = jax.jit(model_lib.prefill_chunk, static_argnums=0)
    outs = {}
    for name, model in (("off", MODEL), ("on", dataclasses.replace(
            MODEL, use_pallas=True, pallas_interpret=True))):
        state = SequenceState(gen, model_lib.state_layout(
            model, gen.param_dtype)).allocate()
        for start, length in ((0, 64), (64, 41)):
            chunk = np.zeros((64,), np.int32)
            chunk[:length] = tokens[start:start + length]
            state, out = step(model, params, state, chunk, 1, start, length,
                              row, np.asarray(LOGIT_IDS, np.int32))
        outs[name] = (out, state)
    (out_on, state_on), (out_off, state_off) = outs["on"], outs["off"]
    assert int(out_on["token"]) == int(out_off["token"])
    assert np.abs(np.asarray(out_on["logits"])
                  - np.asarray(out_off["logits"])).max() < ATOL
    for pool in ("k", "v"):
        written = np.asarray(state_on[pool])[:, row[:7]]
        assert np.abs(written).max() > 0.1
        assert np.abs(written - np.asarray(state_off[pool])[:, row[:7]]
                      ).max() < ATOL


def test_the_share_adds_up():
    """With ``held`` = each half of the experts in turn, the two partial
    outputs of one sparse layer, the shared expert counted once, sum to the
    uncut reference's output of the layer (the one test that ties the share
    to the model)."""
    whole = dataclasses.replace(MODEL, experts_held=None)
    params = model_lib.init_params(whole, jax.random.PRNGKey(3),
                                   jnp.float32)
    l = whole.sparse_layers[1]
    lp = params["layers"][l]
    h = jax.random.normal(jax.random.PRNGKey(4), (24, whole.hidden_size))
    shared, routed = reference.sparse_ffn(h, lp, model_dict(whole))
    hn = model_lib._rms(h, lp["mlp_norm"], whole.rms_norm_eps)
    total = -shared
    pairs = 0
    for first in (0, 8):
        half = dataclasses.replace(whole, experts_held=(first, 8))
        mine = dict(lp, experts_gate_up=lp["experts_gate_up"][first:first + 8],
                    experts_down=lp["experts_down"][first:first + 8])
        part, stats = model_lib._ffn(half, l, hn, mine,
                                     jnp.ones((24,), bool))
        total = total + part
        pairs += int(stats[0])
        # and the reference, given the same share, computes the same part
        shared_r, routed_r = reference.sparse_ffn(h, mine, model_dict(half))
        assert np.abs(np.asarray(part - shared_r - routed_r)).max() < 1e-5
    assert pairs == 24 * whole.num_experts_per_tok
    assert np.abs(np.asarray(total - shared - routed)).max() < 1e-5
    assert np.abs(np.asarray(routed)).max() > 0.1


def _expert_case(routing, H=64, W=32, held=(4, 8), k=4, E=16):
    T = 48 if routing == "even" else 40
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (T, H))
    gate_up = jax.random.normal(ks[1], (held[1], H, 2 * W)) / 8
    down = jax.random.normal(ks[2], (held[1], W, H)) / 6
    logits = jax.random.normal(ks[3], (T, E))
    experts, weights = moe.route(logits, k, 2.5)
    if routing == "even":        # token t -> experts t, t+1, .. (mod E)
        experts = (jnp.arange(T)[:, None] + jnp.arange(k)[None]) % E
    elif routing == "one_held":  # every pair to one held expert
        experts = jnp.full_like(experts, held[0] + 3)
    elif routing == "none_here":
        experts = jnp.zeros_like(experts) + jnp.arange(k)[None] % held[0]
    return x, experts.astype(jnp.int32), weights, held, gate_up, down


@pytest.mark.parametrize("routing,want", [
    ("drawn", None), ("even", (96, 8, 12)), ("one_held", (160, 1, 160)),
    ("none_here", (0, 0, 0)),
])
def test_expert_layer_equals_its_oracle(routing, want):
    """``ops/moe.py``'s sorted pairs and grouped product (the kernel in the
    interpreter) against the plain masked sum: under drawn routing, even
    routing, every token to one held expert, and none here (zero pairs: the
    result is exactly 0 and nothing divides by zero). Padding rows are
    routed nowhere."""
    args = _expert_case(routing)
    real = jnp.arange(args[0].shape[0]) < 33 if routing == "drawn" else None
    a, stats_a = moe.experts_oracle(*args, real)
    b, stats_b = jax.jit(lambda *xs: moe.experts_forward(
        *xs[:3], args[3], *xs[3:], real, interpret=True))(
            *args[:3], *args[4:])
    assert (np.asarray(stats_a) == np.asarray(stats_b)).all()
    if want is not None:
        assert tuple(int(v) for v in stats_b) == want
    assert np.isfinite(np.asarray(b)).all()
    assert np.abs(np.asarray(a - b)).max() < 1e-5
    if routing == "none_here":
        assert not np.asarray(b).any()
    if real is not None:
        assert not np.asarray(b)[33:].any()


def test_route_takes_the_top_k_of_all_and_scales():
    logits = jnp.asarray([[0.0, 3.0, 1.0, 2.0, -1.0]])
    experts, weights = moe.route(logits, 2, 2.5)
    assert experts.tolist() == [[1, 3]]
    p = np.exp([3.0, 2.0])
    assert np.allclose(np.asarray(weights), 2.5 * p / p.sum(), atol=1e-6)


def _yarn_closed_form(rope, r, position):
    """The angle's cos and sin by the formulas of the module text, in
    float64."""
    i = np.arange(r // 2)
    f = float(rope["rope_theta"]) ** (-2.0 * i / r)
    if rope["rope_type"] == "default":
        return np.cos(position * f), np.sin(position * f)
    theta, original = float(rope["rope_theta"]), float(
        rope["original_max_position_embeddings"])

    def c(n):
        return r * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(c(rope["beta_fast"])), 0)
    high = min(math.ceil(c(rope["beta_slow"])), r - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    f = f * (1 - ramp) + f / float(rope["factor"]) * ramp
    a = rope["attention_factor"]
    return a * np.cos(position * f), a * np.sin(position * f)


@pytest.mark.parametrize("kind", [FULL_ATTENTION, SLIDING_ATTENTION])
@pytest.mark.parametrize("position", [0, 8191, 8192, 40000])
def test_rotary_equals_its_closed_form(kind, position):
    """Both schemes at the published sizes (d 128; YaRN over the first 64
    dimensions with theta 500,000, factor 128, 8192 original positions;
    plain over all 128 with theta 10,000), below, at and far past YaRN's
    original context. The program's angle is a float32 product: at position
    40,000 that alone is good to 40,000 * 2^-24 = 2.4e-3 radians."""
    cfg = LagunaConfig()
    rope = cfg.rope[kind]
    r = int(cfg.head_dim * rope["partial_rotary_factor"])
    cos, sin = _yarn_closed_form(rope, r, position)
    x = np.random.default_rng(0).normal(size=(1, 2, cfg.head_dim))
    want = np.concatenate([
        x[..., :r // 2] * cos - x[..., r // 2:r] * sin,
        x[..., r // 2:r] * cos + x[..., :r // 2] * sin, x[..., r:]], -1)
    got = model_lib._rotate(cfg, kind, jnp.asarray(x, jnp.float32),
                            jnp.asarray([position]))
    tol = 1e-5 + 4 * position * 2.0 ** -24 * np.abs(x).max() * 1.5
    assert np.abs(np.asarray(got) - want).max() < tol
    # and the reference's own tables, written independently
    cos_r, sin_r = reference.rotary_tables(
        {"head_dim": cfg.head_dim, "rope_parameters": cfg.rope}, kind,
        [position])
    assert np.abs(np.asarray(cos_r)[0] - cos).max() < tol
    assert np.abs(np.asarray(sin_r)[0] - sin).max() < tol
    if kind == FULL_ATTENTION:
        # the ramp is crossed: the fastest pair rotates as published, the
        # slowest 128 times slower, and the unrotated half passes
        inv, scale = model_lib.rotary_frequencies(cfg, kind)
        assert inv[0] == 1.0 and scale == rope["attention_factor"]
        assert np.isclose(inv[-1] * 128, 500000.0 ** (-62 / 64), rtol=1e-6)
        assert (np.asarray(got)[..., r:] == x[..., r:].astype(
            np.float32)).all()


def test_config_refuses_what_is_not_implemented():
    for bad in (dict(gating="per-layer"), dict(norm_topk_prob=False),
                dict(moe_router_logit_softcapping=30.0),
                dict(experts_held=(200, 100)),
                dict(layer_types=("linear_attention",) * 48),
                dict(mlp_only_layers=(1,))):
        with pytest.raises(ValueError):
            dataclasses.replace(LagunaConfig(), **bad)
    five = LagunaConfig().cut(5)
    assert five.full_layers == (0, 4) and five.sliding_layers == (1, 2, 3)
    assert five.sparse_layers == (1, 2, 3, 4)
    assert five.num_attention_heads_per_layer == (48, 72, 72, 72, 48)
