"""The CCA decoder with an MLP router against its plain reference: seeded
random weights, a tiny size (hidden 64, 2 layers, 4 query and 2 key/value
heads of 16 with half of each rotated, 4 experts of 32 and a "none" choice,
a router 16 wide, page 16, vocabulary 512), float32 storage and compute on
the CPU.

Tolerances. The engine and ``benchmark/reference/zaya.py`` compute the same
function two ways (paged and streamed attention against a blocked one, the
convolutions' carried rows against shifted whole sequences, sorted pairs
against expert-by-expert rows, a cache against a full forward), both in
float32: what is left is summation order, read here below 2e-5 on logits of
unit spread. ``ATOL`` allows 2e-4. A router's top-1 choice turned the
other way by that rounding would move a logit by far more: at this size
and these seeds none turns (the tests compare the choices too).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import zaya as reference
from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    FrameworkConfig,
    GenerateConfig,
    ZayaConfig,
)
from vilbert_multitask_tpu.engine.generate import GenerateEngine
from vilbert_multitask_tpu.models import zaya as model_lib

ATOL = 2e-4
LOGIT_IDS = [1, 2, 3, 500]
NEW = 5

MODEL = ZayaConfig().tiny()


def generate_cfg(model=MODEL, **over) -> FrameworkConfig:
    gen = GenerateConfig(
        model=model, param_dtype="float32", prefill_buckets=(64,),
        decode_buckets=(4,), slots=4, kv_pages=32, page_size=16,
        decode_attention_pages=4)
    return FrameworkConfig(generate=dataclasses.replace(gen, **over))


def model_dict(model=MODEL) -> dict:
    """The configuration as the source's ``config.json`` would say it."""
    return dict(dataclasses.asdict(model), rope_parameters=model.rope)


@pytest.fixture(scope="module")
def engines():
    """The engine the CPU serves with, and the one the chip does with its
    kernels (CCA's preparation, the expert product, both paged attentions)
    in the interpreter; one seed, so the same weights."""
    out = {}
    for name, model in (("jnp", MODEL), ("pallas_interpret",
                                          dataclasses.replace(
                                              MODEL, use_pallas=True,
                                              pallas_interpret=True))):
        out[name] = GenerateEngine(generate_cfg(model))
        out[name].warmup()
    return out


@pytest.fixture(params=["jnp", "pallas_interpret"])
def engine(request, engines):
    return engines[request.param]


experts_step = jax.jit(model_lib._experts, static_argnums=(0, 1))
router_step = jax.jit(model_lib._router, static_argnums=(0,))


def run_to_end(eng, reqs):
    """Drive the engine as the scheduler does (``tests/test_laguna.py`` has
    the same loop)."""
    waiting, running, done = list(reqs), [], []
    while len(done) < len(reqs):
        for r in list(waiting):
            if eng.admit(r):
                waiting = [x for x in waiting if x is not r]
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
            running = [x for x in running if x is not r]
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


def counted(name):
    return sum(v for inst in obs.REGISTRY.instruments() if inst.name == name
               for v in inst.collect().values())


def test_prefill_at_odd_boundaries_then_decode_equals_reference(engine):
    """A prompt of 101 tokens is a chunk of 64 then one of 37 (the carried
    rows cross a boundary mid-page), then decode through the cache, against
    the reference's one full forward: logits, not tokens. Every real row
    is counted at CCA's preparation, in both layers."""
    before = counted("vmt_cca_rows_total")
    req = request(engine, np.random.default_rng(101), 101)
    run_to_end(engine, [req])
    assert worst_difference(engine.params, req) < ATOL
    assert engine.seqstate.bytes_in_use == 0
    assert counted("vmt_cca_rows_total") - before == 2 * (101 + NEW - 1)


def test_sequences_side_by_side_and_a_reused_slot(engine):
    """Four sequences resident at once, more waiting: each slot is used
    again by a later sequence, whose carried rows and previous input must
    start from zero, not from what the slot's last sequence left; a slot
    that sits out a decode step keeps what it holds."""
    rng = np.random.default_rng(5)
    reqs = [request(engine, rng, n, new) for n, new in
            ((9, 7), (70, 2), (33, 5), (16, 3), (17, 4), (40, 2))]
    run_to_end(engine, reqs)
    for req in reqs:
        assert worst_difference(engine.params, req) < ATOL


def test_the_router_choices_are_the_references():
    """Layer by layer over the reference's own residual rows, the program's
    router makes the reference's top-1 choice (or "none") for every token,
    its weight is the reference's, and the draw makes use of every choice:
    the experts and "none"."""
    params = model_lib.init_params(MODEL, jax.random.PRNGKey(2), jnp.float32)
    tokens = np.random.default_rng(2).integers(0, MODEL.vocab_size, 64)
    cos, sin = reference.rotary_tables(model_dict(), np.arange(72))
    x = params["embed"][jnp.pad(jnp.asarray(tokens), (0, 8))].astype(
        jnp.float32)
    r_ref = r_prog = None
    seen = set()
    for l, lp in enumerate(params["layers"]):
        x = reference._attention(
            x, {k: lp[k] for k in reference.ATTENTION_KEYS}, cos, sin,
            jnp.int32(64), dims=(4, 2, 16, MODEL.rms_norm_eps), lower=None)
        xn, r_ref, choice, weight = reference._route(
            x, {k: lp[k] for k in reference.ROUTER_KEYS},
            jnp.zeros_like(x[:, :1]) if r_ref is None else r_ref,
            eps=MODEL.rms_norm_eps, first=l == 0)
        experts, weights, r_prog = router_step(l, xn, lp, r_prog)
        np.testing.assert_array_equal(experts[:64, 0], choice[:64])
        np.testing.assert_allclose(weights[:64, 0], weight[:64], atol=1e-5)
        seen |= set(np.asarray(choice[:64]).tolist())
        y = reference.routed_experts(xn, choice, weight, 64,
                                     lp["experts_gate_up"],
                                     lp["experts_down"])
        x = reference._merge(x, y, lp["moe_res_scale"], lp["moe_out_scale"])
    assert seen == set(range(MODEL.num_experts + 1))


def test_none_adds_nothing_and_is_counted():
    """Rows sent to "none" (the balancing bias makes it every row's
    choice) leave the expert sublayer as ``s * h``: nothing added, nothing
    computed, every real row counted as skipped."""
    params = model_lib.init_params(MODEL, jax.random.PRNGKey(4), jnp.float32)
    lp = dict(params["layers"][0],
              router_bias=jnp.zeros((MODEL.num_experts + 1,)).at[-1].set(1e4))
    h = jax.random.normal(jax.random.PRNGKey(5), (16, MODEL.hidden_size))
    real = jnp.arange(16) < 11
    out, _, stats, skipped = experts_step(MODEL, 0, h, lp, None, real)
    np.testing.assert_array_equal(out, lp["moe_res_scale"] * h)
    assert int(stats[0]) == 0 and int(skipped) == 11


def test_depth_average_carries_the_router_state():
    """Layer 1's router reads ``r_1 + gamma_1 r~_0``: with ``gamma_1`` 0 the
    router state leaving it is its own projection alone, and the reference
    agrees on the choices either way."""
    params = model_lib.init_params(MODEL, jax.random.PRNGKey(6), jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(7), (32, MODEL.hidden_size))
    real = jnp.ones((32,), bool)
    lp0, lp1 = params["layers"]
    _, r0, _, _ = experts_step(MODEL, 0, h, lp0, None, real)
    xn = model_lib._rms(h, lp1["mlp_norm"], MODEL.rms_norm_eps)
    _, r1, _, _ = experts_step(MODEL, 1, h, lp1, r0, real)
    np.testing.assert_allclose(
        r1, xn @ lp1["router"] + lp1["router_eda"][0] * r0, atol=1e-5)
    flat = dict(lp1, router_eda=jnp.zeros((1,)))
    _, r1_flat, _, _ = experts_step(MODEL, 1, h, flat, r0, real)
    np.testing.assert_allclose(r1_flat, xn @ lp1["router"], atol=1e-5)
    assert float(jnp.abs(r1 - r1_flat).max()) > 0.1
    for layer in (lp1, flat):
        small = {k: layer[k] for k in reference.ROUTER_KEYS}
        _, r_ref, choice, _ = reference._route(
            h, small, r0, eps=MODEL.rms_norm_eps, first=False)
        experts, _, r_prog = router_step(1, xn, layer, r0)
        np.testing.assert_allclose(r_ref, r_prog, atol=1e-5)
        np.testing.assert_array_equal(choice, experts[:, 0])


def test_prefill_chunk_with_kernels_equals_without(engines):
    """Each engine's prefill program twice over one prompt (a whole bucket,
    then a chunk of 41, pages scattered) with the kernels on (interpreted)
    and off: the head's logits, every page written and the slot's carried
    rows and previous input agree."""
    from vilbert_multitask_tpu.engine.seqstate import SequenceState

    tokens = np.random.default_rng(3).integers(0, MODEL.vocab_size, 64 + 41)
    row = np.full((32,), 32, np.int32)
    row[:7] = [9, 2, 30, 4, 17, 0, 11]
    outs = {}
    for name, eng in engines.items():
        state = SequenceState(eng.gen, eng.seqstate.layout).allocate()
        for start, length in ((0, 64), (64, 41)):
            chunk = np.zeros((64,), np.int32)
            chunk[:length] = tokens[start:start + length]
            x = dict(eng._host_inputs("prefill", 64), tokens=chunk,
                     slot=np.int32(1), start=np.int32(start),
                     length=np.int32(length), page_row=row,
                     logit_ids=np.asarray(LOGIT_IDS, np.int32))
            state, out = eng._program("prefill", 64)(eng.params, state, x)
        outs[name] = (out, state)
    (out_on, state_on), (out_off, state_off) = (outs["pallas_interpret"],
                                                outs["jnp"])
    assert int(out_on["token"]) == int(out_off["token"])
    assert np.abs(np.asarray(out_on["logits"])
                  - np.asarray(out_off["logits"])).max() < ATOL
    for pool in ("k", "v"):
        written = np.asarray(state_on[pool])[:, row[:7]]
        assert np.abs(written).max() > 0.1
        assert np.abs(written - np.asarray(state_off[pool])[:, row[:7]]
                      ).max() < ATOL
    for name in ("cca_conv", "cca_prev"):
        np.testing.assert_allclose(state_on[name], state_off[name],
                                   atol=ATOL)
        assert np.abs(np.asarray(state_on[name])[:, 1]).max() > 0.1
        assert not np.asarray(state_on[name])[:, 0].any()


def test_the_value_shift_reads_the_token_before():
    """Key/value head 1 of position t is ``W_v2`` of position t - 1's
    normed input: of the carried row at a chunk's first position, and 0 at
    a sequence's start."""
    params = model_lib.init_params(MODEL, jax.random.PRNGKey(8), jnp.float32)
    lp = params["layers"][0]
    xn = jax.random.normal(jax.random.PRNGKey(9), (6, MODEL.hidden_size))
    carried = jax.random.normal(jax.random.PRNGKey(10), (MODEL.hidden_size,))
    d = MODEL.head_dim
    for before in (carried, jnp.zeros_like(carried)):
        v = model_lib._values(MODEL, xn, jnp.concatenate(
            [before[None], xn[:-1]]), lp)
        np.testing.assert_allclose(v[:, 0], xn @ lp["wv"][:, :d], atol=1e-5)
        np.testing.assert_allclose(v[1:, 1], xn[:-1] @ lp["wv"][:, d:],
                                   atol=1e-5)
        np.testing.assert_allclose(v[0, 1], before @ lp["wv"][:, d:],
                                   atol=1e-5)


def test_config_refuses_what_is_not_implemented():
    for bad in (dict(cca_time0=3), dict(num_experts_per_tok=2),
                dict(zaya_use_mod=False), dict(sliding_window=4096),
                dict(tie_word_embeddings=False),
                dict(layer_types=("hybrid",) * 39)):
        with pytest.raises(ValueError):
            dataclasses.replace(ZayaConfig(), **bad)
