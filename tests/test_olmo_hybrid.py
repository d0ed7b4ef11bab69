"""The hybrid decoder against its plain reference (ISSUE 28, tests a, b, d):
seeded random weights, a tiny size (hidden 64, 2 periods of 3 linear + 1
full layer, 4 heads of d_k 8 / d_v 16, vocabulary 512), float32 storage and
compute on the CPU.

Tolerances. The engine and ``benchmark/reference/olmo_hybrid.py`` compute
the same function two ways (chunked scan against token-by-token recurrence,
paged and streamed attention against one softmax, a cache against a full
forward), both in float32: what is left is summation order, read here at
3e-5 on logits of unit spread. ``ATOL`` allows 2e-4. Each omission of test
(d) moves a logit by 1e-2 or more, fifty times that.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as reference
from vilbert_multitask_tpu.config import (
    FrameworkConfig,
    GenerateConfig,
    OlmoHybridConfig,
)
from vilbert_multitask_tpu.engine.generate import GenerateEngine
from vilbert_multitask_tpu.models import olmo_hybrid as model_lib
from vilbert_multitask_tpu.ops import gated_delta

ATOL = 2e-4
BROKEN = 1e-2
LOGIT_IDS = [1, 2, 3, 500]
NEW = 8

MODEL = OlmoHybridConfig().tiny()


def generate_cfg(**over) -> FrameworkConfig:
    gen = GenerateConfig(
        model=MODEL, param_dtype="float32", prefill_buckets=(64, 128),
        decode_buckets=(2, 4), slots=4, kv_pages=32, page_size=16,
        decode_attention_pages=4)
    return FrameworkConfig(generate=dataclasses.replace(gen, **over))


@pytest.fixture(scope="module", params=["jnp", "pallas_interpret"])
def engine(request):
    """The engine the CPU serves with, and the one the chip does with its
    kernels (the scan, the paged decode attention) in the interpreter."""
    model = MODEL if request.param == "jnp" else dataclasses.replace(
        MODEL, use_pallas_scan=True, pallas_interpret=True)
    eng = GenerateEngine(generate_cfg(model=model))
    eng.warmup()
    return eng


def model_dict() -> dict:
    return dataclasses.asdict(MODEL)


def run_to_end(eng, arrive):
    """Drive the engine as the scheduler does: each iteration admits what
    has arrived and fits, runs one prefill chunk, then one decode step
    over every running sequence, and releases a sequence as soon as its
    last token is dispatched. ``arrive``: iteration -> requests arriving
    then. Returns the iterations it took."""
    total = sum(len(reqs) for reqs in arrive.values())
    waiting, running, done, it = [], [], [], 0
    while len(done) < total:
        it += 1
        waiting += arrive.get(it, [])
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
    return it


def request(eng, rng, length, new=NEW):
    return eng.prepare_generate({
        "prompt_ids": rng.integers(0, MODEL.vocab_size, length).tolist(),
        "max_new_tokens": new, "logit_ids": LOGIT_IDS})


def worst_difference(params, req, model=None):
    """The served logits (the chosen token's and the ids asked for, at every
    generated position) against the reference's full forward over prompt +
    the served tokens."""
    n = len(req.prompt)
    rows = np.arange(n - 1, n - 1 + req.max_new_tokens)
    ref = np.asarray(reference.forward(
        params, model or model_dict(), list(req.prompt) + req.tokens,
        rows=rows))
    assert (ref.argmax(-1) == np.asarray(req.tokens)).all()
    return max(np.abs(ref.max(-1) - np.asarray(req.token_logits)).max(),
               np.abs(ref[:, LOGIT_IDS] - np.asarray(req.logits)).max())


def test_whole_prompt_prefill_equals_reference_at_every_position(engine):
    """(a) ``prefill_chunk`` over a whole prompt, asked for each position's
    logits in turn (``length`` = position + 1: the rows behind are padding
    and must change nothing)."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, MODEL.vocab_size, 50)
    ref = np.asarray(reference.forward(engine.params, model_dict(), prompt))
    for length in (1, 17, 33, 50):
        req = engine.prepare_generate({
            "prompt_ids": prompt[:length].tolist(), "max_new_tokens": 1,
            "logit_ids": LOGIT_IDS})
        run_to_end(engine, {1: [req]})
        assert abs(req.token_logits[0] - ref[length - 1].max()) < ATOL
        assert np.abs(np.asarray(req.logits[0])
                      - ref[length - 1, LOGIT_IDS]).max() < ATOL


def test_chunked_prefill_and_decode_equal_reference_full_forward(engine):
    """(b) prefill in two or three chunks of unequal, padded length, then 8
    decode steps through the state manager, for 3 sequences of different
    length decoded in one batch with a fourth admitted mid-way."""
    rng = np.random.default_rng(0)
    first = [request(engine, rng, n) for n in (150, 70, 200)]
    fourth = request(engine, rng, 33)
    # Iteration 6: the first sequence (two chunks) has begun to decode.
    run_to_end(engine, {1: first, 6: [fourth]})
    assert fourth.seq.slot == 3
    for req in first + [fourth]:
        assert len(req.tokens) == NEW
        assert worst_difference(engine.params, req) < ATOL
    assert not engine.seqstate.live()


def test_prefill_chunk_with_kernels_equals_without():
    """``prefill_chunk`` twice over one prompt (a whole bucket, then a
    chunk shorter than its bucket, its pages scattered) with the kernels on
    (interpreted: the scan and the causal paged attention of ISSUE 33) and
    off: the head's logits and every page written agree."""
    from vilbert_multitask_tpu.engine.seqstate import SequenceState

    gen = generate_cfg().generate
    params = model_lib.init_params(MODEL, jax.random.PRNGKey(3), jnp.float32)
    tokens = np.random.default_rng(3).integers(0, MODEL.vocab_size, 64 + 41)
    row = np.full((32,), 32, np.int32)
    row[:7] = [9, 2, 30, 4, 17, 0, 11]
    step = jax.jit(model_lib.prefill_chunk, static_argnums=0)
    outs = {}
    for name, model in (("off", MODEL), ("on", dataclasses.replace(
            MODEL, use_pallas_scan=True, pallas_interpret=True))):
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


def test_a_freed_slot_and_its_pages_serve_the_next_sequence(engine):
    """Five sequences over four slots, arriving one by one: the fifth
    waits for a slot, then runs in the slot and pages another has just
    given back, while the tables the earlier steps were dispatched with
    are already changing (each step must have carried its own copy)."""
    rng = np.random.default_rng(5)
    reqs = [request(engine, rng, n, new=6) for n in (150, 70, 33, 200, 9)]
    run_to_end(engine, {it: [r] for it, r in zip((1, 4, 5, 6, 7), reqs)})
    assert reqs[4].seq.slot == reqs[0].seq.slot == 0
    for req in reqs:
        assert worst_difference(engine.params, req) < ATOL


def test_unreal_requests_are_refused(engine):
    with pytest.raises(ValueError, match="exceed the context"):
        engine.prepare_generate({"prompt_ids": [1] * 600,
                                 "max_new_tokens": 8})
    with pytest.raises(ValueError, match="lie in"):
        engine.prepare_generate({"prompt_ids": [1, 9999],
                                 "max_new_tokens": 8})
    with pytest.raises(ValueError, match="at most"):
        engine.prepare_generate({"prompt_ids": [1], "max_new_tokens": 1,
                                 "logit_ids": list(range(17))})


def _broken(monkeypatch, what):
    """An engine with one piece of the mathematics left out."""
    if what == "state_dropped_at_chunk_boundary":
        real = model_lib._prefill_linear
        monkeypatch.setattr(
            model_lib, "_prefill_linear",
            lambda cfg, x, lp, rec, tail, *a: real(
                cfg, x, lp, jnp.zeros_like(rec), tail, *a))
    elif what == "convolution_tail_dropped":
        real = model_lib._prefill_linear
        monkeypatch.setattr(
            model_lib, "_prefill_linear",
            lambda cfg, x, lp, rec, tail, *a: real(
                cfg, x, lp, rec, jnp.zeros_like(tail), *a))
    elif what == "beta_without_its_factor_2":
        return GenerateEngine(generate_cfg(model=dataclasses.replace(
            MODEL, linear_allow_neg_eigval=False)))
    elif what == "state_kept_in_bfloat16":
        real = gated_delta._chunk_update

        def rounded(state, *rest):
            out, new = real(state, *rest)
            return out, new.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(gated_delta, "_chunk_update", rounded)
    return GenerateEngine(generate_cfg())


@pytest.mark.parametrize("what", [
    "state_dropped_at_chunk_boundary", "convolution_tail_dropped",
    "beta_without_its_factor_2", "state_kept_in_bfloat16"])
def test_what_must_fail_does(monkeypatch, what):
    """(d) each omission moves (b)'s comparison past its tolerance. The
    state in bfloat16 is rounded after every chunk of 64 over a prompt of
    256 tokens."""
    eng = _broken(monkeypatch, what)
    rng = np.random.default_rng(3)
    req = request(eng, rng, 256 if what == "state_kept_in_bfloat16" else 150)
    run_to_end(eng, {1: [req]})
    n = len(req.prompt)
    ref = np.asarray(reference.forward(
        eng.params, model_dict(), list(req.prompt) + req.tokens,
        rows=np.arange(n - 1, n - 1 + NEW)))
    served = np.asarray(req.logits)
    assert np.abs(ref[:, LOGIT_IDS] - served).max() > BROKEN


def test_program_and_reference_share_the_tree():
    shapes = model_lib.param_shapes(MODEL)
    assert shapes == reference.param_shapes(model_dict())
    params = model_lib.init_params(MODEL, jax.random.PRNGKey(0), jnp.float32)
    assert jax.tree_util.tree_map(lambda a: a.shape, params) == shapes
    # The gate's decay spans about 0.9 to 0.999 before the input moves it.
    lin = params["linear"]
    alpha = np.exp(-np.exp(lin["A_log"]) * np.log1p(np.exp(lin["dt_bias"])))
    assert 0.89 < alpha.min() and alpha.max() < 0.9995


def test_layer_pattern_is_checked():
    with pytest.raises(ValueError, match="repeat one period"):
        dataclasses.replace(MODEL, layer_types=(
            "linear_attention", "full_attention") * 3
            + ("full_attention", "linear_attention"))
    assert (MODEL.period, MODEL.periods) == (4, 2)
    full = OlmoHybridConfig()
    assert (full.period, full.periods, full.head_dim) == (4, 8, 128)
    assert full.conv_width == 30 * (96 + 96 + 192)
