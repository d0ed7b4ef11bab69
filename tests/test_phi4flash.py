"""The decoder-hybrid-decoder against its plain reference (ISSUE 34): seeded
random weights, a tiny size (hidden 64, 8 / 4 heads of 8, state 4, window
16, page 16, vocabulary 384; 8 layers: Mamba and window twice, the memory's
Mamba, the full layer, one gated memory unit, one cross layer; one case at
12 layers, where those two come three times), float32 storage and compute
on the CPU.

Tolerances. The engine and ``benchmark/reference/phi4flash.py`` compute the
same function two ways (a prefill that stops half-way down the stack
against every layer over every position, a chunked scan against a
token-by-token recurrence, zero-padded pair rows through grouped attention
against the two maps written out, a ring and pages against masked full
products), both in float32: what is left is summation order, read here at
3e-5 on logits of unit spread. ``ATOL`` allows 3e-4.
"""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4flash as reference
from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    FrameworkConfig,
    GenerateConfig,
    Phi4FlashConfig,
)
from vilbert_multitask_tpu.engine.generate import GenerateEngine
from vilbert_multitask_tpu.models import phi4flash as model_lib
from vilbert_multitask_tpu.models.decoder import _decode_attention
from vilbert_multitask_tpu.ops import paged_attention

ATOL = 3e-4
LOGIT_IDS = [1, 2, 3, 300]
NEW = 6

MODEL = Phi4FlashConfig().tiny()
DEEPER = dataclasses.replace(MODEL, num_hidden_layers=12)


def generate_cfg(model=MODEL, **over) -> FrameworkConfig:
    gen = GenerateConfig(
        model=model, param_dtype="float32", prefill_buckets=(32, 64),
        decode_buckets=(2, 4), slots=4, kv_pages=32, page_size=16,
        decode_attention_pages=4)
    return FrameworkConfig(generate=dataclasses.replace(gen, **over))


@pytest.fixture(scope="module", params=["jnp", "pallas_interpret"])
def engine(request):
    """The engine the CPU serves with, and the one the chip does with its
    kernels (the selective scan, both paged attentions) in the
    interpreter."""
    model = MODEL if request.param == "jnp" else dataclasses.replace(
        MODEL, use_pallas=True, pallas_interpret=True)
    eng = GenerateEngine(generate_cfg(model))
    eng.warmup()
    return eng


def run_to_end(eng, reqs):
    """Drive the engine as the scheduler does (``tests/test_olmo_hybrid.py``
    has the same loop)."""
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


def worst_difference(eng, req):
    """The served logits (the chosen token's and the ids asked for, at every
    generated position) against the reference's full forward over prompt +
    the served tokens."""
    n = len(req.prompt)
    rows = np.arange(n - 1, n - 1 + req.max_new_tokens)
    ref = np.asarray(reference.forward(
        eng.params, dataclasses.asdict(eng.model_cfg),
        list(req.prompt) + req.tokens, rows=rows))
    assert (ref.argmax(-1) == np.asarray(req.tokens)).all()
    return max(np.abs(ref.max(-1) - np.asarray(req.token_logits)).max(),
               np.abs(ref[:, LOGIT_IDS] - np.asarray(req.logits)).max())


def counter(name, **labels):
    return obs.REGISTRY.counter(name, labelnames=tuple(labels)).value(
        **labels)


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
    assert worst_difference(engine, req) < ATOL
    assert engine.seqstate.bytes_in_use == 0


def test_sequences_side_by_side_do_not_touch_each_other(engine):
    """Four sequences of different lengths resident at once, a fifth
    waiting for a slot: every one equals its own reference (state, rings
    and pages are a slot's own; an inactive slot's are left as they
    were)."""
    rng = np.random.default_rng(5)
    reqs = [request(engine, rng, n) for n in (9, 70, 33, 120, 17)]
    run_to_end(engine, reqs)
    for req in reqs:
        assert worst_difference(engine, req) < ATOL


def test_the_split_is_exact_and_counted(engine):
    """A prompt of 100 tokens is two chunks; the cross-decoder runs once,
    for its last row, and the first generated token's logits equal the
    reference's, which ran every layer over every position."""
    rng = np.random.default_rng(21)
    req = request(engine, rng, 100, new=1)
    before = {n: counter(n, program="prefill") for n in (
        "vmt_self_decoder_rows_total", "vmt_cross_decoder_rows_total",
        "vmt_ssm_scan_tokens_total")}
    run_to_end(engine, [req])
    rose = {n: counter(n, program="prefill") - v for n, v in before.items()}
    assert rose == {"vmt_self_decoder_rows_total": 100,
                    "vmt_cross_decoder_rows_total": 1,
                    "vmt_ssm_scan_tokens_total": 300}
    assert worst_difference(engine, req) < ATOL


def test_decode_counts_every_row_through_both_halves(engine):
    rng = np.random.default_rng(22)
    reqs = [request(engine, rng, 40, new=3)]
    names = ("vmt_self_decoder_rows_total", "vmt_cross_decoder_rows_total")
    before = [counter(n, program="decode") for n in names]
    reads = obs.REGISTRY.counter("vmt_shared_kv_page_reads_total")
    reads_before = reads.value()
    run_to_end(engine, reqs)
    assert [counter(n, program="decode") - v
            for n, v in zip(names, before)] == [2, 2]
    # Two decode steps, each over the 3 pages in use, read by the full
    # layer and the one cross layer.
    assert reads.value() - reads_before == 2 * 3 * 2


def test_twelve_layers_have_three_memory_units_and_cross_layers():
    """Three gated memory units read one layer's scan output and three
    cross layers one layer's pages; the layout still has one paged layer."""
    assert DEEPER.layer_kinds == (
        "mamba", "window", "mamba", "window", "mamba", "window", "mamba",
        "full", "gmu", "cross", "gmu", "cross")
    eng = GenerateEngine(generate_cfg(DEEPER))
    assert eng.seqstate.layout.paged_layers == 1
    assert model_lib.step_work(DEEPER) == {"ssm_layers": 4,
                                           "pool_readers": 3}
    rng = np.random.default_rng(12)
    reqs = [request(eng, rng, n) for n in (70, 23)]
    run_to_end(eng, reqs)
    for req in reqs:
        assert worst_difference(eng, req) < ATOL


def test_published_layer_kinds():
    cfg = Phi4FlashConfig()
    kinds = cfg.layer_kinds
    assert kinds[:16] == ("mamba", "window") * 8
    assert kinds[16:18] == ("mamba", "full")
    assert kinds[18:] == ("gmu", "cross") * 7
    assert (cfg.d_inner, cfg.dt_rank, cfg.head_dim,
            cfg.self_decoder_layers) == (5120, 160, 64, 18)
    assert model_lib.step_work(cfg) == {"ssm_layers": 9, "pool_readers": 8}


@pytest.mark.parametrize("bad,why", [
    (dict(mb_per_layer=4), "mb_per_layer"),
    (dict(num_hidden_layers=10), "multiple of 4"),
    (dict(tie_word_embeddings=False), "tied head"),
    (dict(num_key_value_heads=5), "pairs heads"),
    (dict(resid_pdrop=0.1), "dropout"),
])
def test_config_says_what_is_not_implemented(bad, why):
    with pytest.raises(ValueError, match=why):
        dataclasses.replace(MODEL, **bad)


# ------------------------------------------------- differential attention
def direct_differential(q, k, v, lam, group, mask):
    """The formula written out: ``q`` [T, n, d], ``k``, ``v`` [S, kv, d],
    ``mask`` [T, S]; returns ``o`` [T, n / 2, 2 d]."""
    T, n, d = q.shape
    out = np.zeros((T, n // 2, 2 * d))
    for i in range(n // 2):
        j = i // group
        V = np.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], -1)
        maps = []
        for s in (0, 1):
            scores = q[:, 2 * i + s] @ k[:, 2 * j + s].T / math.sqrt(d)
            scores = np.where(mask, scores, -np.inf)
            e = np.exp(scores - scores.max(-1, keepdims=True))
            maps.append(e / e.sum(-1, keepdims=True))
        out[:, i] = (maps[0] - lam * maps[1]) @ V
    return out


@pytest.mark.parametrize("group", [1, 2])
@pytest.mark.parametrize("lam", [0.0, 0.37])
@pytest.mark.parametrize("kernel", [False, True])
def test_pair_rows_through_grouped_attention_are_differential_attention(
        group, lam, kernel):
    """Zero-padded pair rows through the shared decode attention (the
    ``jax.numpy`` oracle and the Pallas kernel in the interpreter), then
    the subtraction: the formula evaluated directly. ``lam`` = 0 leaves
    plain attention over the first map."""
    kv, d, page, pages = 4, 8, 16, 4
    n = kv * group
    cfg = dataclasses.replace(MODEL, num_attention_heads=n,
                              num_key_value_heads=kv,
                              hidden_size=n * d)
    rng = np.random.default_rng(group)
    S = 40                              # positions 0 .. 39 of one sequence
    q = rng.normal(size=(1, n, d))
    k, v = rng.normal(size=(S, kv, d)), rng.normal(size=(S, kv, d))
    want = direct_differential(q, k, v, lam, group, np.ones((1, S), bool))

    def pool(rows):                     # [S, kv, d] -> [1, pages + 1, kv/2, page, 2d]
        padded = np.zeros((pages * page, kv // 2, 2 * d), np.float32)
        padded[:S] = rows.reshape(S, kv // 2, 2 * d)
        paged = padded.reshape(pages, page, kv // 2, 2 * d)
        paged = np.concatenate([paged, np.zeros_like(paged[:1])])
        return jnp.asarray(np.swapaxes(paged, 1, 2)[None])

    rows = model_lib._pair_queries(cfg, jnp.asarray(
        q.reshape(1, n * d), jnp.float32), jnp.float32)
    args = (rows, pool(k), pool(v), 0, jnp.asarray([S - 1], jnp.int32),
            jnp.zeros((pages,), jnp.int32),
            jnp.arange(pages, dtype=jnp.int32), 1, pages)
    ctx = (paged_attention.paged_decode_attention(*args, interpret=True)
           if kernel else _decode_attention(cfg, *args))
    ctx = np.asarray(ctx).reshape(1, kv // 2, 2, group, 2 * d)
    got = (ctx[:, :, 0] - lam * ctx[:, :, 1]).reshape(1, n // 2, 2 * d)
    assert np.abs(got - want).max() < 2e-5
    if lam == 0.0:
        plain = direct_differential(q, k, v, 0.0, group,
                                    np.ones((1, S), bool))
        assert np.abs(got - plain).max() < 2e-5


def test_lambda_follows_the_layer():
    lp = {k: jnp.full((8,), v) for k, v in (
        ("lambda_q1", 0.1), ("lambda_k1", 0.2), ("lambda_q2", 0.3),
        ("lambda_k2", -0.1))}
    lam, init = model_lib._lambda(5, lp)
    assert init == pytest.approx(0.8 - 0.6 * math.exp(-1.5))
    assert float(lam) == pytest.approx(
        math.exp(8 * 0.02) - math.exp(-8 * 0.03) + init, rel=1e-6)


# ------------------------------------------------------------------ layout
def test_a_long_prompt_holds_no_more_slot_bytes_than_a_short_one():
    """16 windows of prompt against one: the same slot bytes (state and
    rings), more pages, and those of one layer whatever the depth."""
    from vilbert_multitask_tpu.engine.seqstate import SequenceState

    gen = generate_cfg().generate
    st = SequenceState(gen, model_lib.state_layout(MODEL, "float32"))
    short = st.admit(16, 4)
    used_short = st.bytes_in_use
    long = st.admit(256, 4)
    assert (st.bytes_in_use - used_short
            - len(long.pages) * st.page_bytes) == st.slot_bytes
    assert used_short - len(short.pages) * st.page_bytes == st.slot_bytes
    deep = SequenceState(dataclasses.replace(gen, model=DEEPER),
                         model_lib.state_layout(DEEPER, "float32"))
    assert deep.page_bytes == st.page_bytes
    assert deep.pool_shape[0] == 1
