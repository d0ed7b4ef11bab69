"""Engine tests: every served task type end-to-end on a tiny model (CPU),
bucket padding invariance, and mesh-sharded execution on the virtual
8-device mesh (SURVEY.md §4 device-test strategy)."""

import dataclasses

import numpy as np
import pytest

from vilbert_multitask_tpu.config import (
    EngineConfig,
    FrameworkConfig,
    MeshConfig,
    TASK_REGISTRY,
)
from vilbert_multitask_tpu.engine import InferenceEngine
from vilbert_multitask_tpu.features.pipeline import RegionFeatures
from vilbert_multitask_tpu.parallel import build_mesh, param_specs


def make_regions(n, num_boxes=7, feat_dim=32, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        boxes = rng.uniform(0, 200, size=(num_boxes, 4)).astype(np.float32)
        boxes[:, 2:] = boxes[:, :2] + 10 + boxes[:, 2:] * 0.3
        out.append(
            RegionFeatures(
                features=rng.randn(num_boxes, feat_dim).astype(np.float32),
                boxes=np.clip(boxes, 0, 640),
                image_width=640,
                image_height=480,
            )
        )
    return out


def _cpu_engine_cfg(**kw):
    """XLA attention for CPU engine tests (kernel coverage lives in
    test_pallas_coattention; interpret-mode Pallas is ~10x slower here)."""
    kw.setdefault("use_pallas_coattention", False)
    kw.setdefault("use_pallas_self_attention", False)
    return EngineConfig(compute_dtype="float32", **kw)


@pytest.fixture(scope="module")
def engine(tiny_config):
    cfg = FrameworkConfig(
        model=tiny_config,
        engine=_cpu_engine_cfg(max_regions=11),
    )
    return InferenceEngine(cfg, seed=0)


def test_params_device_resident(engine):
    """BENCH_r02 regression: every param leaf must live on a device as a
    jax.Array after engine boot — host-numpy leaves silently re-upload the
    full tree on every jitted forward (the 23.7 s p50 of round 2). This is
    the JAX equivalent of the reference's one-time ``model.cuda(0)``
    (worker.py:534-536)."""
    import jax

    leaves = jax.tree_util.tree_leaves(engine.params)
    assert leaves
    for leaf in leaves:
        assert isinstance(leaf, jax.Array), type(leaf)
        assert not isinstance(leaf, np.ndarray)
        assert len(leaf.devices()) >= 1


def test_engine_device_pins_host_params(tiny_config):
    """Passing a host-numpy tree (the checkpoint-restore shape) must still
    yield device-resident params — the upload happens once, at boot."""
    import jax

    cfg = FrameworkConfig(
        model=tiny_config,
        engine=_cpu_engine_cfg(max_regions=11),
    )
    donor = InferenceEngine(cfg, seed=0)
    host_tree = jax.tree_util.tree_map(
        lambda x: np.asarray(x), donor.params)
    eng = InferenceEngine(cfg, params=host_tree)
    for leaf in jax.tree_util.tree_leaves(eng.params):
        assert isinstance(leaf, jax.Array) and not isinstance(leaf, np.ndarray)


def test_kernel_compile_error_propagates_out_of_warmup(tiny_config,
                                                       monkeypatch):
    """There is no automatic XLA fallback: a kernel the compiler refuses
    fails warmup() — and so the boot — with the compiler's own message,
    and the engine stays on the path its config names. The remedy is the
    explicit ``use_pallas_*`` flag, never a silent re-route."""
    from vilbert_multitask_tpu.ops import coattention

    cfg = FrameworkConfig(
        model=tiny_config,
        engine=EngineConfig(compute_dtype="float32", max_regions=11),
    )
    # Construction never compiles the kernel (init runs through an XLA
    # twin), so the engine builds; the refusal surfaces at warmup.
    eng = InferenceEngine(cfg, seed=0)
    assert eng.pallas_enabled
    # Pallas on, interpret not requested, backend not a TPU: an error, not
    # a slow interpreted success.
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        eng.warmup(buckets=(1,), parallel=False)

    def boom(*a, **k):
        raise RuntimeError("Mosaic rejected the kernel (simulated)")

    monkeypatch.setattr(coattention, "flash_cross_attention", boom)
    with pytest.raises(RuntimeError, match="Mosaic rejected the kernel"):
        eng.warmup(buckets=(2, 4))  # parallel pool: first error wins
    # ...and on a live request against an un-warmed bucket, the same way.
    regions = make_regions(2, feat_dim=cfg.model.v_feature_size)
    with pytest.raises(RuntimeError, match="Mosaic rejected the kernel"):
        eng.run(eng.prepare(7, "a man riding a horse", regions))
    assert eng.pallas_enabled  # nothing was rebuilt behind the caller


def test_vocab_overflow_fails_at_boot(tiny_config, caplog):
    """VERDICT r2 #7: a vocab bigger than the embedding table must fail at
    boot (on TPU an OOB gather clamps silently); a much-wider table warns."""
    import logging

    # Overflow: table with fewer rows than the committed 1,037-token vocab.
    # (The check runs before param init, so the failure is immediate.)
    small = dataclasses.replace(tiny_config, vocab_size=512)
    with pytest.raises(ValueError, match="index out of the embedding"):
        InferenceEngine(FrameworkConfig(
            model=small, engine=_cpu_engine_cfg(max_regions=11)))
    # Dead-weight gap: big table over the small vocab → warning, not error.
    # params={} skips the (slow, irrelevant) random init compile.
    wide = dataclasses.replace(tiny_config, vocab_size=30522)
    with caplog.at_level(logging.WARNING):
        InferenceEngine(FrameworkConfig(
            model=wide, engine=_cpu_engine_cfg(max_regions=11)), params={})
    assert any("dead weight" in r.message for r in caplog.records)


def test_engine_defaults_to_committed_assets(engine):
    """No tokenizer/label args → the committed vocab + reference-layout
    pickles load by default (never the in-memory demo vocab)."""
    assert engine.tokenizer.cls_id == 101  # bert-base-uncased layout
    assert len(engine.tokenizer.vocab) > 1000
    assert engine.labels.get("vqa")[0] == "yes"  # from the committed pickle
    assert len(engine.labels.get("vqa")) == 3129


TASK_QUESTIONS = {
    1: "what is the man holding",
    2: "what color is the car",
    15: "is the bowl right of the mug",
    4: "which object can you eat",
    11: "the woman in the red coat",
    16: "q: is it a person? a: no q: is it red? a: yes",
    13: "two dogs are playing in the snow",
    12: "both images contain two wolves",
    7: "a man riding a horse on the beach",
}


@pytest.mark.parametrize("task_id", sorted(
    t for t, spec in TASK_REGISTRY.items() if spec.decode != "generate"))
def test_all_tasks_end_to_end(engine, task_id):
    spec = TASK_REGISTRY[task_id]
    n = spec.min_images
    regions = make_regions(n, feat_dim=engine.cfg.model.v_feature_size)
    req = engine.prepare(task_id, TASK_QUESTIONS[task_id], regions)
    _, result = engine.run(req)
    assert result.task_id == task_id
    assert result.kind == spec.decode
    if spec.decode in ("labels", "binary", "trinary"):
        assert len(result.answers) == min(
            spec.top_k, {"binary": 2, "trinary": 3}.get(spec.decode, spec.top_k)
        )
        confs = [a["confidence"] for a in result.answers]
        assert confs == sorted(confs, reverse=True)
        assert all(0.0 <= c <= 1.0 for c in confs)
    elif spec.decode == "grounding":
        assert len(result.boxes) == spec.top_k
        for b in result.boxes:
            x1, y1, x2, y2 = b["box_xyxy"]
            assert 0 <= x1 <= 640 and 0 <= y2 <= 480 or b["is_global"]
    elif spec.decode == "ranking":
        assert len(result.ranking) == n
        assert [r["rank"] for r in result.ranking] == list(range(1, n + 1))


def test_retrieval_bucket_padding_invariance(engine):
    """3 candidates pad to the 4-bucket; scores of real rows must match an
    unpadded 2-candidate run row-for-row (pad rows never leak into decode)."""
    feat_dim = engine.cfg.model.v_feature_size
    regions = make_regions(3, feat_dim=feat_dim, seed=1)
    req3 = engine.prepare(7, "a dog on a beach", regions)
    assert req3.bucket == 4 and req3.n_images == 3
    _, res3 = engine.run(req3)
    assert len(res3.ranking) == 3

    req2 = engine.prepare(7, "a dog on a beach", regions[:2])
    assert req2.bucket == 2
    _, res2 = engine.run(req2)
    score3 = {r["image"]: r["score"] for r in res3.ranking}
    score2 = {r["image"]: r["score"] for r in res2.ranking}
    for k, v in score2.items():
        assert score3[k] == pytest.approx(v, abs=1e-4)


def test_nlvr2_requires_two_images(engine):
    regions = make_regions(1, feat_dim=engine.cfg.model.v_feature_size)
    with pytest.raises(ValueError, match="task 12"):
        engine.prepare(12, "both images", regions)


def test_guesswhat_dialog_reformat_changes_tokens(engine):
    """Task 16 reformats Q/A dialogs (fixing the reference's dead code,
    SURVEY.md §2.4) — its ids must differ from the raw-encoded query."""
    regions = make_regions(1, feat_dim=engine.cfg.model.v_feature_size)
    q = "q: is it a person? a: no"
    req16 = engine.prepare(16, q, regions)
    req11 = engine.prepare(11, q, regions)
    assert not np.array_equal(req16.text.input_ids, req11.text.input_ids)


def test_mesh_sharded_engine_matches_single_device(tiny_config):
    """dp×tp sharded run (virtual 8-device mesh) must reproduce the
    single-device logits — XLA collectives only change placement."""
    cfg = FrameworkConfig(
        model=tiny_config,
        engine=_cpu_engine_cfg(max_regions=11),
        mesh=MeshConfig(dp=4, tp=2),
    )
    base = InferenceEngine(cfg, seed=3)
    mesh = build_mesh(cfg.mesh)
    sharded = InferenceEngine(cfg, seed=3, mesh=mesh)

    regions = make_regions(2, feat_dim=cfg.model.v_feature_size, seed=5)
    req_a = base.prepare(12, "both images contain wolves", regions)
    req_b = sharded.prepare(12, "both images contain wolves", regions)
    out_a, res_a = base.run(req_a)
    out_b, res_b = sharded.run(req_b)
    np.testing.assert_allclose(
        np.asarray(out_a.vil_binary_prediction),
        np.asarray(out_b.vil_binary_prediction), atol=1e-4,
    )
    assert [a["answer"] for a in res_a.answers] == [
        a["answer"] for a in res_b.answers
    ]


def test_mesh_sharded_run_many_matches_single_device(tiny_config):
    """The batched path's mesh branch (_dispatch_many packs a whole-chunk
    device_put with batch shardings) must reproduce single-device decodes
    for a mixed single/multi-image backlog."""
    cfg = FrameworkConfig(
        model=tiny_config,
        engine=_cpu_engine_cfg(max_regions=11, image_buckets=(1, 2, 4),
                               throughput_buckets=(8,)),
        mesh=MeshConfig(dp=4, tp=2),
    )
    base = InferenceEngine(cfg, seed=3)
    sharded = InferenceEngine(cfg, seed=3, mesh=build_mesh(cfg.mesh))

    regions = make_regions(4, feat_dim=cfg.model.v_feature_size, seed=5)
    backlog = [
        (1, "what is the man holding", 1),
        (12, "both images contain wolves", 2),
        (7, "a red car parked outside", 4),
        (15, "is the bowl right of the mug", 1),
        (12, "both show dogs", 2),
    ]
    res_a = base.run_many([base.prepare(t, q, regions[:n])
                           for t, q, n in backlog])
    res_b = sharded.run_many([sharded.prepare(t, q, regions[:n])
                              for t, q, n in backlog])
    assert [r.kind for r in res_a] == [r.kind for r in res_b]
    for a, b in zip(res_a, res_b):
        if a.answers is not None:
            assert [x["answer"] for x in a.answers] == \
                [x["answer"] for x in b.answers]
        if a.ranking is not None:
            assert [x["image"] for x in a.ranking] == \
                [x["image"] for x in b.ranking]


def test_partition_rules_shard_big_matmuls(tiny_config):
    """TP rules must actually shard the FFN/QKV kernels when dims divide."""
    cfg = FrameworkConfig(
        model=tiny_config, engine=_cpu_engine_cfg(),
        mesh=MeshConfig(dp=4, tp=2),
    )
    eng = InferenceEngine(cfg, seed=0)
    mesh = build_mesh(cfg.mesh)
    specs = param_specs(eng.params, mesh)
    qkv = specs["bert"]["encoder"]["t_layer_0"]["attention"]["qkv"]["kernel"]
    assert tuple(qkv) == (None, "tp")
    ffn_out = specs["bert"]["encoder"]["t_layer_0"]["ffn"]["output"]["kernel"]
    assert tuple(ffn_out) == ("tp", None)
    norm = specs["bert"]["encoder"]["t_layer_0"]["ffn"]["norm"]["scale"]
    assert tuple(norm) == ()


def test_device_input_cache_hit_and_parity(engine):
    """cache_keys pins the region row in a slab slot after the first run; a
    repeat request resolves to the SAME slot (no re-upload) and decodes
    identically to an uncached run."""
    regions = make_regions(1, feat_dim=engine.cfg.model.v_feature_size, seed=3)
    cached = engine.prepare(1, "what is on the table", regions,
                            cache_keys=["imgA"])
    plain = engine.prepare(1, "what is on the table", regions)
    assert cached.cache_keys == ["imgA"] and plain.cache_keys is None

    _, r1 = engine.run(cached)
    slot = engine._input_cache["imgA"]
    assert slot != 0  # slot 0 is the permanent pad row, never a cache entry
    hits_before = engine.input_cache_stats["hits"]
    _, r2 = engine.run(cached)
    assert engine._input_cache["imgA"] == slot  # LRU hit, same slab slot
    assert engine.input_cache_stats["hits"] > hits_before
    _, r_plain = engine.run(plain)
    a1 = [a["confidence"] for a in r1.answers]
    assert a1 == [a["confidence"] for a in r2.answers]
    assert a1 == pytest.approx(
        [a["confidence"] for a in r_plain.answers], abs=1e-6)


def test_device_input_cache_lru_eviction(tiny_config):
    cfg = FrameworkConfig(
        model=tiny_config,
        engine=_cpu_engine_cfg(max_regions=11, device_input_cache_entries=1),
    )
    eng = InferenceEngine(cfg, seed=0)
    regions = make_regions(1, feat_dim=tiny_config.v_feature_size)
    for key in ("a", "b"):
        eng.run(eng.prepare(1, "q", regions, cache_keys=[key]))
    assert list(eng._input_cache) == ["b"]  # "a" evicted

    # entries=0 disables the cache entirely (no key ever recorded)
    cfg0 = FrameworkConfig(
        model=tiny_config,
        engine=_cpu_engine_cfg(max_regions=11, device_input_cache_entries=0),
    )
    eng0 = InferenceEngine(cfg0, seed=0)
    req = eng0.prepare(1, "q", regions, cache_keys=["a"])
    assert req.cache_keys is None


def test_run_many_uses_device_cache_and_matches_solo(engine):
    """The batched path rides the same row cache as solo serving, and its
    per-row decodes match run() row-for-row."""
    feat_dim = engine.cfg.model.v_feature_size
    r_a = make_regions(1, feat_dim=feat_dim, seed=11)
    r_b = make_regions(1, feat_dim=feat_dim, seed=12)
    reqs = [engine.prepare(1, "what is this", r_a, cache_keys=["many_a"]),
            engine.prepare(15, "is it red", r_b, cache_keys=["many_b"]),
            engine.prepare(1, "what is this", r_a, cache_keys=["many_a"])]
    results = engine.run_many(reqs)
    assert {"many_a", "many_b"} <= set(engine._input_cache)
    solo = [engine.run(r)[1] for r in reqs]
    for batched, s in zip(results, solo):
        assert ([a["confidence"] for a in batched.answers]
                == pytest.approx([a["confidence"] for a in s.answers],
                                 abs=1e-5))


def test_retrieval_pads_with_shared_device_row(engine):
    """Bucket padding resolves to slab slot 0 — the permanent device-resident
    pad row (no per-request pad upload, ever) — and padded requests still
    decode all real rows."""
    import jax

    feat_dim = engine.cfg.model.v_feature_size
    regions = make_regions(3, feat_dim=feat_dim, seed=13)
    req = engine.prepare(7, "a dog on a beach", regions,
                         cache_keys=["p0", "p1", "p2"])
    assert req.bucket == 4 and req.n_images == 3
    slab, slots = engine._pack_rows(engine._request_rows(req), req.bucket)
    assert slots.shape == (4,) and slots[3] == 0  # pad row = slab slot 0
    assert all(s != 0 for s in slots[:3])  # real rows never alias the pad
    assert all(isinstance(v, jax.Array) for v in slab.values())
    # Slot 0 carries the canonical pad content: zero features, mask[0]=1.
    assert float(jax.device_get(slab["features"])[0].sum()) == 0.0
    assert int(jax.device_get(slab["image_mask"])[0][0]) == 1
    _, res = engine.run(req)
    assert len(res.ranking) == 3


def test_rows_dispatch_leaf_count_is_constant(engine, monkeypatch):
    """O(1)-leaf regression: the rows program's per-dispatch argument tree
    (slab + pack) must have the SAME leaf count at bucket 1 and bucket 4 —
    3 slab tensors + 5 pack tensors, never 3×bucket image leaves. A leaf
    count that scales with bucket size is the round-5 per-dispatch
    marshalling cost creeping back in."""
    import jax

    counts = {}
    real = engine._call_forward

    def spy(bucket, collect_attention, *args, **kw):
        counts[bucket] = len(jax.tree_util.tree_leaves(args))
        return real(bucket, collect_attention, *args, **kw)

    monkeypatch.setattr(engine, "_call_forward", spy)
    feat_dim = engine.cfg.model.v_feature_size
    engine.run(engine.prepare(1, "what is this",
                              make_regions(1, feat_dim=feat_dim, seed=21)))
    engine.run(engine.prepare(7, "a dog on a beach",
                              make_regions(3, feat_dim=feat_dim, seed=22)))
    assert counts[1] == counts[4] == 8, counts


@pytest.mark.parametrize("bucket", EngineConfig().all_row_buckets())
def test_rows_program_reads_the_rows_a_gather_reads(bucket):
    """The rows program's row-by-row read of the slab
    (``runtime._gather_rows``) returns exactly what ``slab[k][rows]``
    returns, for all three leaves in their served dtypes: repeated slots,
    the pad slot 0 and the slab's last scratch slot among the rows."""
    import jax
    import jax.numpy as jnp

    from vilbert_multitask_tpu.engine.runtime import _gather_rows

    n_rows, regions = 1 + 40 + 32, 11
    rng = np.random.RandomState(bucket)
    slab = jax.device_put(dict(
        features=rng.randn(n_rows, regions, 16).astype(jnp.bfloat16),
        spatials=rng.rand(n_rows, regions, 5).astype(np.float32),
        image_mask=rng.randint(0, 2, (n_rows, regions)).astype(np.int32)))
    rows = rng.randint(0, n_rows, size=bucket).astype(np.int32)
    rows[0] = n_rows - 1
    rows[-1] = 0
    if bucket > 2:
        rows[1] = rows[2]
    rows = jax.device_put(rows)
    served, gather = jax.jit(_gather_rows), jax.jit(lambda x, r: x[r])
    for name, leaf in slab.items():
        got, want = served(leaf, rows), gather(leaf, rows)
        assert got.dtype == want.dtype and got.shape == want.shape, name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)


def test_bf16_param_storage_decode_parity(tiny_config):
    """EngineConfig.param_dtype="bfloat16" halves served-weight HBM; decodes
    must stay within bf16 rounding of the f32 engine for EVERY decode
    family's head — the parity gate on the serving storage mode."""
    import jax
    import jax.numpy as jnp

    eng32 = InferenceEngine(FrameworkConfig(
        model=tiny_config, engine=_cpu_engine_cfg(max_regions=11)), seed=0)
    host = jax.device_get(eng32.params)  # f32 masters, checkpoint-shaped
    engbf = InferenceEngine(FrameworkConfig(
        model=tiny_config,
        engine=dataclasses.replace(_cpu_engine_cfg(max_regions=11),
                                   param_dtype="bfloat16"),
    ), params=host)
    for leaf in jax.tree_util.tree_leaves(engbf.params):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            assert leaf.dtype == jnp.bfloat16, leaf.dtype

    feat_dim = tiny_config.v_feature_size
    for task_id, spec in sorted(TASK_REGISTRY.items()):
        regions = make_regions(spec.min_images, feat_dim=feat_dim,
                               seed=40 + task_id)
        question = spec.placeholder or "what is in the picture"
        out32, res32 = eng32.run(eng32.prepare(task_id, question, regions))
        outbf, resbf = engbf.run(engbf.prepare(task_id, question, regions))
        head32 = np.asarray(
            jax.device_get(getattr(out32, spec.head)), np.float32)
        headbf = np.asarray(
            jax.device_get(getattr(outbf, spec.head)), np.float32)
        np.testing.assert_allclose(
            headbf, head32, rtol=0.1, atol=0.05,
            err_msg=f"task {task_id} ({spec.name}) head {spec.head}")
        assert resbf.task_id == res32.task_id == task_id
        assert type(resbf) is type(res32)


def test_int8_param_storage_decode_parity(tiny_config):
    """EngineConfig.param_dtype="int8" quarters served-weight HBM; with
    in-program dequant fused before each matmul, every decode family's
    head must stay within per-channel quantization noise of the f32
    engine. Tolerances are bumped over the bf16 gate — int8 carries ~3 bits
    less mantissa than bf16 through a 12-layer trunk."""
    import jax
    import jax.numpy as jnp

    from vilbert_multitask_tpu import quant
    from vilbert_multitask_tpu.engine.flops import param_tree_bytes

    eng32 = InferenceEngine(FrameworkConfig(
        model=tiny_config, engine=_cpu_engine_cfg(max_regions=11)), seed=0)
    host = jax.device_get(eng32.params)  # f32 masters, checkpoint-shaped
    engq = InferenceEngine(FrameworkConfig(
        model=tiny_config,
        engine=dataclasses.replace(_cpu_engine_cfg(max_regions=11),
                                   param_dtype="int8"),
    ), params=host)
    assert quant.tree_is_quantized(engq.params)
    for leaf in jax.tree_util.tree_leaves(engq.params):
        assert leaf.dtype in (jnp.int8, jnp.float32), leaf.dtype
    # The roofline claim: int8 storage reads ~0.3× the f32 bytes (scales
    # and untouched vector leaves keep it off the exact quarter).
    ratio = param_tree_bytes(engq.params) / param_tree_bytes(eng32.params)
    assert ratio < 0.35, ratio

    feat_dim = tiny_config.v_feature_size
    for task_id, spec in sorted(TASK_REGISTRY.items()):
        regions = make_regions(spec.min_images, feat_dim=feat_dim,
                               seed=40 + task_id)
        question = spec.placeholder or "what is in the picture"
        out32, res32 = eng32.run(eng32.prepare(task_id, question, regions))
        outq, resq = engq.run(engq.prepare(task_id, question, regions))
        head32 = np.asarray(
            jax.device_get(getattr(out32, spec.head)), np.float32)
        headq = np.asarray(
            jax.device_get(getattr(outq, spec.head)), np.float32)
        np.testing.assert_allclose(
            headq, head32, rtol=0.15, atol=0.15,
            err_msg=f"task {task_id} ({spec.name}) head {spec.head}")
        assert resq.task_id == res32.task_id == task_id
        assert type(resq) is type(res32)


def test_fused_heads_match_per_head_decode_on_mixed_chunk(tiny_config):
    """The fused decode-head program (one batched slab matmul + in-program
    gather by task id) must decode a mixed-task run_many chunk to the same
    answers as the per-head path (fused_task_heads=False) on the SAME
    weights — answer order exact, confidences to f32 noise."""
    import jax

    fused = InferenceEngine(FrameworkConfig(
        model=tiny_config, engine=_cpu_engine_cfg(max_regions=11)), seed=3)
    assert fused.head_slabs is not None
    host = jax.device_get(fused.params)
    perhead = InferenceEngine(FrameworkConfig(
        model=tiny_config,
        engine=dataclasses.replace(_cpu_engine_cfg(max_regions=11),
                                   fused_task_heads=False),
    ), params=host)
    assert perhead.head_slabs is None

    regions = make_regions(4, feat_dim=tiny_config.v_feature_size, seed=5)
    backlog = [
        (1, "what is the man holding", 1),   # VQA labels
        (12, "both images contain wolves", 2),  # NLVR2 pair
        (7, "a red car parked outside", 4),  # retrieval ranking
        (15, "is the bowl right of the mug", 1),  # GQA labels
        (13, "a person entailed by a premise", 1),  # SNLI-VE trinary
        (4, "which hand holds the phone", 1),  # Visual7W grounding
    ]
    res_a = fused.run_many([fused.prepare(t, q, regions[:n])
                            for t, q, n in backlog])
    res_b = perhead.run_many([perhead.prepare(t, q, regions[:n])
                              for t, q, n in backlog])
    assert [r.kind for r in res_a] == [r.kind for r in res_b]
    for a, b in zip(res_a, res_b):
        if a.answers is not None:
            assert [x["answer"] for x in a.answers] == \
                [x["answer"] for x in b.answers]
            np.testing.assert_allclose(
                [x["confidence"] for x in a.answers],
                [x["confidence"] for x in b.answers], rtol=1e-4, atol=1e-6)
        if a.ranking is not None:
            assert [x["image"] for x in a.ranking] == \
                [x["image"] for x in b.ranking]
        if a.boxes is not None:
            np.testing.assert_allclose(
                [x["score"] for x in a.boxes],
                [x["score"] for x in b.boxes], rtol=1e-4, atol=1e-6)


def test_swap_requantizes_f32_checkpoint(tiny_config):
    """POST /admin/swap regression: load_params on an int8 engine must
    RE-QUANTIZE an incoming f32 host tree (restore_params ships f32 when
    the checkpoint predates the storage mode) — and republish the fused
    head slabs against the new tree atomically. A swap that silently
    serves the fat tree defeats the storage mode without failing."""
    import jax
    import jax.numpy as jnp

    from vilbert_multitask_tpu import quant

    eng32 = InferenceEngine(FrameworkConfig(
        model=tiny_config, engine=_cpu_engine_cfg(max_regions=11)), seed=0)
    host = jax.device_get(eng32.params)
    engq = InferenceEngine(FrameworkConfig(
        model=tiny_config,
        engine=dataclasses.replace(_cpu_engine_cfg(max_regions=11),
                                   param_dtype="int8"),
    ), params=host)
    slabs_before = engq.head_slabs

    bumped = jax.tree_util.tree_map(lambda x: x * 1.01, host)
    engq.load_params(bumped)  # the rolling_swap load_fn path
    assert quant.tree_is_quantized(engq.params)
    for leaf in jax.tree_util.tree_leaves(engq.params):
        assert leaf.dtype in (jnp.int8, jnp.float32), leaf.dtype
    # Slabs republished against the swapped tree, and quantized kernels
    # stay quantized through the swap.
    assert engq.head_slabs is not slabs_before
    assert quant.is_quantized_leaf(engq.head_slabs["label_d1_kernel"])
    # An already-quantized tree round-trips through load_params untouched
    # (the idempotent double-cast on the restore path).
    requant = jax.device_get(engq.params)
    engq.load_params(requant)
    assert quant.tree_is_quantized(engq.params)
    regions = make_regions(1, feat_dim=tiny_config.v_feature_size, seed=9)
    _, res = engq.run(engq.prepare(1, "what is this", regions))
    assert res.task_id == 1


def test_transfer_dtype_follows_compute_dtype(tiny_config):
    """bf16 engines ship features as bf16 (half the host→device payload;
    bit-identical because the model casts at its first dense layer); f32
    engines — every golden-fixture test — keep f32 features untouched."""
    import jax.numpy as jnp

    f32 = InferenceEngine(FrameworkConfig(
        model=tiny_config, engine=_cpu_engine_cfg(max_regions=11)), seed=0)
    regions = make_regions(1, feat_dim=tiny_config.v_feature_size)
    assert f32.prepare(1, "q", regions).features.dtype == np.float32

    bf = InferenceEngine(FrameworkConfig(
        model=tiny_config,
        engine=dataclasses.replace(
            _cpu_engine_cfg(max_regions=11), compute_dtype="bfloat16"),
    ), seed=0)
    req = bf.prepare(1, "q", regions)
    assert req.features.dtype == jnp.bfloat16
    # warmup and live requests must hit the SAME compiled program: the
    # dummy batch ships the transfer dtype too (a dtype mismatch means a
    # silent recompile on the first live request of every bucket).
    for eng in (f32, bf):
        assert (eng._dummy_batch(1)["features"].dtype
                == eng.prepare(1, "q", regions).features.dtype)
    _, result = bf.run(req)  # bf16 inputs flow through the forward + decode
    assert result.task_id == 1


def test_input_cache_stats_counts(tiny_config):
    eng = InferenceEngine(FrameworkConfig(
        model=tiny_config, engine=_cpu_engine_cfg(max_regions=11)), seed=0)
    regions = make_regions(1, feat_dim=tiny_config.v_feature_size)
    assert eng.input_cache_stats == {"entries": 0, "hits": 0, "misses": 0}
    req = eng.prepare(1, "q", regions, cache_keys=["statA"])
    eng.run(req)
    eng.run(req)
    s = eng.input_cache_stats
    assert s["entries"] == 1 and s["misses"] == 1 and s["hits"] >= 1


# ---------------------------------------- residency is asked before the read
# (ISSUE 35) prepare_from_store takes a file's identity, asks the device
# cache for it and reads only what the device does not hold; the pack stays
# the authority.
from vilbert_multitask_tpu import obs  # noqa: E402
from vilbert_multitask_tpu.features.store import (  # noqa: E402
    FeatureStore,
    save_reference_npy,
)


class CountingStore(FeatureStore):
    """A FeatureStore that notes every ``fetch``; file loads are the
    store's own ``vmt_feature_store_*`` counters."""

    def __init__(self, root):
        super().__init__(root)
        self.fetched = []

    def fetch(self, image_path):
        self.fetched.append(image_path)
        return super().fetch(image_path)


class PlainStore:
    """A store without ``fetch`` (and so without identities): the engine
    reads every row of every request through ``get_batch``, caches nothing.
    Wrapped around a real store, it is the read path the others are held
    to, and the minimal test double ``prepare_from_store`` always took."""

    def __init__(self, root):
        self._store = FeatureStore(root)

    def get_batch(self, image_paths):
        return self._store.get_batch(image_paths)


class NoIdentityStore(PlainStore):
    """``fetch`` but no ``identity``: keys for the device cache as ever, and
    nothing to ask the device with before the read."""

    def fetch(self, image_path):
        return self._store.fetch(image_path)


def _write_image(root, name, seed, *, boxes=6, dim=32, size=None):
    """One reference-schema file; sizes and box counts differ by seed so a
    frame taken from the wrong row shows in a decoded box."""
    rng = np.random.RandomState(seed)
    xy = rng.uniform(0, 200, size=(boxes, 4)).astype(np.float32)
    xy[:, 2:] = xy[:, :2] + 10 + xy[:, 2:] * 0.3
    width, height = size or (320 + 16 * (seed % 7), 300 + 8 * (seed % 5))
    region = RegionFeatures(
        features=rng.randn(boxes, dim).astype(np.float32), boxes=xy,
        image_width=width, image_height=height)
    save_reference_npy(f"{root}/{name}.npy", region, name)


def _store_reads():
    return (obs.FEATURE_STORE_HITS.value() + obs.FEATURE_STORE_MISSES.value())


def _intake_rows():
    return {k: getattr(obs, f"INTAKE_ROWS_{k.upper()}").value()
            for k in ("resident", "read", "late")}


def _rose(before):
    return {k: v - before[k] for k, v in _intake_rows().items()}


@pytest.fixture(scope="module")
def gallery(tiny_config, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("gallery"))
    for i in range(14):
        _write_image(root, f"g{i:02d}", seed=100 + i, boxes=4 + i % 5,
                     dim=tiny_config.v_feature_size)
    return root


def _resident_cfg(tiny_config, **kw):
    return FrameworkConfig(
        model=tiny_config,
        engine=_cpu_engine_cfg(max_regions=11, image_buckets=(1, 2, 10),
                               throughput_buckets=None, **kw))


def _engine_pair(tiny_config, root, **kw):
    """(engine under test, its counting store, the read-path engine): one
    parameter tree, so equal inputs give equal bits."""
    store = CountingStore(root)
    eng = InferenceEngine(_resident_cfg(tiny_config, **kw), seed=0,
                          feature_store=store)
    ref = InferenceEngine(_resident_cfg(tiny_config), params=eng.params,
                          feature_store=PlainStore(root))
    return eng, store, ref


@pytest.fixture(scope="module")
def served_pair(tiny_config, gallery):
    return _engine_pair(tiny_config, gallery)


def _answer(engine, task_id, question, paths, many=False):
    req = engine.prepare_from_store(task_id, question, paths)
    if many:
        return req, engine.run_many([req])[0]
    return req, engine.run(req)[1]


# family -> (task, images of the request, images made resident beforehand)
RESIDENT_CASES = {
    "labels": (1, ["g00.jpg"], []),
    "binary_one_resident_one_carried": (12, ["g01.jpg", "g02.jpg"],
                                        ["g01.jpg"]),
    "trinary": (13, ["g03.jpg"], []),
    "ranking_over_10": (7, [f"g{i:02d}.jpg" for i in range(4, 14)], []),
    "grounding": (4, ["g03.jpg"], []),
}


@pytest.mark.parametrize("many", [False, True], ids=["run", "run_many"])
@pytest.mark.parametrize("family", sorted(RESIDENT_CASES))
def test_resident_rows_are_not_read_and_decode_bit_for_bit(
        served_pair, family, many):
    """With every row on the device the intake makes no ``fetch`` and no
    store read, carries no host tensor, and the decoded result is the read
    path's, bit for bit: the request's first serving (all read, or the
    NLVR2 pair's one row) and its second (all resident) both equal an
    engine that reads every row every time."""
    eng, store, ref = served_pair
    task_id, paths, first = RESIDENT_CASES[family]
    question = TASK_QUESTIONS[task_id]
    _, want = _answer(ref, task_id, question, paths, many)
    for p in first:  # resident through a request of its own
        eng.run(eng.prepare_from_store(1, "what is this", [p]))
    held = eng.resident_frames([store.identity(p) for p in paths])
    n_held = sum(f is not None for f in held)

    store.fetched.clear()
    rows = _intake_rows()
    req, got = _answer(eng, task_id, question, paths, many)
    assert got == want
    assert store.fetched == [p for p, f in zip(paths, held) if f is None]
    assert _rose(rows) == {"resident": n_held, "read": len(paths) - n_held,
                           "late": 0}
    if 0 < n_held < len(paths):  # the pair: one frame, one carried row
        assert [f is not None for f in req.frames] == [True, False]
        assert req.features.shape[0] == 1
        assert [h is None for h in req.host_rows()] == [True, False]

    store.fetched.clear()
    rows, reads = _intake_rows(), _store_reads()
    req, got = _answer(eng, task_id, question, paths, many)
    assert got == want
    assert store.fetched == [] and _store_reads() == reads
    assert _rose(rows) == {"resident": len(paths), "read": 0, "late": 0}
    assert req.features is None and req.spatials is None
    assert req.host_rows() == [None] * len(paths)
    assert [(m.width, m.height) for m in req.images] == [
        (r.image_width, r.image_height)
        for r in FeatureStore(store.root).get_batch(paths)]


@pytest.mark.parametrize("what", ["mtime", "size"])
def test_a_replaced_feature_file_is_read_again(tiny_config, tmp_path, what):
    """The identity is taken anew for every request (path, mtime, size): a
    file replaced between two requests is a miss, is read, and its new
    content is what the second request is answered from."""
    import os

    root = str(tmp_path)
    dim = tiny_config.v_feature_size
    _write_image(root, "pic", seed=1, boxes=5, dim=dim)
    eng, store, ref = _engine_pair(tiny_config, root)
    _, first = _answer(eng, 4, TASK_QUESTIONS[4], ["pic.jpg"])
    assert eng.prepare_from_store(4, "q", ["pic.jpg"]).frames is not None
    before = os.stat(f"{root}/pic.npy")
    if what == "mtime":  # other content, the same size, a later mtime
        _write_image(root, "pic", seed=2, boxes=5, dim=dim)
        os.utime(f"{root}/pic.npy", ns=(before.st_atime_ns,
                                        before.st_mtime_ns + 1_000_000))
        assert os.stat(f"{root}/pic.npy").st_size == before.st_size
    else:  # another size under the very same mtime
        _write_image(root, "pic", seed=2, boxes=7, dim=dim)
        os.utime(f"{root}/pic.npy", ns=(before.st_atime_ns,
                                        before.st_mtime_ns))
        assert os.stat(f"{root}/pic.npy").st_size != before.st_size
    store.fetched.clear()
    rows = _intake_rows()
    req, second = _answer(eng, 4, TASK_QUESTIONS[4], ["pic.jpg"])
    assert store.fetched == ["pic.jpg"] and req.frames is None
    assert _rose(rows) == {"resident": 0, "read": 1, "late": 0}
    _, want = _answer(ref, 4, TASK_QUESTIONS[4], ["pic.jpg"])
    assert second == want and second != first


@pytest.mark.parametrize("case", ["run_labels", "run_many_grounding",
                                  "evicted_and_replaced"])
def test_a_row_evicted_between_intake_and_pack_is_read_late(
        tiny_config, tmp_path, case):
    """The pack is the authority: a row the intake called resident and the
    LRU has dropped since is read and encoded at the pack, the request is
    answered as the read path answers it (from the file as it is THEN), and
    the row is counted ``late``."""
    root = str(tmp_path)
    dim = tiny_config.v_feature_size
    for i, name in enumerate(("pic", "other_a", "other_b")):
        _write_image(root, name, seed=10 + i, boxes=5 + i, dim=dim)
    eng, store, ref = _engine_pair(tiny_config, root,
                                   device_input_cache_entries=2)
    task_id = 1 if case == "run_labels" else 4
    question = TASK_QUESTIONS[task_id]
    eng.predict(task_id, question, ["pic.jpg"])
    req = eng.prepare_from_store(task_id, question, ["pic.jpg"])
    assert req.frames is not None and req.features is None
    for other in ("other_a.jpg", "other_b.jpg"):  # two slots: pic goes
        eng.predict(1, "what is this", [other])
    assert eng.resident_frames(req.cache_keys) == [None]
    if case == "evicted_and_replaced":
        _write_image(root, "pic", seed=99, boxes=8, dim=dim)
    store.fetched.clear()
    rows = _intake_rows()
    if case == "run_many_grounding":
        got = eng.run_many([req])[0]
    else:
        got = eng.run(req)[1]
    assert store.fetched == ["pic.jpg"]
    assert _rose(rows) == {"resident": 0, "read": 0, "late": 1}
    _, want = _answer(ref, task_id, question, ["pic.jpg"])
    assert got == want
    # The request learned what it was packed from; the row is resident again.
    assert req.cache_keys == [store.identity("pic.jpg")]
    assert eng.resident_frames(req.cache_keys)[0] is not None
    rows = _intake_rows()
    assert eng.run(req)[1] == want and _rose(rows)["late"] == 0


def test_a_cache_smaller_than_a_pack_never_hands_a_slot_out_twice(
        tiny_config, gallery):
    """Two cache slots, a pack of ten: rows the cache holds are pinned
    before anything is inserted and the overflow rides scratch slots, so
    every row of the pack has a slot of its own and the answer is the read
    path's."""
    eng, _, ref = _engine_pair(tiny_config, gallery,
                               device_input_cache_entries=2)
    paths = [f"g{i:02d}.jpg" for i in range(10)]
    eng.predict(1, "what is this", [paths[9]])  # resident, last in the pack
    req = eng.prepare_from_store(7, TASK_QUESTIONS[7], paths)
    assert [f is not None for f in req.frames] == [False] * 9 + [True]
    _, slots = eng._pack_rows(eng._request_rows(req), req.bucket)
    assert len(set(slots.tolist())) == 10
    _, want = _answer(ref, 7, TASK_QUESTIONS[7], paths)
    assert eng.run(req)[1] == want


@pytest.mark.parametrize("case", ["mesh", "no_fetch", "no_identity",
                                  "cache_off"])
def test_who_cannot_ask_the_device_reads_every_row_as_before(
        tiny_config, gallery, case):
    """Mesh serving, a store without ``fetch`` (or without ``identity``)
    and an engine without a device cache run the code they ran: every row
    read and encoded on every request, the bucket-padded arrays carried."""
    kw, mesh, store = {}, None, CountingStore(gallery)
    if case == "mesh":
        mesh = build_mesh(MeshConfig(dp=4, tp=2))
    elif case == "no_fetch":
        store = PlainStore(gallery)
    elif case == "no_identity":
        store = NoIdentityStore(gallery)
    else:
        kw = dict(device_input_cache_entries=0)
    cfg = dataclasses.replace(_resident_cfg(tiny_config, **kw),
                              mesh=MeshConfig(dp=4, tp=2))
    eng = InferenceEngine(cfg, seed=0, feature_store=store, mesh=mesh)
    paths = ["g00.jpg", "g01.jpg", "g02.jpg"]
    for serving in range(2):
        rows = _intake_rows()
        req = eng.prepare_from_store(7, TASK_QUESTIONS[7], paths)
        assert _rose(rows) == {"resident": 0, "read": 3, "late": 0}
        assert req.frames is None and req.features.shape[0] == req.bucket
        assert len(req.host_rows()) == 3 and None not in req.host_rows()
        assert (req.cache_keys is None) == (case in ("no_fetch",
                                                     "cache_off"))
        if case in ("no_identity", "cache_off"):
            assert len(eng.run(req)[1].ranking) == 3


def test_intakes_and_packs_race_an_evicting_cache_and_every_answer_stands(
        tiny_config, gallery):
    """Stress: more threads than cores prepare and run against a device
    cache of three slots over fourteen images, so rows the intake was
    promised are evicted before their pack all the time. Every answer is
    the read path's, the frames stay the cache's key for key, and the three
    ways a row can come in add up to the rows asked for."""
    import os
    import sys
    import threading

    eng, _, ref = _engine_pair(tiny_config, gallery,
                               device_input_cache_entries=3)
    images = [f"g{i:02d}.jpg" for i in range(14)]
    want = {p: _answer(ref, 4, TASK_QUESTIONS[4], [p])[1] for p in images}
    eng.predict(4, TASK_QUESTIONS[4], images[:1])  # compiled before the race
    threads, rounds = 2 * (os.cpu_count() or 4), 12
    rows = _intake_rows()
    wrong, errors = [], []

    def worker(k):
        try:
            for j in range(rounds):
                path = images[(5 * k + 3 * j) % len(images)]
                got = _answer(eng, 4, TASK_QUESTIONS[4], [path])[1]
                if got != want[path]:
                    wrong.append(path)
        except Exception as e:  # noqa: BLE001 - reported by the assert below
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=worker, args=(k,), daemon=True)
                for k in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    assert errors == [] and wrong == []
    rose = _rose(rows)
    assert rose["resident"] + rose["read"] == threads * rounds
    assert rose["late"] <= rose["resident"]
    with eng._input_cache_lock:
        assert set(eng._input_frames) == set(eng._input_cache)
        assert len(eng._input_cache) <= 3
