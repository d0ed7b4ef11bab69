"""Cross-task micro-batching: one forward serving a mixed-task batch."""

import numpy as np
import pytest

from vilbert_multitask_tpu.serve import make_job_message


def _prep(engine, task_id, question, keys):
    regions = engine.feature_store.get_batch(keys)
    return engine.prepare(task_id, question, regions, keys)


def test_run_many_matches_individual_runs(engine):
    reqs = [
        _prep(engine, 1, "what is this", ["img_a.jpg"]),
        _prep(engine, 15, "is it red", ["img_b.jpg"]),
        _prep(engine, 13, "a dog plays", ["img_a.jpg"]),
        _prep(engine, 11, "the left box", ["img_b.jpg"]),
    ]
    batched = engine.run_many(reqs)
    assert [r.kind for r in batched] == ["labels", "labels", "trinary",
                                        "grounding"]
    for req, got in zip(reqs, batched):
        _, solo = engine.run(req)
        if got.answers is not None:
            assert [a["answer"] for a in got.answers] == \
                [a["answer"] for a in solo.answers]
            np.testing.assert_allclose(
                [a["confidence"] for a in got.answers],
                [a["confidence"] for a in solo.answers], atol=1e-4)
        if got.boxes is not None:
            assert [b["region_index"] for b in got.boxes] == \
                [b["region_index"] for b in solo.boxes]


def test_run_many_batches_multi_image(engine):
    """NLVR2 pairs and retrieval candidate sets ride the batched path
    (round-3 ceiling removed): results must match solo run() exactly, in
    input order, with pair rows staying even-aligned inside chunks."""
    reqs = [
        _prep(engine, 12, "both show dogs", ["img_a.jpg", "img_b.jpg"]),
        _prep(engine, 1, "what is this", ["img_a.jpg"]),
        _prep(engine, 12, "both show cats", ["img_b.jpg", "img_a.jpg"]),
        _prep(engine, 7, "a dog in snow",
              ["img_a.jpg", "img_b.jpg", "img_a.jpg", "img_b.jpg"]),
        _prep(engine, 12, "two wolves", ["img_a.jpg", "img_b.jpg"]),
    ]
    batched = engine.run_many(reqs)
    assert [r.kind for r in batched] == ["binary", "labels", "binary",
                                        "ranking", "binary"]
    for req, got in zip(reqs, batched):
        _, solo = engine.run(req)
        if got.answers is not None:
            assert [a["answer"] for a in got.answers] == \
                [a["answer"] for a in solo.answers], req.spec.task_id
            np.testing.assert_allclose(
                [a["confidence"] for a in got.answers],
                [a["confidence"] for a in solo.answers], atol=1e-4)
        if got.ranking is not None:
            assert [r["image"] for r in got.ranking] == \
                [r["image"] for r in solo.ranking]


def test_run_many_rejects_oversized_request(engine):
    """A request wider than the chunk cannot pack — clear error."""
    reqs = [_prep(engine, 7, "query",
                  ["img_a.jpg", "img_b.jpg"] * 2)]
    with pytest.raises(ValueError, match="exceeds"):
        engine.run_many(reqs, chunk_rows=2)


def test_run_many_empty(engine):
    assert engine.run_many([]) == []


def test_run_many_chunks_beyond_max_bucket(engine):
    """Batches above the largest compiled bucket split, not crash."""
    max_bucket = max(engine.cfg.engine.image_buckets)
    n = max_bucket + 3
    reqs = [
        _prep(engine, 1, f"question {i}", [("img_a.jpg", "img_b.jpg")[i % 2]])
        for i in range(n)
    ]
    results = engine.run_many(reqs)
    assert len(results) == n
    assert all(r.kind == "labels" for r in results)


def test_throughput_bucket_chunking(tiny_framework_cfg, features_dir):
    """run_many chunks at the throughput bucket (not the max image bucket)
    when one is configured, produces the same decodes, and honors the
    chunk_rows override; row_bucket_for folds the extra bucket in."""
    import dataclasses

    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.features.store import FeatureStore

    cfg = dataclasses.replace(
        tiny_framework_cfg,
        engine=dataclasses.replace(
            tiny_framework_cfg.engine,
            image_buckets=(1, 2, 4), throughput_buckets=(8,)),
    )
    assert cfg.engine.row_bucket_for(5) == 8
    assert cfg.engine.bucket_for(4) == 4  # image-axis semantics unchanged
    with pytest.raises(ValueError, match="row bucket"):
        cfg.engine.row_bucket_for(9)

    eng = InferenceEngine(cfg, feature_store=FeatureStore(features_dir))
    reqs = [
        _prep(eng, 1, f"question {i}", [("img_a.jpg", "img_b.jpg")[i % 2]])
        for i in range(6)
    ]
    batched = eng.run_many(reqs)  # one 8-row chunk (6 rows + 2 pad)
    assert len(batched) == 6
    solo_answers = []
    for r in reqs:
        _, s = eng.run(r)
        solo_answers.append([a["answer"] for a in s.answers])
    assert [[a["answer"] for a in b.answers] for b in batched] == solo_answers
    # Override back to the image buckets: two chunks of 4 — identical output.
    chunked = eng.run_many(reqs, chunk_rows=4)
    assert [[a["answer"] for a in b.answers]
            for b in chunked] == solo_answers
    with pytest.raises(ValueError, match="row bucket"):
        eng.run_many(reqs, chunk_rows=16)
    for bad in (0, -4):  # must error, never silently drop requests
        with pytest.raises(ValueError, match=">=1"):
            eng.run_many(reqs, chunk_rows=bad)


def test_chunk_plan_is_run_manys_packing(engine):
    """Pin the plan's semantics, so a packing change breaks a test.
    Tiny engine: image buckets (1,2,4,8), no throughput buckets → max 8."""
    counts = [1, 2, 1, 4, 2, 1, 1]  # mixed single/pair/quad backlog
    plan = engine.chunk_plan(counts)
    # Mixed-count packing (round 5): evens first (2+4+2 fills a chunk),
    # then the singles share one — 2 dispatches where per-count grouping
    # paid 3.
    assert plan == [[1, 3, 4], [0, 2, 5, 6]]
    assert sorted(i for c in plan for i in c) == list(range(len(counts)))
    for chunk in plan:
        assert sum(counts[i] for i in chunk) <= 8
        # even-count requests lead the chunk AND sit at even row offsets
        # (the binary head pairs rows 2k/2k+1; decode reads offset//2)
        offset, seen_odd = 0, False
        for i in chunk:
            if counts[i] % 2 == 0:
                assert not seen_odd and offset % 2 == 0, (chunk, i)
            else:
                seen_odd = True
            offset += counts[i]
    # chunk_rows override changes the plan the same way run_many chunks
    assert engine.chunk_plan([1] * 6, chunk_rows=4) == [[0, 1, 2, 3], [4, 5]]
    with pytest.raises(ValueError, match="exceeds"):
        engine.chunk_plan([9])


def test_mixed_count_chunk_decodes_match_solo(engine):
    """Functional proof of the round-5 mixed packer: NLVR2 pairs, a
    retrieval set, and singles packed into SHARED chunks must decode
    identically to one-request-at-a-time runs — pair alignment, ranking
    row spans, and label rows all survive mixed packing."""
    reqs = [
        _prep(engine, 1, "what is it", ["img_a.jpg"]),
        _prep(engine, 12, "both contain dogs", ["img_a.jpg", "img_b.jpg"]),
        _prep(engine, 13, "dogs play", ["img_b.jpg"]),
        _prep(engine, 7, "a dog catching",
              ["img_a.jpg", "img_b.jpg", "img_a.jpg", "img_b.jpg"]),
        _prep(engine, 12, "both contain cats", ["img_b.jpg", "img_a.jpg"]),
        _prep(engine, 15, "is it red", ["img_a.jpg"]),
    ]
    # 1+2+1+4+2+1 = 11 rows over max bucket 8 → two mixed chunks
    plan = engine.chunk_plan([r.n_images for r in reqs])
    assert len(plan) == 2 and any(
        len({reqs[i].n_images for i in c}) > 1 for c in plan)
    batched = engine.run_many(reqs)
    for req, got in zip(reqs, batched):
        _, solo = engine.run(req)
        assert got.kind == solo.kind
        if got.answers is not None:
            assert [a["answer"] for a in got.answers] == \
                [a["answer"] for a in solo.answers], req.spec.task_id
        if got.ranking is not None:
            assert [r["image"] for r in got.ranking] == \
                [r["image"] for r in solo.ranking]


def test_prepare_clips_oversized_feature_files(engine):
    """Feature files with more boxes than the engine's region budget clip to
    the top-N (files are confidence-ordered) instead of erroring."""
    from vilbert_multitask_tpu.features.pipeline import RegionFeatures

    max_regions = engine.cfg.engine.max_regions
    n = max_regions + 20
    rng = np.random.default_rng(5)
    region = RegionFeatures(
        features=rng.normal(
            size=(n, engine.cfg.model.v_feature_size)).astype(np.float32),
        boxes=np.tile(np.array([[1, 1, 50, 50]], np.float32), (n, 1)),
        image_width=100, image_height=100)
    req = engine.prepare(1, "what", [region])
    assert req.features.shape[1] == max_regions
    assert int(req.image_mask[0].sum()) == max_regions  # global + N-1 boxes
    _, result = engine.run(req)
    assert result.kind == "labels"


def test_worker_step_batch_mixed_tasks(stack):
    s, hub, q, store, worker = stack
    before = len(store.recent(100))
    q.publish(make_job_message(["img_a.jpg"], "what", 1, "m1"))
    q.publish(make_job_message(["img_b.jpg"], "where", 15, "m2"))
    q.publish(make_job_message(["img_a.jpg", "img_b.jpg"], "both", 12, "m3"))
    q.publish(make_job_message(["img_b.jpg"], "entails", 13, "m4"))
    assert worker.step_batch(max_jobs=8) == 4
    assert q.counts() == {}
    rows = store.recent(100)
    assert len(rows) == before + 4
    by_task = {r["task_id"]: r for r in rows[:4]}
    assert by_task[12]["answer_text"]["kind"] == "binary"
    assert by_task[1]["answer_text"]["kind"] == "labels"


def test_worker_batches_multi_image_jobs(stack, monkeypatch):
    """NLVR2/retrieval jobs complete through run_many, never the solo
    path: with engine.run() poisoned, a mixed drain must still finish
    every job (round-3's known ceiling — multi-image jobs paid one
    forward each — is gone)."""
    s, hub, q, store, worker = stack

    def _boom(*a, **k):
        raise AssertionError("solo run() must not be used by step_batch")

    monkeypatch.setattr(worker.engine, "run", _boom)
    q.publish(make_job_message(["img_a.jpg", "img_b.jpg"], "both", 12, "b1"))
    q.publish(make_job_message(["img_a.jpg"], "what", 1, "b2"))
    q.publish(make_job_message(
        ["img_a.jpg", "img_b.jpg", "img_a.jpg", "img_b.jpg"],
        "a dog", 7, "b3"))
    assert worker.step_batch() == 3
    assert q.counts() == {}
    rows = store.recent(3)
    kinds = {r["task_id"]: r["answer_text"]["kind"] for r in rows}
    assert kinds == {12: "binary", 1: "labels", 7: "ranking"}


def test_worker_step_batch_poison_isolated(stack):
    """One bad job in a batch must not poison its batchmates."""
    s, hub, q, store, worker = stack
    q.publish(make_job_message(["img_a.jpg"], "ok", 1, "p1"))
    q.publish(make_job_message(["no_such_key.jpg"], "bad", 1, "p2"))
    q.publish(make_job_message(["img_b.jpg"], "ok2", 15, "p3"))
    assert worker.step_batch(max_jobs=8) == 2
    counts = q.counts()
    assert counts.get("pending") == 1  # poison requeued, good ones gone
