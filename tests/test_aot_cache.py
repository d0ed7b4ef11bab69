"""AOT executable cache tests (engine/aotcache.py): disk round trip — a
second engine boots entirely from deserialized executables with zero
compiles — plus fingerprint hygiene (a stale/corrupt entry must MISS and
recompile, never poison the boot)."""

import dataclasses
import glob
import os

import numpy as np
import pytest

from vilbert_multitask_tpu.config import EngineConfig, FrameworkConfig
from vilbert_multitask_tpu.engine import aotcache, runtime
from vilbert_multitask_tpu.engine.runtime import InferenceEngine
from vilbert_multitask_tpu.features.pipeline import RegionFeatures


def _regions(n=1, num_boxes=4, feat_dim=32, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        boxes = rng.uniform(0, 100, size=(num_boxes, 4)).astype(np.float32)
        boxes[:, 2:] = boxes[:, :2] + 10
        out.append(RegionFeatures(
            features=rng.randn(num_boxes, feat_dim).astype(np.float32),
            boxes=boxes, image_width=320, image_height=240))
    return out


def _cfg(tiny_config, aot_dir, **kw):
    """One-bucket engine: warmup is exactly one compiled program, so the
    hit/compile accounting below has no slack to hide in."""
    knobs = dict(
        max_text_len=8, max_regions=5, num_features=4,
        image_buckets=(1,), throughput_buckets=None,
        device_input_cache_entries=2, compute_dtype="float32",
        use_pallas_coattention=False, use_pallas_self_attention=False,
        aot_cache_dir=str(aot_dir))
    knobs.update(kw)
    return FrameworkConfig(model=tiny_config, engine=EngineConfig(**knobs))


def _total_compiles() -> float:
    return sum(runtime._COMPILES.collect().values())


def _failures(event: str) -> float:
    """Swallowed-failure count for one event — what chip_smoke.py requires
    to stay flat, since no flight recorder is installed during boot."""
    return aotcache._FAILURES.collect().get((event,), 0.0)


def test_record_key_matches_manifest_grammar():
    key = aotcache.record_key("rows", 8, "bfloat16", True, "dp-1.tp1.sp1",
                              False)
    assert key == "rows/b8/bfloat16/fused/dp-1.tp1.sp1/plain"
    assert aotcache.entry_filename(key).endswith(aotcache.ENTRY_SUFFIX)
    assert "/" not in aotcache.entry_filename(key)


def test_fingerprint_discriminates(tiny_config):
    cfg = FrameworkConfig(model=tiny_config)
    fp = aotcache.compile_fingerprint(cfg)
    # Any compile-relevant knob flip lands in a different cache
    # generation (the Pallas flags included: an XLA-attention program must
    # never be served to an engine configured for the kernels).
    xla = dataclasses.replace(
        cfg, engine=dataclasses.replace(cfg.engine,
                                        use_pallas_coattention=False))
    assert (aotcache.fingerprint_hash(aotcache.compile_fingerprint(xla))
            != aotcache.fingerprint_hash(fp))
    other = dataclasses.replace(
        cfg, engine=dataclasses.replace(cfg.engine, param_dtype="bfloat16"))
    assert (aotcache.fingerprint_hash(aotcache.compile_fingerprint(other))
            != aotcache.fingerprint_hash(fp))
    # Non-compile knobs (paths, warmup parallelism) must NOT split caches.
    same = dataclasses.replace(
        cfg, engine=dataclasses.replace(cfg.engine, vocab_path="elsewhere",
                                        parallel_warmup=False))
    assert (aotcache.fingerprint_hash(aotcache.compile_fingerprint(same))
            == aotcache.fingerprint_hash(fp))


def test_round_trip_zero_compiles(tiny_config, tmp_path):
    aot_dir = tmp_path / "aot"
    cfg = _cfg(tiny_config, aot_dir)

    cold = InferenceEngine(cfg, seed=0)
    cold.warmup()
    stats = cold.live_stats()
    assert stats["engine_aot_compiled"] == 1.0
    assert stats["engine_aot_hits"] == 0.0
    assert cold._aot.entry_count() == 1
    assert stats.get("engine_boot_compile_s", 0.0) > 0.0
    _, ref = cold.run(cold.prepare(1, "what is this", _regions()))

    # Fresh engine, same dir: every warmup program deserializes — the
    # fast-boot contract is ZERO traces/compiles for manifest-covered
    # programs (ISSUE acceptance).
    before = _total_compiles()
    fell_back = _failures("exec_fallback")
    warm = InferenceEngine(cfg, params=cold.params, seed=0)
    assert warm.boot_from_cache() is True
    stats = warm.live_stats()
    assert stats["engine_aot_hits"] == 1.0
    assert stats["engine_aot_compiled"] == 0.0
    assert stats["engine_aot_fallbacks"] == 0.0
    assert stats.get("engine_boot_cache_load_s", 0.0) > 0.0
    assert _total_compiles() == before
    # The deserialized executable must SERVE, same numbers as the compiled
    # one (shared params → identical logits path).
    _, out = warm.run(warm.prepare(1, "what is this", _regions()))
    assert out.task_id == ref.task_id
    assert ([a["answer"] for a in out.answers]
            == [a["answer"] for a in ref.answers])
    np.testing.assert_allclose([a["confidence"] for a in out.answers],
                               [a["confidence"] for a in ref.answers],
                               rtol=1e-5)
    assert warm.live_stats()["engine_aot_fallbacks"] == 0.0
    assert _failures("exec_fallback") == fell_back
    assert _total_compiles() == before


def test_corrupt_entry_misses_and_recompiles(tiny_config, tmp_path):
    aot_dir = tmp_path / "aot"
    cfg = _cfg(tiny_config, aot_dir)
    cold = InferenceEngine(cfg, seed=0)
    cold.warmup()
    (entry,) = glob.glob(
        os.path.join(str(aot_dir), "**", "*" + aotcache.ENTRY_SUFFIX),
        recursive=True)
    with open(entry, "wb") as f:
        f.write(b"not a pickled executable")

    # A poisoned entry must cost a recompile, never a broken engine:
    # load fails -> miss -> compile -> the entry is rewritten healthy.
    # Swallowed, but counted.
    before = _failures("load_failed")
    warm = InferenceEngine(cfg, params=cold.params, seed=0)
    assert warm.boot_from_cache() is False
    assert _failures("load_failed") == before + 1
    warm.warmup()
    stats = warm.live_stats()
    assert stats["engine_aot_compiled"] == 1.0
    _, out = warm.run(warm.prepare(1, "what is this", _regions()))
    assert out.answers

    rewarmed = InferenceEngine(cfg, params=cold.params, seed=0)
    assert rewarmed.boot_from_cache() is True


def test_stale_fingerprint_misses(tiny_config, tmp_path):
    """Same cache dir, different compile-relevant config: the entry must
    MISS on fingerprint, not deserialize into a wrong-shape executable."""
    aot_dir = tmp_path / "aot"
    cold = InferenceEngine(_cfg(tiny_config, aot_dir), seed=0)
    cold.warmup()
    changed = _cfg(tiny_config, aot_dir, max_regions=7)
    other = InferenceEngine(changed, seed=0)
    assert other.boot_from_cache() is False
