"""AOT fast-boot smoke: two boots, one cache dir — second must be warm.

Bounded CI gate (scripts/check.sh) for the executable cache
(engine/aotcache.py), on the tiny model so it runs in a couple of minutes.
Each boot is a FRESH subprocess (in-process trace caches would fake the
warm number) sharing one AOT cache dir and one XLA persistent-cache dir —
the product recipe: the AOT tier covers the warmup programs, the XLA tier
covers the init-time jits. Both dirs are fresh temp dirs ON PURPOSE: the
first boot must be cold. The XLA dir reaches the children the way a
deployment places it, through ``JAX_COMPILATION_CACHE_DIR``.

CPU-only: both children pin the CPU platform, and the parent never touches
JAX (a parent that had would hold the chip its children need).

Gates:
- the second boot compiles ZERO warmup programs (every one deserializes,
  none falls back) — the ISSUE acceptance "warm second boot performs zero
  trace+compiles for manifest-covered programs";
- warm boot wall < 50% of the cold boot (hardware target is <10% of the
  ~150 s cold boot; CPU-tiny measures the same mechanism at smaller scale).

Usage: python scripts/aot_smoke.py [--out AOT_SMOKE.json]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BOOT_TIMEOUT_S = 420.0


def boot_once() -> int:
    """Child body: one engine boot (cache-first), one real request."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from vilbert_multitask_tpu.config import (
        EngineConfig,
        FrameworkConfig,
        ViLBertConfig,
    )
    from vilbert_multitask_tpu.engine import cachedir
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.features.pipeline import RegionFeatures

    t0 = time.perf_counter()
    cachedir.enable_compilation_cache()  # → $JAX_COMPILATION_CACHE_DIR
    cfg = FrameworkConfig(
        model=ViLBertConfig().tiny(),
        engine=EngineConfig(
            max_text_len=12, max_regions=9, num_features=8,
            image_buckets=(1, 2), throughput_buckets=None,
            compute_dtype="float32",
            use_pallas_coattention=False, use_pallas_self_attention=False,
            aot_cache_dir=os.environ["AOT_SMOKE_AOT_DIR"]))
    eng = InferenceEngine(cfg, seed=0)
    # The replica-boot sequence (serve/pool.py): cache first, warmup only
    # on a miss — exactly what rolling restarts and add_replica() run.
    from_cache = eng.boot_from_cache()
    if not from_cache:
        eng.warmup()
    rng = np.random.RandomState(0)
    boxes = np.clip(rng.uniform(0, 200, size=(5, 4)), 0, 640)
    boxes[:, 2:] = boxes[:, :2] + 10
    regions = [RegionFeatures(
        features=rng.randn(5, cfg.model.v_feature_size).astype(np.float32),
        boxes=boxes.astype(np.float32), image_width=640, image_height=480)]
    _, res = eng.run(eng.prepare(1, "what is this", regions))
    assert res.answers, "smoke request decoded nothing"
    wall = time.perf_counter() - t0
    stats = eng.live_stats()
    print(json.dumps({
        "wall_s": round(wall, 2),
        "from_cache": bool(from_cache),
        "aot_hits": stats.get("engine_aot_hits", 0.0),
        "aot_compiled": stats.get("engine_aot_compiled", 0.0),
        "aot_fallbacks": stats.get("engine_aot_fallbacks", 0.0),
        "cache_load_s": round(stats.get("engine_boot_cache_load_s", 0.0), 3),
        "compile_s": round(stats.get("engine_boot_compile_s", 0.0), 3),
    }), flush=True)
    return 0


def _run_boot(env: dict) -> dict:
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--boot"],
        capture_output=True, text=True, timeout=BOOT_TIMEOUT_S,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})
    line = next((ln for ln in proc.stdout.splitlines()
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        tail = (proc.stderr or "").strip().splitlines()[-8:]
        raise RuntimeError(
            f"boot child rc={proc.returncode}: " + " | ".join(tail))
    return json.loads(line)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    root = tempfile.mkdtemp(prefix="vmt_aot_smoke_")
    env = {"AOT_SMOKE_AOT_DIR": os.path.join(root, "aot"),
           "JAX_COMPILATION_CACHE_DIR": os.path.join(root, "xla")}
    cold = _run_boot(env)
    warm = _run_boot(env)
    ratio = warm["wall_s"] / max(cold["wall_s"], 1e-9)
    print(f"# cold {cold['wall_s']}s (compiled {cold['aot_compiled']:.0f}) "
          f"-> warm {warm['wall_s']}s (hits {warm['aot_hits']:.0f}), "
          f"ratio {ratio:.3f}", file=sys.stderr)

    failures = []
    if not (cold["aot_compiled"] > 0):
        failures.append(f"cold boot compiled nothing: {cold}")
    if warm["aot_compiled"] != 0 or warm["aot_fallbacks"] != 0:
        failures.append("warm boot compiled/fell back: "
                        f"{warm['aot_compiled']:.0f} compiles, "
                        f"{warm['aot_fallbacks']:.0f} fallbacks")
    if warm["aot_hits"] != cold["aot_compiled"]:
        failures.append(f"warm hits {warm['aot_hits']:.0f} != cold "
                        f"compiles {cold['aot_compiled']:.0f}")
    if not warm["from_cache"]:
        failures.append("warm boot did not take the cache path")
    if ratio >= 0.5:
        failures.append(f"warm boot {warm['wall_s']}s is {ratio:.0%} of "
                        f"cold {cold['wall_s']}s (gate: <50%)")

    payload = {
        "ok": not failures,
        "cold_boot_s": cold["wall_s"],
        "warm_cache_s": warm["wall_s"],
        "warm_over_cold": round(ratio, 4),
        "programs": cold["aot_compiled"],
        "cold": cold,
        "warm": warm,
        **({"failures": failures} if failures else {}),
    }
    line = json.dumps(payload)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0 if not failures else 1


if __name__ == "__main__":
    if "--boot" in sys.argv[1:]:
        sys.exit(boot_once())
    sys.exit(main())
