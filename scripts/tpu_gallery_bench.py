"""Gallery-retrieval throughput on the live backend → JSON artifact.

BASELINE's "Flickr30k IR R@1" protocol ranks each caption against the
full test gallery (~1,000 images). The eval path exists and is
CPU-tested (evals/harness.py:eval_retrieval_gallery); this bench records
its COST at serving scale: captions/s against an N-image synthetic
gallery, with the device input cache keeping gallery features resident
so each caption after the first ships only text. The number projects
directly to the real split: wall ≈ n_captions / captions_per_s once
features are onboarded.

Usage: python scripts/tpu_gallery_bench.py [--gallery 100] [--captions 20]
       [--out FILE.json] [--tiny]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

# Runnable from anywhere: sys.path[0] is scripts/, the package lives one up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--gallery", type=int, default=100)
    p.add_argument("--captions", type=int, default=20)
    p.add_argument("--out", default="GALLERY_BENCH.json")
    p.add_argument("--tiny", action="store_true",
                   help="tiny model + CPU pin (smoke runs)")
    args = p.parse_args(argv)

    import dataclasses

    if args.tiny:
        import jax

        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from vilbert_multitask_tpu.config import FrameworkConfig
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.evals.harness import Evaluator
    from vilbert_multitask_tpu.features.pipeline import synthetic_regions
    from vilbert_multitask_tpu.features.store import (
        FeatureStore,
        save_reference_npy,
    )

    cfg = FrameworkConfig()
    if args.tiny:
        # CPU smoke: tiny model on XLA attention (the Pallas kernels
        # compile only for a TPU).
        cfg = dataclasses.replace(
            cfg, model=cfg.model.tiny(), engine=dataclasses.replace(
                cfg.engine, use_pallas_coattention=False,
                use_pallas_self_attention=False))
    # Size the device input cache to the gallery: the protocol's whole
    # economy is gallery features staying resident (~0.4 MB bf16/image —
    # a 1k gallery is ~0.4 GB of a 16 GB HBM). The 64-entry serving
    # default would thrash and re-upload every caption.
    if args.gallery > cfg.engine.device_input_cache_entries:
        cfg = dataclasses.replace(cfg, engine=dataclasses.replace(
            cfg.engine, device_input_cache_entries=args.gallery))

    root = tempfile.mkdtemp(prefix="gallery_bench_")
    rng = np.random.default_rng(0)
    keys = [f"g{i:04d}" for i in range(args.gallery)]
    for k in keys:
        save_reference_npy(
            os.path.join(root, f"{k}.npy"),
            synthetic_regions(cfg.model.v_feature_size, n_boxes=36, rng=rng),
            k)
    examples = [{"caption": f"a photo of scene number {i}",
                 "image": keys[i % len(keys)]}
                for i in range(args.captions)]

    t0 = time.perf_counter()
    engine = InferenceEngine(cfg, feature_store=FeatureStore(root))
    init_s = time.perf_counter() - t0
    ev = Evaluator(engine, batch=8)
    # One caption warms every compiled bucket the chunking uses AND pins
    # the whole gallery in the device input cache (store-backed keys are
    # content-stable identities).
    t0 = time.perf_counter()
    ev.eval_retrieval_gallery(examples[:1], gallery=keys)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = ev.eval_retrieval_gallery(examples, gallery=keys)
    dt = time.perf_counter() - t0

    dev = __import__("jax").devices()[0]
    report = {
        "metric": "gallery_captions_per_s",
        "value": round(len(examples) / dt, 3),
        "unit": "captions/s",
        "n_gallery": args.gallery,
        "n_captions": len(examples),
        "wall_s": round(dt, 2),
        "first_caption_s": round(warm_s, 2),
        "chunk": out["chunk"],
        # Random weights: recall is noise, but the protocol plumbing ran —
        # the rank bookkeeping found every target in its gallery scores.
        "median_rank_random_weights": out["median_rank"],
        "projected_flickr30k_test_s": round(
            5000 / max(len(examples) / dt, 1e-9), 1),
        "init_s": round(init_s, 1),
        "device_kind": dev.device_kind,
        "backend": dev.platform,
        "model": "tiny" if args.tiny else "full",
        "input_cache": engine.input_cache_stats,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
