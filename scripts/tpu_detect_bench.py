"""TPU live-extractor bench: compile time + per-image latency → JSON.

The Flax Faster R-CNN (detect/model.py) is CPU-tested but has never
compiled on TPU — an 800-canvas ResNeXt through gather-based ROIAlign is
exactly the graph XLA-TPU can be pathological on. Reference puts live
extraction in the serving hot path (worker.py:192-193), so the cost must
be on record. Run it on the chip as one command of its own.

Usage: python scripts/tpu_detect_bench.py [--out FILE.json] [--reps 5]
       [--canvas 800] [--tiny]   # --tiny: small detector for smoke runs
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# Runnable from anywhere: sys.path[0] is scripts/, the package lives one up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="DETECT_BENCH.json")
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--canvas", type=int, default=None,
                   help="override canvas (default: DetectorConfig default)")
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)

    import dataclasses
    import statistics

    import jax
    import numpy as np

    dev = jax.devices()[0]
    print(f"# device: {dev.device_kind}", file=sys.stderr)

    from vilbert_multitask_tpu.config import DetectorConfig
    from vilbert_multitask_tpu.detect.extractor import LiveFeatureExtractor

    cfg = DetectorConfig().tiny() if args.tiny else DetectorConfig()
    if args.canvas:
        cfg = dataclasses.replace(cfg, canvas=args.canvas)

    report = {"metric": "detect_ms_per_image", "unit": "ms",
              "canvas": cfg.canvas, "device_kind": dev.device_kind,
              "backend": dev.platform, "tiny": bool(args.tiny)}
    try:
        t0 = time.perf_counter()
        ex = LiveFeatureExtractor(cfg)
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        ex.warmup()  # the first compile — the number this script exists for
        compile_s = time.perf_counter() - t0
        rng = np.random.default_rng(0)
        img = (rng.random((600, 800, 3)) * 255).astype(np.uint8)
        lat = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            regions = ex.extract_array(img)
            lat.append((time.perf_counter() - t0) * 1e3)
        report.update({
            "value": round(statistics.median(lat), 1),
            "compile_s": round(compile_s, 1),
            "build_s": round(build_s, 1),
            "n_boxes": int(regions.features.shape[0]),
            "reps": args.reps,
            "ok": True,
        })
        rc = 0
    except Exception as e:  # noqa: BLE001 — a Mosaic/XLA blowup IS a result
        report.update({"value": None, "ok": False,
                       "error": f"{type(e).__name__}: {e}"[:600]})
        rc = 1
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
