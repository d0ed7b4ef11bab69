"""int8 weight-storage smoke: parity + roofline-knee plumbing, CPU-sized.

Bounded CI gate (scripts/check.sh) for the ``param_dtype="int8"`` serving
mode, on the tiny model so it runs in seconds:

- **storage**: the int8 engine's served tree is quantized pairs and reads
  < 0.35x the f32 bytes (scales + vector leaves keep it off exactly 0.25);
- **parity**: one representative task per decode family (labels / binary /
  grounding) decodes within per-channel quantization noise of the f32
  engine, through the FUSED head path (the serving default);
- **knee**: the analytic batch-knee (engine/flops.knee_rows) is finite,
  >= 1, and strictly smaller for int8 than for f32 storage: fewer weight
  bytes flip the roofline verdict to compute-bound at a smaller batch.
  ``weight_bytes_per_row`` must shrink with batch and with the storage
  dtype.

Usage: python scripts/quant_smoke.py [--out QUANT_SMOKE.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import numpy as np

    from vilbert_multitask_tpu import quant
    from vilbert_multitask_tpu.config import (
        EngineConfig,
        FrameworkConfig,
        TASK_REGISTRY,
        ViLBertConfig,
    )
    from vilbert_multitask_tpu.engine.flops import (
        knee_rows,
        param_tree_bytes,
        weight_bytes_per_row,
    )
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.features.pipeline import RegionFeatures

    model = ViLBertConfig().tiny()
    ecfg = EngineConfig(compute_dtype="float32", max_regions=11,
                        use_pallas_coattention=False,
                        use_pallas_self_attention=False)
    eng32 = InferenceEngine(
        FrameworkConfig(model=model, engine=ecfg), seed=0)
    host = jax.device_get(eng32.params)
    engq = InferenceEngine(
        FrameworkConfig(model=model,
                        engine=dataclasses.replace(ecfg,
                                                   param_dtype="int8")),
        params=host)
    assert quant.tree_is_quantized(engq.params), "int8 engine not quantized"
    assert engq.head_slabs is not None, "fused head slabs missing"

    b32 = param_tree_bytes(eng32.params)
    bq = param_tree_bytes(engq.params)
    ratio = bq / b32
    assert ratio < 0.35, f"int8 tree reads {ratio:.2f}x of f32 (want <0.35)"

    # One task per decode family, through run() (the fused serving path).
    rng = np.random.RandomState(0)
    fd = model.v_feature_size
    boxes = np.clip(rng.uniform(0, 200, size=(7, 4)), 0, 640)
    boxes[:, 2:] = boxes[:, :2] + 10
    regions = [RegionFeatures(
        features=rng.randn(7, fd).astype(np.float32),
        boxes=boxes.astype(np.float32), image_width=640, image_height=480)
        for _ in range(2)]
    maxdiffs = {}
    for task_id in (1, 12, 4):  # labels / binary / grounding
        spec = TASK_REGISTRY[task_id]
        imgs = regions[:spec.min_images]
        q = spec.placeholder or "what is in the picture"
        out32, _ = eng32.run(eng32.prepare(task_id, q, imgs))
        outq, _ = engq.run(engq.prepare(task_id, q, imgs))
        a = np.asarray(jax.device_get(getattr(out32, spec.head)), np.float32)
        b = np.asarray(jax.device_get(getattr(outq, spec.head)), np.float32)
        diff = float(np.max(np.abs(a - b)))
        span = float(np.max(np.abs(a))) or 1.0
        assert diff <= 0.15 + 0.15 * span, (
            f"task {task_id} {spec.head}: int8 drifted {diff:.3f} "
            f"(span {span:.3f})")
        maxdiffs[spec.head] = round(diff, 5)

    # The analytic knee on a NAMED chip (this smoke runs on CPU, which has
    # no entry in the peak tables): int8's fewer weight bytes must flip
    # the v5e roofline verdict at a strictly smaller batch.
    kind = "TPU v5e"
    knee32 = knee_rows(model, ecfg, kind, b32)
    kneeq = knee_rows(model, ecfg, kind, bq)
    assert 1 <= kneeq < knee32, (kneeq, knee32)
    wpr = {str(n): round(weight_bytes_per_row(bq, n), 1)
           for n in (64, 128, 256)}
    assert wpr["256"] < wpr["64"]

    payload = {
        "ok": True,
        "param_bytes_f32": b32,
        "param_bytes_int8": bq,
        "bytes_ratio": round(ratio, 4),
        "head_maxdiff": maxdiffs,
        "knee_chip": kind,
        "knee_rows_f32": knee32,
        "knee_rows_int8": kneeq,
        "weight_bytes_per_row_int8": wpr,
    }
    line = json.dumps(payload)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
