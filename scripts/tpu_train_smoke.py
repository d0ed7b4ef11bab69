"""TPU training smoke: N tiny-config steps on the live chip → JSON artifact.

The trainer (train/loop.py) is otherwise only ever run on CPU. This
captures hardware step time, memory headroom and the donation check into
a JSON artifact; run it on the chip as one command of its own.

Usage: python scripts/tpu_train_smoke.py [--steps 50] [--out FILE.json]
       [--full]   # flagship-size model instead of tiny
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
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--out", default="TRAIN_SMOKE.json")
    p.add_argument("--full", action="store_true",
                   help="flagship 270M config instead of tiny")
    args = p.parse_args(argv)

    import dataclasses

    import jax
    import numpy as np

    t_boot = time.perf_counter()
    dev = jax.devices()[0]
    print(f"# device: {dev.device_kind} ({dev.platform}), "
          f"init {time.perf_counter() - t_boot:.1f}s", file=sys.stderr)

    from vilbert_multitask_tpu.config import FrameworkConfig
    from vilbert_multitask_tpu.train.loop import (
        LoopConfig,
        MultiTaskSampler,
        SyntheticTaskData,
        Trainer,
    )

    cfg = FrameworkConfig()
    if not args.full:
        cfg = dataclasses.replace(cfg, model=cfg.model.tiny())
    heads = ("vqa", "tri", "grounding")
    datasets = {h: SyntheticTaskData(h, cfg) for h in heads}
    # log_every=1: every step's log call timestamps it, so the steady-state
    # rate below can exclude the first-occurrence compiles (one jit program
    # per head) that would otherwise dominate a 50-step wall clock.
    loop = LoopConfig(total_steps=args.steps, batch_size=args.batch,
                      log_every=1,
                      ckpt_every=10 * args.steps,  # no snapshots: pure smoke
                      warmup_steps=max(args.steps // 10, 1))

    step_ts: list = []

    def _log(s: str) -> None:
        step_ts.append(time.perf_counter())
        print(f"# {s}", file=sys.stderr)

    t0 = time.perf_counter()
    trainer = Trainer(cfg, MultiTaskSampler(datasets), loop, log_fn=_log)
    build_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    final = trainer.train()
    wall_s = time.perf_counter() - t0
    # Steady state = the back half of the run: every head's program has
    # compiled by then (3 heads alternate round-robin from step 1).
    steady = None
    half = len(step_ts) // 2
    if half >= 2:
        span = step_ts[-1] - step_ts[half - 1]
        if span > 0:
            steady = round((len(step_ts) - half) / span, 3)

    mem = {}
    try:
        stats = dev.memory_stats() or {}
        mem = {
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit"),
            "headroom_frac": (
                round(1 - stats["peak_bytes_in_use"] / stats["bytes_limit"],
                      4)
                if stats.get("peak_bytes_in_use") and stats.get("bytes_limit")
                else None),
        }
    except Exception as e:  # noqa: BLE001 — memory stats are best-effort
        mem = {"error": str(e)[:120]}

    # First step includes compile; steady-state rate excludes it by timing
    # the whole run and subtracting nothing — report both wall and marginal.
    report = {
        "metric": "train_steps_per_s",
        "value": round(args.steps / wall_s, 3),
        # compile-excluded rate from the back half of the run — the number
        # that actually answers "how fast does a hardware step run".
        "steady_steps_per_s": steady,
        "unit": "steps/s",
        "steps": args.steps,
        "batch": args.batch,
        "model": "full" if args.full else "tiny",
        "final_loss": float(final["loss/total"]),
        "loss_finite": bool(np.isfinite(final["loss/total"])),
        "build_s": round(build_s, 1),
        "wall_s": round(wall_s, 1),
        "device_kind": dev.device_kind,
        "backend": dev.platform,
        **mem,
    }
    # Per-step span timeline (train.data / train.step / train.checkpoint)
    # next to the report — load at https://ui.perfetto.dev to see which
    # steps carried first-occurrence compiles.
    from vilbert_multitask_tpu.obs import dump_trace

    trace_file = os.path.splitext(args.out)[0] + "_trace.json"
    dump_trace(trace_file)
    report["trace_file"] = trace_file

    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)
    return 0 if report["loss_finite"] else 1


if __name__ == "__main__":
    sys.exit(main())
