"""Offline AOT cache population: ``python -m …engine.prewarm``.

Walks the COMPILE_SURFACE.json manifest (analysis/surface.py — the repo's
static enumeration of every program the engine can compile) and ensures an
AOT cache entry exists for each record matching this process's engine
variant: lower+compile+serialize on miss, verify-deserialize on hit. Run it
in CI after a config or model change and every replica host that mounts the
cache directory boots warm — restarts deserialize in seconds instead of
re-tracing for minutes (engine/aotcache.py).

One process covers ONE variant (param_dtype × fused × topology): records
for other variants are reported as skipped, not errors — re-run with
``--dtype``/``--per-head`` or on the target topology to cover them.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time


def _parse_buckets(text: str | None):
    if not text:
        return None
    return {int(tok) for tok in text.replace(",", " ").split()}


def main(argv=None) -> int:
    from vilbert_multitask_tpu.config import (
        FrameworkConfig,
        add_backend_args,
        apply_backend_args,
    )

    p = argparse.ArgumentParser(
        description="populate the AOT executable cache from the compile-"
                    "surface manifest (offline; replicas then boot warm)")
    p.add_argument("--manifest", default="COMPILE_SURFACE.json",
                   help="compile-surface manifest (analysis surface)")
    p.add_argument("--cache-dir", default=None,
                   help="AOT cache root (default: EngineConfig.aot_cache_dir"
                        " or serve_state/aot_cache)")
    p.add_argument("--family", choices=("batched", "rows"), default=None,
                   help="restrict to one program family")
    p.add_argument("--buckets", default=None,
                   help="comma-separated bucket filter (default: all)")
    p.add_argument("--dtype", default=None,
                   choices=("float32", "bfloat16", "int8"),
                   help="prewarm this param-storage variant instead of the "
                        "config default")
    p.add_argument("--per-head", action="store_true",
                   help="prewarm the per-head (non-fused) head variant")
    add_backend_args(p)
    args = p.parse_args(argv)

    cfg = apply_backend_args(FrameworkConfig(), args)
    ecfg = cfg.engine
    overrides = {}
    if args.dtype:
        overrides["param_dtype"] = args.dtype
    if args.per_head:
        overrides["fused_task_heads"] = False
    cache_dir = (args.cache_dir or ecfg.aot_cache_dir
                 or os.path.join("serve_state", "aot_cache"))
    overrides["aot_cache_dir"] = cache_dir
    cfg = dataclasses.replace(
        cfg, engine=dataclasses.replace(ecfg, **overrides))

    with open(args.manifest) as f:
        manifest = json.load(f)
    records = manifest["records"]

    # jax only after apply_backend_args (--cpu pins the platform).
    import jax

    from vilbert_multitask_tpu.engine import aotcache
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine

    mesh = None
    if jax.device_count() > 1:
        from vilbert_multitask_tpu.parallel import build_mesh

        mesh = build_mesh(cfg.mesh)
    topology = aotcache.topology_id(cfg.mesh)
    want_buckets = _parse_buckets(args.buckets)
    valid_buckets = set(cfg.engine.all_row_buckets())

    def matches(rec) -> str | None:
        """None if this process can compile the record, else skip reason."""
        if rec["param_dtype"] != cfg.engine.param_dtype:
            return "dtype"
        if rec["fused"] != cfg.engine.fused_task_heads:
            return "heads"
        if rec["topology"] != topology:
            return "topology"
        if rec["bucket"] not in valid_buckets:
            return "bucket"
        if args.family and rec["family"] != args.family:
            return "filtered"
        if want_buckets is not None and rec["bucket"] not in want_buckets:
            return "filtered"
        return None

    todo = [(rec, matches(rec)) for rec in records]
    n_todo = sum(1 for _, why in todo if why is None)
    print(f"prewarm: {n_todo}/{len(records)} manifest records match this "
          f"variant ({cfg.engine.param_dtype}/"
          f"{'fused' if cfg.engine.fused_task_heads else 'perhead'}/"
          f"{topology}) -> {cache_dir}")
    if not n_todo:
        return 0

    t0 = time.perf_counter()
    engine = InferenceEngine(cfg, mesh=mesh, replica_id="prewarm")
    init_s = time.perf_counter() - t0

    width = max(len(rec["key"]) for rec in records)
    counts = {"hit": 0, "compiled": 0}
    skipped: dict = {}
    for rec, why in todo:
        if why is not None:
            skipped[why] = skipped.get(why, 0) + 1
            continue
        t1 = time.perf_counter()
        status = engine.aot_compile_record(
            rec["family"], rec["bucket"], rec["collect_attention"])
        ms = (time.perf_counter() - t1) * 1e3
        counts[status] = counts.get(status, 0) + 1
        print(f"  {rec['key']:<{width}}  {status:<8}  {ms:8.1f} ms")
    skip_text = " ".join(f"{k}={v}" for k, v in sorted(skipped.items()))
    print(f"prewarm: hits={counts['hit']} compiled={counts['compiled']} "
          f"skipped=[{skip_text or 'none'}] "
          f"entries={engine._aot.entry_count()} "
          f"init={init_s:.1f}s total={time.perf_counter() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
