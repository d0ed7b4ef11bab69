"""Serving-latency benchmark: p50 per query over a full task round-robin.

Measures the BASELINE.md north-star metric — per-query latency across all
served task endpoints (reference instrumented but never published this;
worker.py:657-658) — on the TPU, in ONE process, and prints ONE JSON line:

    {"metric": "p50_latency_ms", "value": N, "unit": "ms", "vs_baseline": R,
     ..., "backend": "tpu", "device_kind": "...", "device_count": 1}

``vs_baseline`` is target/measured against the <150 ms p50 target from
BASELINE.json ("north_star"): >1.0 beats the target.

The run either measures or fails: without a TPU it exits non-zero naming
the platform it found (``config.require_tpu``), and an exception in any
phase — latency, throughput, anatomy — fails the run instead of becoming a
missing key. One process holds the chip from start to end; nothing is
spawned. The compile caches sit where engine/cachedir.py puts them.

Env knobs:
- ``BENCH_TINY=1``     the explicit CPU smoke: tiny model config, CPU
  platform pinned in-process, XLA attention. Its numbers are CPU numbers
  (``"backend": "cpu"``) and ledger under a separate metric name.
- ``BENCH_PALLAS=0|1``  force the kernel path off/on (a second run with 0
  is the kernel-on-vs-off comparison); unset → config defaults.
- ``BENCH_SWEEP_ROWS`` comma-separated extra run_many chunk sizes (e.g.
  ``64,128``) to time alongside the configured buckets — the chunk-size
  knee finder. Each size costs one extra bucket compile; the headline
  ``batch_qps`` becomes the best size measured.
- ``BENCH_PROFILE_DIR`` capture a ``jax.profiler`` device trace of one
  warm round-robin pass into this directory.
- ``BENCH_TRACE_OUT`` write the measurement's span trace (engine tokenize /
  features / forward / decode intervals) as Chrome-trace JSON to this path
  (open at https://ui.perfetto.dev).
- ``VMT_PERF_LEDGER`` when set, the run appends its headline keys to that
  ledger file (``scripts/perf_ledger.py check`` gates on it); unset,
  nothing is appended anywhere.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

from vilbert_multitask_tpu.obs import Histogram, dump_trace, percentile

BASELINE_P50_MS = 150.0

# BENCH_TINY=1 is the explicit CPU smoke: tiny model config, CPU pinned
# (the CPU backend is ~100x slower than a chip on the 270M config). Without
# it the run requires a TPU.
TINY = os.environ.get("BENCH_TINY", "") not in ("", "0")
# Forced kernel selection ("0"/"1"); unset → config defaults.
FORCE_PALLAS = os.environ.get("BENCH_PALLAS", "")


# Extra run_many chunk sizes to time in the throughput pass (see docstring).
# Malformed or non-positive entries are dropped, not raised.
def _parse_sweep(raw: str) -> tuple:
    out = []
    for s in raw.split(","):
        try:
            v = int(s)
        except ValueError:
            continue
        if v > 0:
            out.append(v)
    return tuple(out)


# Hardware default "64,128,256": the knee above the 32-row bucket is the
# open throughput question (ROADMAP A3) — 256 rows brackets the analytic
# int8 knee (engine/flops.py:knee_rows) from above, so the sweep can
# observe the verdict flip. Three extra bucket compiles. TINY smoke keeps
# no sweep.
SWEEP_ROWS = _parse_sweep(
    os.environ.get("BENCH_SWEEP_ROWS", "" if TINY else "64,128,256"))


def synth_regions(rng, cfg, n_boxes=100):
    from vilbert_multitask_tpu.features.pipeline import synthetic_regions

    return synthetic_regions(cfg.model.v_feature_size, n_boxes=n_boxes,
                             rng=rng)


# The 8 served task types (config.TASK_REGISTRY). Retrieval runs at 2, 4, 8
# and 10 candidates so EVERY compiled shape bucket (EngineConfig.image_buckets
# = 1,2,4,8,10) is warmed and timed — the reference serves 2-10 candidate
# images (worker.py:278-284).
ROUND_ROBIN = [
    (1, "what is the man holding", 1),      # VQA
    (15, "is the bowl right of the mug", 1),  # GQA
    (4, "which object can you eat", 1),     # Visual7W pointing
    (11, "the woman in the red coat", 1),   # RefCOCO
    (16, "q: is it a person? a: no", 1),    # GuessWhat
    (13, "two dogs play in the snow", 1),   # SNLI-VE
    (12, "both images contain two wolves", 2),  # NLVR2
    (7, "a man riding a horse", 2),         # Retrieval, bucket 2
    (7, "a dog catching a frisbee", 4),     # Retrieval, bucket 4
    (7, "a red car parked outside", 8),     # Retrieval, bucket 8
    (7, "people waiting for a train", 10),  # Retrieval, bucket 10
]
MAX_IMAGES = max(n for _, _, n in ROUND_ROBIN)


def _build_engine(pallas: bool | None):
    """Engine with the serving config; ``pallas`` overrides the kernel knobs."""
    import dataclasses

    from vilbert_multitask_tpu.config import FrameworkConfig
    from vilbert_multitask_tpu.engine import cachedir
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine

    cfg = FrameworkConfig()
    if TINY:
        # CPU smoke: XLA attention unless the kernel is forced on, in which
        # case it runs in the Pallas interpreter — said here, not inferred.
        cfg = dataclasses.replace(
            cfg, model=cfg.model.tiny(pallas_interpret=True))
        pallas = bool(pallas)
    # Both caches where every entry point keeps them (engine/cachedir.py):
    # a second run deserializes the warmup programs outright, and the
    # headline JSON records the boot-phase split either way.
    cachedir.enable_compilation_cache()
    over = dict(aot_cache_dir=cachedir.default_aot_cache_dir())
    if pallas is not None:
        over.update(use_pallas_coattention=pallas,
                    use_pallas_self_attention=pallas)
    # The CONFIGURED ceiling, recorded before any sweep extension below:
    # _measure_throughput always times this baseline size so artifacts
    # stay comparable across rounds whatever the sweep adds.
    base_tb = cfg.engine.max_batch_rows()
    if SWEEP_ROWS:
        # Sweep sizes must be compiled row buckets before run_many can
        # chunk at them; union with the configured ones.
        over["throughput_buckets"] = tuple(sorted(
            {*(cfg.engine.throughput_buckets or ()), *SWEEP_ROWS}))
    cfg = dataclasses.replace(
        cfg, engine=dataclasses.replace(cfg.engine, **over))
    return cfg, InferenceEngine(cfg), base_tb


def _round_opt(v, digits: int = 3):
    """Round-or-None: windowed percentiles are None on an empty window."""
    return round(v, digits) if v is not None else None


def _measure(engine, cfg, *, budget_s: float = 45.0):
    """Warm every bucket the round-robin hits, then time it."""
    from vilbert_multitask_tpu.engine.flops import serving_forward_flops

    rng = np.random.default_rng(0)
    regions = [synth_regions(rng, cfg) for _ in range(MAX_IMAGES)]
    # Stable per-image identities, as the serving worker passes for
    # store-backed media paths (serve/worker.py:_intake) — the demo-image
    # steady state: region tensors pin in HBM after first use and repeat
    # queries ship only the ~KB text payload. The cold (novel-upload) path
    # is measured separately below.
    reqs, tok_ms, feat_ms = [], [], []
    for task_id, q, n in ROUND_ROBIN:
        reqs.append(
            engine.prepare(task_id, q, regions[:n],
                           cache_keys=[f"bench_img_{i}" for i in range(n)]))
        # Host-side stage costs are paid at prepare() time; with no feature
        # store attached the "features" stage is the region encode.
        tok_ms.append(engine.stage_times.get("tokenize_s", 0.0) * 1e3)
        feat_ms.append(engine.stage_times.get("features_s", 0.0) * 1e3)
    # Warm exactly the buckets the timed loop hits: anything less recompiles
    # mid-measurement, anything more burns the one hardware run on compiles.
    buckets = sorted({r.bucket for r in reqs})
    t0 = time.perf_counter()
    engine.warmup(buckets=buckets)
    warm_s = time.perf_counter() - t0

    # One untimed pass absorbs host-side caches, then the timed epochs.
    t0 = time.perf_counter()
    for req in reqs:
        engine.run(req)
    per_pass_s = time.perf_counter() - t0
    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    if profile_dir:
        # One traced warm pass, separate from the timed epochs (tracing
        # adds overhead; the headline numbers must not carry it).
        import jax

        with jax.profiler.trace(profile_dir):
            for req in reqs:
                engine.run(req)
        print(f"# profiler trace written to {profile_dir}", file=sys.stderr)
    # Scale timed work to the budget so the bench fits on any backend
    # (CPU smoke runs are ~100x slower than the TPU path). The cap exists
    # for fast backends; 30 epochs × 11 queries gives percentiles real
    # support now that a query is ~100ms, not 24s.
    epochs = max(1, min(30, int(budget_s / max(per_pass_s, 1e-3))))
    lat_ms, fwd_ms, dec_ms, tflops = [], [], [], []
    # Ride the cost-attribution plane through the timed loop: each query
    # is a single-member batch (the latency bench's serving shape), so
    # device_s_conservation must come back 1.0 — the plumbing smoke — and
    # tail_kept_frac reports what the sampler kept of a real workload.
    import tempfile

    from vilbert_multitask_tpu import obs

    store = obs.TraceStore(os.path.join(
        tempfile.mkdtemp(prefix="bench_attrib_"), "traces.sqlite3"),
        "bench")
    attrib = obs.CostAttributor(
        ring=8192, on_finish=lambda c: store.offer(c))
    # Live view beside the lifetime percentiles: the same sliding-window
    # aggregation the serving SLOs run on (obs.Histogram.window_percentile)
    # over the trailing slice of the run — on a long bench this is "what a
    # dashboard would show right now", and a drift between live and
    # lifetime p95 flags a run that degraded as it went.
    live = Histogram("bench_latency_ms", "Per-query bench latency (ms).",
                     reservoir=4096)
    live_window_s = 30.0
    for _ in range(epochs):
        for (task_id, _q, _n), req in zip(ROUND_ROBIN, reqs):
            t = time.perf_counter()
            engine.run(req)
            lat_ms.append((time.perf_counter() - t) * 1e3)
            live.observe(lat_ms[-1])
            fwd_s = engine.stage_times.get("forward_s", 0.0)
            fwd_ms.append(fwd_s * 1e3)
            dec_ms.append(engine.stage_times.get("decode_s", 0.0) * 1e3)
            tid = f"bench{len(lat_ms):06d}"
            attrib.begin(tid, task=str(task_id))
            attrib.charge_batch(fwd_s, [(tid, req.n_images)],
                                batch_rows=req.n_images,
                                bucket=req.bucket)
            attrib.charge(tid, "decode",
                          engine.stage_times.get("decode_s", 0.0))
            attrib.finish(tid, "ok")
            # Achieved FLOP/s for THIS query's compiled bucket (padding rows
            # count — they're real MXU work the bucketing strategy pays for).
            flops = serving_forward_flops(cfg.model, cfg.engine, req.bucket)
            tflops.append(flops / max(fwd_s, 1e-9) / 1e12)
    # Cold pass: the same round-robin with NO cache identities — every
    # query re-uploads its region tensors (the novel-upload serving path).
    cold_ms = []
    for task_id, q, n in ROUND_ROBIN:
        req = engine.prepare(task_id, q, regions[:n])
        t = time.perf_counter()
        engine.run(req)
        cold_ms.append((time.perf_counter() - t) * 1e3)
    # Dispatch floor: a trivial jitted op on a resident device array, timed
    # the same way as a query. Separates per-dispatch overhead (PJRT launch
    # cost) from model compute.
    import jax
    import jax.numpy as jnp

    floor_ms = []
    tiny_fn = jax.jit(lambda x: x + 1.0)
    resident = jax.device_put(jnp.zeros((8, 128), jnp.float32))
    jax.block_until_ready(tiny_fn(resident))  # compile outside the timing
    for _ in range(20):
        t = time.perf_counter()
        jax.block_until_ready(tiny_fn(resident))
        floor_ms.append((time.perf_counter() - t) * 1e3)
    # All percentiles through the one shared obs implementation (linear
    # interpolation) — bench, serve, and the soak now agree on the math.
    return {
        "dispatch_floor_ms": round(percentile(floor_ms, 0.5), 3),
        "warmup_s": round(warm_s, 1),
        "n_queries": len(lat_ms),
        "cold_p50_ms": round(percentile(cold_ms, 0.5), 3),
        "buckets": buckets,
        "p50_ms": round(percentile(lat_ms, 0.5), 3),
        "p95_ms": round(percentile(lat_ms, 0.95), 3),
        # Trailing-window percentiles (last live_window_s of timed queries).
        "live_window_s": live_window_s,
        "live_p50_ms": _round_opt(live.window_percentile(0.5, live_window_s)),
        "live_p95_ms": _round_opt(
            live.window_percentile(0.95, live_window_s)),
        "forward_p50_ms": round(percentile(fwd_ms, 0.5), 3),
        "decode_p50_ms": round(percentile(dec_ms, 0.5), 3),
        "achieved_tflops_p50": round(percentile(tflops, 0.5), 4),
        # Where a query's milliseconds go, host to host (p50 per stage).
        "stage_ms": {
            "tokenize": round(percentile(tok_ms, 0.5), 3),
            "features": round(percentile(feat_ms, 0.5), 3),
            "forward": round(percentile(fwd_ms, 0.5), 3),
            "decode": round(percentile(dec_ms, 0.5), 3),
        },
        "cost_attrib": {
            "device_s_conservation": attrib.conservation()["ratio"],
            "tail_kept_frac": store.stats()["tail_kept_frac"],
        },
    }


def _measure_throughput(engine, cfg, *, n: int = 160,
                        base_tb: int | None = None):
    """Micro-batched serving throughput: ``run_many`` over single-image
    tasks — the BASELINE "full 12-task round-robin batch (shared trunk, all
    heads hot)" mode. Measured per chunk size so the round's artifact
    records the throughput-bucket decision: the 10-row max image bucket
    (retrieval semantics) vs the dedicated throughput buckets that exist purely to keep the MXU
    fed, plus any ``BENCH_SWEEP_ROWS`` knee-finder sizes. ``n`` is raised
    to 2× the largest size (rounded to a multiple of it) so every size
    gets at least two full chunks and the biggest has no ragged tail."""
    from vilbert_multitask_tpu.engine.flops import serving_forward_flops

    max_img = max(cfg.engine.image_buckets)
    # Always time the max image bucket (the pre-throughput-bucket ceiling)
    # and the largest pre-sweep configured bucket (``base_tb`` from
    # _build_engine — artifacts stay comparable across rounds whatever the
    # sweep adds); BENCH_SWEEP_ROWS adds knee-finder sizes on top.
    # Headline batch_qps = the best size measured.
    tb = base_tb if base_tb is not None else cfg.engine.max_batch_rows()
    sizes = sorted({max_img, tb, *SWEEP_ROWS})
    biggest = max(sizes)
    if n < 2 * biggest:
        n = 2 * biggest
    n = -(-n // biggest) * biggest  # round up: no ragged tail at `biggest`

    rng = np.random.default_rng(1)
    regions = [synth_regions(rng, cfg)]
    single_tasks = [(1, "what is the man holding"),
                    (15, "is the bowl right of the mug"),
                    (4, "which object can you eat"),
                    (11, "the woman in the red coat"),
                    (16, "q: is it a person? a: no"),
                    (13, "two dogs play in the snow")]
    # Same store-backed steady state as the latency pass: one pinned image,
    # so the throughput number measures compute + text upload, not feature
    # re-shipping (run_many rides the same device row cache as run()).
    reqs = [
        engine.prepare(*single_tasks[i % len(single_tasks)], regions,
                       cache_keys=["bench_thr_img"])
        for i in range(n)
    ]

    def timed(chunk_rows: int) -> tuple:
        # Fair per-size comparison: time the largest multiple of the chunk
        # size that fits in the request list, so no size pays a ragged tail
        # dispatch the others don't (n is a multiple of the biggest size,
        # so every size keeps >= half the requests).
        n_s = (n // chunk_rows) * chunk_rows
        # The warm call pays this size's bucket compile (if the persistent
        # cache missed); log it so sweep sizes carry their real price in
        # the round's stderr record — "near-free qps" claims need the
        # compile bill next to them.
        t0 = time.perf_counter()
        engine.run_many(reqs[:chunk_rows], chunk_rows=chunk_rows)  # warm
        warm_s = time.perf_counter() - t0
        print(f"# chunk {chunk_rows}: warm+compile {warm_s:.1f}s",
              file=sys.stderr)
        t0 = time.perf_counter()
        results = engine.run_many(reqs[:n_s], chunk_rows=chunk_rows)
        dt = time.perf_counter() - t0
        assert len(results) == n_s
        # Padded rows count as real work the chunking pays for; the plan
        # comes from the engine (the single copy of the packing math).
        rows = engine.padded_rows([1] * n_s, chunk_rows=chunk_rows)
        tflops = serving_forward_flops(cfg.model, cfg.engine, rows) / dt / 1e12
        return round(n_s / dt, 2), round(tflops, 4), round(warm_s, 1)

    by_size = {s: timed(s) for s in sizes}
    best = max(sizes, key=lambda s: by_size[s][0])
    out = {}
    for s in sizes:
        if s != best:
            out[f"batch_qps_b{s}"] = by_size[s][0]
            out[f"batch_tflops_b{s}"] = by_size[s][1]
        # Per-size warm+compile cost: what the sweep size actually charged
        # this run (≈0 when the persistent compile cache hit).
        out[f"batch_warm_s_b{s}"] = by_size[s][2]
    out.update({"batch_qps": by_size[best][0],
                "batch_tflops": by_size[best][1],
                "batch_chunk_rows": best})
    # The CONFIGURED ceiling under a stable key: headline batch_qps means
    # "best size measured including sweep sizes", so run-over-run
    # comparisons need a key that doesn't depend on which BENCH_SWEEP_ROWS
    # ran. tb is the pre-sweep configured bucket from _build_engine.
    out["batch_qps_base"] = by_size[tb][0]
    out["batch_chunk_rows_base"] = tb
    if best != max_img:
        out["batch_speedup_vs_max_image_bucket"] = round(
            by_size[best][0] / max(by_size[max_img][0], 1e-9), 3)
    out.update(_measure_throughput_mixed(engine, cfg))
    return out


def _measure_throughput_mixed(engine, cfg, *, groups_n: int = 8):
    """Literal "all heads hot" backlog: single-image tasks, NLVR2 pairs,
    and retrieval-4 sets in one run_many call (multi-image batching landed
    round 4 — this records that the 2-/10-image tasks stopped paying one
    dispatch each). Reported as examples/s plus the padded-row TFLOP/s."""
    from vilbert_multitask_tpu.engine.flops import serving_forward_flops

    rng = np.random.default_rng(2)
    regions = [synth_regions(rng, cfg) for _ in range(4)]
    keys = [f"bench_mix_img_{i}" for i in range(4)]
    pattern = [
        (1, "what is the man holding", 1),
        (12, "both images contain dogs", 2),
        (15, "is the bowl right of the mug", 1),
        (7, "a dog catching a frisbee", 4),
        (13, "two dogs play in the snow", 1),
        (12, "both images contain wolves", 2),
    ]
    reqs = []
    for _ in range(groups_n):
        for task_id, q, n in pattern:
            reqs.append(engine.prepare(task_id, q, regions[:n],
                                       cache_keys=keys[:n]))
    engine.run_many(reqs[: len(pattern)])  # warm the packed-chunk buckets
    t0 = time.perf_counter()
    results = engine.run_many(reqs)
    dt = time.perf_counter() - t0
    assert len(results) == len(reqs)
    # Padded-row FLOP accounting rides run_many's OWN plan (engine.padded_
    # rows) — not a re-derivation that could drift from the real packing.
    rows = engine.padded_rows([r.n_images for r in reqs])
    tflops = serving_forward_flops(cfg.model, cfg.engine, rows) / dt / 1e12
    return {"batch_qps_mixed": round(len(reqs) / dt, 2),
            "batch_tflops_mixed": round(tflops, 4),
            "batch_mixed_n": len(reqs)}


def _anatomy_probes(*, reps: int = 20, include_bigarg: bool = False) -> dict:
    """Latency anatomy: attribute the per-dispatch milliseconds.

    These probes separate the candidate per-dispatch costs so the headline
    p50 can be attributed instead of guessed at:

      manyarg_exec_ms   trivial jitted fn over 192 small resident arrays —
                        the per-ARGUMENT marshalling term (a serving forward
                        ships the whole ~190-leaf param tree every execute).
      roundtrip_ms      device_put of fresh host bytes + scalar fetch per
                        rep (fresh data defeats host-copy caching) — the
                        host<->device round trip.
      bigarg_exec_ms    (non-TINY only) trivial fn over 4 x 128 MB resident
                        arrays — per-BYTE cost for resident args; should be
                        ~free since only buffer handles are passed.

    Read together with the headline's ``dispatch_floor_ms`` (timed inside
    ``_measure``, same method): if manyarg >> floor the fix is fewer/larger
    leaves per execute (the O(1)-leaf rows path exists for exactly this);
    if neither dominates, the p50 is genuine device time and worth a
    ``BENCH_PROFILE_DIR`` trace.
    """
    import jax
    import jax.numpy as jnp

    def median_ms(fn) -> float:
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            ts.append((time.perf_counter() - t0) * 1e3)
        return round(percentile(ts, 0.5), 3)

    out: dict = {}
    leaves = [jax.device_put(jnp.full((16,), float(i), jnp.float32))
              for i in range(192)]
    manyarg = jax.jit(lambda *ls: ls[0][0] + ls[-1][0])
    jax.block_until_ready(manyarg(*leaves))  # compile outside the timing
    out["manyarg_exec_ms"] = median_ms(
        lambda: jax.block_until_ready(manyarg(*leaves)))

    counter = [0]

    def rt():
        counter[0] += 1
        y = jax.device_put(np.array([counter[0]], np.float32))
        if float(y[0]) != counter[0]:
            raise RuntimeError("host<->device round trip returned stale data")

    rt()
    out["roundtrip_ms"] = median_ms(rt)

    if include_bigarg:
        # Serving-scale resident bytes (4 x 128 MB ≈ the f32 param tree);
        # skipped in TINY/CPU smoke where the 512 MB allocation is all cost
        # and no signal.
        big = [jax.device_put(jnp.zeros((32, 1024, 1024), jnp.float32))
               for _ in range(4)]
        bigarg = jax.jit(lambda a, b, c, d: a[0, 0, 0] + d[0, 0, 0])
        jax.block_until_ready(bigarg(*big))
        out["bigarg_exec_ms"] = median_ms(
            lambda: jax.block_until_ready(bigarg(*big)))
    return out


def run_measurement() -> None:
    """Build, warm, time, print the JSON line — all in this process."""
    import jax

    from vilbert_multitask_tpu.config import require_tpu

    if TINY:
        # The explicit CPU smoke: pin in-process before backend init.
        jax.config.update("jax_platforms", "cpu")
    else:
        require_tpu("bench.py")

    t0 = time.perf_counter()
    forced = {"0": False, "1": True}.get(FORCE_PALLAS)
    cfg, engine, base_tb = _build_engine(forced)
    init_s = time.perf_counter() - t0
    print(f"# engine init {init_s:.1f}s; compiling buckets...", file=sys.stderr)
    # A kernel the compiler refuses raises out of warmup (no XLA fallback);
    # a failure in any phase below fails the run.
    stats = _measure(engine, cfg)
    thr = _measure_throughput(engine, cfg, base_tb=base_tb)
    t0 = time.perf_counter()
    anatomy = _anatomy_probes(include_bigarg=not TINY)
    print(f"# anatomy stage {time.perf_counter() - t0:.1f}s: {anatomy}",
          file=sys.stderr)
    trace_out = os.environ.get("BENCH_TRACE_OUT")
    if trace_out:
        # The engine spans recorded during _measure (tokenize / features /
        # forward / decode per query) as a Perfetto-loadable artifact.
        dump_trace(trace_out)
        print(f"# span trace written to {trace_out}", file=sys.stderr)
    device_kind = jax.devices()[0].device_kind
    print(
        f"# device={device_kind} "
        f"n_queries={stats['n_queries']} buckets={stats['buckets']} "
        f"p50={stats['p50_ms']}ms p95={stats['p95_ms']}ms "
        f"cold_p50={stats['cold_p50_ms']}ms "
        f"forward_p50={stats['forward_p50_ms']}ms "
        f"decode_p50={stats['decode_p50_ms']}ms init={init_s:.1f}s "
        f"warmup={stats['warmup_s']}s "
        f"achieved={stats['achieved_tflops_p50']}TFLOP/s "
        f"batch_qps={thr.get('batch_qps')} "
        f"batch_tflops={thr.get('batch_tflops')}",
        file=sys.stderr,
    )
    # MFU and roofline against the chip's published peaks — on the chip
    # only: the TINY CPU smoke has no entry in the peak tables and reports
    # none of these keys.
    from vilbert_multitask_tpu.engine.flops import (
        knee_rows,
        param_tree_bytes,
        peak_flops_for,
        serving_roofline,
        weight_bytes_per_row,
    )

    peak = peak_flops_for(device_kind)
    # Boot-phase split + AOT cache outcome (engine/aotcache.py): where the
    # init+warmup seconds went, and whether this boot was served from the
    # executable cache. A fully-warm boot (every warmup program
    # deserialized, zero compiles) records its wall time under
    # ``warm_cache_s`` — the fast-restart number the ledger tracks.
    live = engine.live_stats()
    boot_phases = {k[len("engine_boot_"):]: round(v, 3)
                   for k, v in live.items() if k.startswith("engine_boot_")}
    aot_hits = int(live.get("engine_aot_hits", 0))
    aot_compiled = int(live.get("engine_aot_compiled", 0))
    warm_cache_s = (round(init_s + stats["warmup_s"], 2)
                    if aot_hits and not aot_compiled else None)
    # Roofline context for the MFU numbers: every forward reads the whole
    # param tree from HBM, so small batches are weight-read-bound and a low
    # measured MFU can be the ROOF, not a software gap. param_bytes sums the
    # tree as actually stored (f32 / bf16 / int8 values + f32 scales), so it
    # also records which storage dtype served; knee_rows is the analytic
    # batch size where the verdict flips to compute-bound — the sweep's
    # 64/128/256 chunks exist to bracket it with measurements.
    param_bytes = param_tree_bytes(engine.params)
    roof_batch = thr["batch_chunk_rows"]
    roof = {}
    if not TINY:  # raises for a TPU the peak tables do not know
        roofline = serving_roofline(cfg.model, cfg.engine, roof_batch,
                                    device_kind, param_bytes)
        roof = {
            "achievable_mfu": roofline["achievable_mfu"],
            "roofline": roofline["reason"],
            "knee_rows": knee_rows(cfg.model, cfg.engine, device_kind,
                                   param_bytes),
            "mfu": round(stats["achieved_tflops_p50"] * 1e12 / peak, 5),
            "batch_mfu": round(thr["batch_tflops"] * 1e12 / peak, 5),
        }

    headline = {
        "metric": "p50_latency_ms",
        "value": stats["p50_ms"],
        "unit": "ms",
        "vs_baseline": round(BASELINE_P50_MS / stats["p50_ms"], 3),
        "p95_ms": stats["p95_ms"],
        "cold_p50_ms": stats["cold_p50_ms"],
        "device_input_cache": True,
        # Hit rate over the warm round-robin: nearly all hits, one miss
        # per distinct image — hardware evidence the row cache engages.
        # (The cold pass doesn't show here: no cache identities means it
        # bypasses the cache entirely, touching neither counter.)
        "input_cache": engine.input_cache_stats,
        "forward_p50_ms": stats["forward_p50_ms"],
        "decode_p50_ms": stats["decode_p50_ms"],
        "stage_ms": stats["stage_ms"],
        "dispatch_floor_ms": stats["dispatch_floor_ms"],
        "cost_attrib": stats["cost_attrib"],
        **anatomy,
        "param_bytes": param_bytes,
        "param_dtype": cfg.engine.param_dtype,
        "fused_task_heads": cfg.engine.fused_task_heads,
        **roof,
        "weight_bytes_per_row": round(
            weight_bytes_per_row(param_bytes, roof_batch), 1),
        "n_queries": stats["n_queries"],
        "buckets_timed": stats["buckets"],
        "init_s": round(init_s, 1),
        "warmup_s": stats["warmup_s"],
        "boot_phases": boot_phases,
        "aot_hits": aot_hits,
        "aot_compiled": aot_compiled,
        **({"warm_cache_s": warm_cache_s}
           if warm_cache_s is not None else {}),
        "achieved_tflops_p50": stats["achieved_tflops_p50"],
        **thr,
        "backend": jax.default_backend(),
        "device_kind": device_kind,
        "device_count": jax.device_count(),
        "pallas_coattention": engine.model.config.use_pallas_coattention,
    }
    print(json.dumps(headline), flush=True)
    _ledger_append(headline)


def _ledger_append(obj: dict) -> None:
    """Perf-ledger ride-along: append the run's comparable keys to the
    ledger ``$VMT_PERF_LEDGER`` names (``perf_ledger.py check`` diffs it
    against the trailing baseline window). With the variable unset this
    writes nothing — the repo's PERF_LEDGER.jsonl is not this program's."""
    from vilbert_multitask_tpu import obs
    from vilbert_multitask_tpu.config import (FrameworkConfig,
                                              config_fingerprint)

    fp = config_fingerprint(FrameworkConfig())
    device = {"backend": obj["backend"], "device_kind": obj["device_kind"]}
    values = {k: obj[k] for k in (
        "value", "p95_ms", "forward_p50_ms", "decode_p50_ms",
        "batch_qps", "knee_rows", "init_s",
    ) if isinstance(obj.get(k), (int, float))}
    # Tiny smokes ledger under their own metric: a tiny-model CPU p50 must
    # never become the hardware run's baseline (or vice versa — check()
    # windows are per-metric).
    suffix = ".tiny" if TINY else ""
    obs.ledger_append("bench.p50_latency_ms" + suffix, values,
                      config_fingerprint=fp, extra=device)
    # Warm-boot ledger line: only runs that booted fully from the AOT
    # cache append it (the ``_s`` suffix gives it direction=lower in
    # perf_ledger check), so regressions in restart wall time gate.
    if "warm_cache_s" in obj:
        obs.ledger_append("boot.warm_cache_s" + suffix,
                          {"value": obj["warm_cache_s"],
                           **obj["boot_phases"]},
                          config_fingerprint=fp, extra=device)


if __name__ == "__main__":
    run_measurement()
