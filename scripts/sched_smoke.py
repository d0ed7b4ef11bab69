"""Scheduler soak smoke: continuous batching must beat the solo loop.

Bounded CI gate for the continuous-batching data plane
(serve/scheduler.py): serve the same mixed burst twice through one shared
tiny engine — once as a strictly serial batch=1 loop (claim → step_one,
the reference worker's shape and the scheduler's floor), once through the
pipelined intake → EDF window dispatch → async completion plane — and
assert the scheduler (a) loses nothing (every job exactly one result,
queue empty, nothing stuck inflight) and (b) sustains at least the solo
loop's throughput. No HTTP/websocket tiers: the subject is the
worker/engine seam, so jobs publish straight into a DurableQueue and
results read straight off the PushHub.

Usage: python scripts/sched_smoke.py [--jobs 32] [--out SCHED_SMOKE.json]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import queue as queue_mod
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from serve_soak import (  # noqa: E402
    PATTERN,
    _build_cfg,
    _make_features,
)


def _fresh_stack(cfg, engine, root, tag):
    from vilbert_multitask_tpu.serve import (
        DurableQueue,
        PushHub,
        ResultStore,
        ServeWorker,
    )

    s = dataclasses.replace(
        cfg.serving,
        queue_db_path=os.path.join(root, f"q_{tag}.sqlite3"),
        results_db_path=os.path.join(root, f"r_{tag}.sqlite3"))
    hub = PushHub()
    q = DurableQueue(s.queue_db_path,
                     max_delivery_attempts=s.max_delivery_attempts)
    store = ResultStore(s.results_db_path)
    return s, hub, q, store, ServeWorker(engine, q, store, hub, s)


def _publish_burst(q, n, sock):
    from vilbert_multitask_tpu.resilience import Deadline
    from vilbert_multitask_tpu.serve.queue import make_job_message

    for i in range(n):
        task_id, q_t, n_img = PATTERN[i % len(PATTERN)]
        q.publish(make_job_message(
            [f"img_{k}.jpg" for k in range(n_img)], q_t.format(i=i),
            task_id, sock, deadline=Deadline(120.0).to_wire(),
            published_unix=time.time()))


def _count_results(sub, n, timeout_s=120.0):
    got = 0
    deadline = time.monotonic() + timeout_s
    while got < n and time.monotonic() < deadline:
        try:
            frame = sub.get(timeout=5)
        except queue_mod.Empty:
            continue
        if "result" in frame:
            got += 1
    return got


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--jobs", type=int, default=32)
    p.add_argument("--out", default="SCHED_SMOKE.json")
    args = p.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.features.store import FeatureStore

    root = tempfile.mkdtemp(prefix="sched_smoke_")
    cfg = _build_cfg(root, full=False)
    feat = _make_features(root, cfg.model.v_feature_size)
    engine = InferenceEngine(cfg, feature_store=FeatureStore(feat))
    engine.warmup()

    # --- baseline: strictly serial batch=1 loop (claim → step_one) ------
    _s, hub, q, _store, worker = _fresh_stack(cfg, engine, root, "solo")
    sub = hub.subscribe("smoke")
    _publish_burst(q, args.jobs, "smoke")
    t0 = time.perf_counter()
    solo_done = 0
    while True:
        job = worker._claim()
        if job is None:
            break
        if worker.step_one(job) == "acked":
            solo_done += 1
    solo_s = time.perf_counter() - t0
    solo_done = min(solo_done, _count_results(sub, solo_done, timeout_s=10))

    # --- scheduler: the pipelined three-stage data plane ----------------
    _s, hub, q, _store, worker = _fresh_stack(cfg, engine, root, "sched")
    sub = hub.subscribe("smoke")
    _publish_burst(q, args.jobs, "smoke")
    stop = threading.Event()
    t0 = time.perf_counter()
    wt = threading.Thread(target=worker.run_forever,
                          kwargs={"poll_interval_s": 0.01,
                                  "stop_event": stop}, daemon=True)
    wt.start()
    sched_done = _count_results(sub, args.jobs)
    sched_s = time.perf_counter() - t0
    stop.set()
    wt.join(timeout=30)

    counts = q.counts()
    solo_qps = solo_done / solo_s if solo_s > 0 else 0.0
    sched_qps = sched_done / sched_s if sched_s > 0 else 0.0
    no_lost = (sched_done == args.jobs and not wt.is_alive()
               and counts.get("inflight", 0) == 0
               and worker.inflight_count() == 0)
    # The scheduler must not regress below the serial loop. A small
    # tolerance keeps the gate robust to CI timer noise on a loaded box;
    # the real margin (2x+) is the soak's subject, not this smoke's.
    verdict = bool(no_lost and solo_done == args.jobs
                   and sched_qps >= solo_qps * 0.9)
    report = {
        "metric": "sched_smoke",
        "jobs": args.jobs,
        "solo_qps": round(solo_qps, 2),
        "sched_qps": round(sched_qps, 2),
        "speedup": round(sched_qps / solo_qps, 2) if solo_qps else None,
        "solo_completed": solo_done,
        "sched_completed": sched_done,
        "queue_counts_after": counts,
        "no_lost_jobs": no_lost,
        "verdict": verdict,
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
