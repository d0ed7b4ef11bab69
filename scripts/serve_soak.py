"""End-to-end serving soak: the WHOLE stack under a burst of mixed jobs.

Drives HTTP POST → durable queue → micro-batched worker → result store →
websocket push as one system (the reference's full L0-L6 pipeline,
SURVEY §1) and measures what no unit test does: end-to-end job latency
(submit → result frame on the browser socket) and sustained jobs/s while
the worker drains a backlog through ``run_many`` batched forwards.

``--chaos`` runs the same burst under a seeded resilience FaultPlan —
transport flaps on the remote-worker path, slow claims, slow engine
dispatch, intake errors — and asserts the no-lost-jobs invariant: every
submitted job reaches EXACTLY ONE terminal state (result frame,
dead-letter error frame, or deadline-exceeded frame), never zero, never
two. The worker runs in remote mode (HTTP shims) so the injected
transport faults exercise the real RetryPolicy + CircuitBreaker path.

Runs on CPU with the tiny model by default (the serving tiers are
host-side; the forward is not the subject here) and prints ONE JSON line
plus an artifact file. ``--full`` uses the serving-size model — on a TPU
window that makes this the full-system hardware soak.

``--replicas N --dryrun`` runs the REPLICA-POOL soak: N stub engines whose
per-row service time is a GIL-releasing sleep (so replica concurrency shows
on a 1-core box) behind the real pool/scheduler/queue planes. It always
runs a 1-replica baseline burst first and reports the pool/baseline qps
ratio, plus a rolling checkpoint swap mid-burst (zero requests lost, >=1
replica ready throughout). ``--kill-replica`` adds a seeded chaos burst:
one replica is silently killed mid-burst and the run asserts exactly one
terminal per job, zero double-executions, and the dead replica visible in
/healthz within about one sampler cadence.

``--autoscale`` runs the CLOSED-LOOP AUTOSCALER soak: a diurnal +
flash-crowd load shape (ramp → spike → trough) over dryrun replicas with
``serve/autoscale.py`` live on the sampler tick. The spike must grow the
pool within one AOT-boot latency of the sustained-breach decision with
nothing shed, and the trough must retire capacity back to the floor;
``--autoscale --chaos`` instead floods poisoned jobs (seeded
``worker.intake`` faults + slow claims) and asserts the controller never
scales into the poison storm.

Every mode prints its report as one JSON line and exits by its verdict;
``--out`` also writes the report to a file.

Usage: python scripts/serve_soak.py [--jobs 96] [--out report.json]
       [--full] [--chaos] [--seed 0]
       [--replicas 2 --dryrun [--kill-replica]]
       [--autoscale [--chaos]] [--zipf [--chaos]]
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import queue as queue_mod
import sys
import tempfile
import threading
import time

# Runnable from anywhere: sys.path[0] is scripts/, the package lives one up.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# A soak's subject is the serving tiers, not the accelerator; default to
# CPU unless the caller explicitly wants the hardware path (--full implies
# whatever backend jax picks).

def _finish(report: dict, verdict: bool, out) -> int:
    """The soak's contract: the report as one JSON line (also written to
    ``--out`` when given) and the verdict as the exit code."""
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)
    return 0 if verdict else 1


def _build_cfg(root: str, full: bool, tenant_weights=None,
               extra_serving=None):
    from vilbert_multitask_tpu.config import (
        EngineConfig,
        FrameworkConfig,
        ServingConfig,
        ViLBertConfig,
    )

    model = ViLBertConfig() if full else ViLBertConfig().tiny()
    engine = EngineConfig() if full else EngineConfig(
        max_text_len=12, max_regions=9, num_features=8,
        image_buckets=(1, 2, 4), throughput_buckets=(8, 16),
        use_pallas_coattention=False, use_pallas_self_attention=False,
    )
    serving_kwargs = dict(
        queue_db_path=os.path.join(root, "queue.sqlite3"),
        results_db_path=os.path.join(root, "results.sqlite3"),
        media_root=os.path.join(root, "media"),
        http_port=0, ws_port=0,
        # Live-health plane tuned for a short run: fast sampler ticks,
        # and every trigger event dumps a bundle (the chaos acceptance
        # bar reads the injected fault's bundle back).
        sampler_cadence_s=0.25,
        recorder_min_interval_s=0.0,
        recorder_max_bundles=64,
        tenant_weights=tenant_weights,
    )
    # Mode-specific knob overrides (the autoscale soak shrinks windows and
    # cooldowns to CI scale).
    if extra_serving:
        serving_kwargs.update(extra_serving)
    return FrameworkConfig(model=model, engine=engine,
                           serving=ServingConfig(**serving_kwargs))


def _make_features(root: str, dim: int, n: int = 4) -> str:
    import numpy as np

    from vilbert_multitask_tpu.features.pipeline import synthetic_regions
    from vilbert_multitask_tpu.features.store import save_reference_npy

    d = os.path.join(root, "features")
    os.makedirs(d, exist_ok=True)
    rng = np.random.default_rng(0)
    for i in range(n):
        region = synthetic_regions(dim, n_boxes=3, rng=rng)
        save_reference_npy(os.path.join(d, f"img_{i}.npy"), region,
                           f"img_{i}")
    return d


def _chaos_plan(seed: int):
    """The seeded schedule: faults at four sites (≥3 per the acceptance
    bar) — transport flaps, slow claims, slow dispatch, intake errors.

    The transport flaps are a BOUNDED burst (max_injections): the claim
    poll hits remote.post continuously, and an unbounded 15% failure rate
    there is a dead web host, not a flap — it pins the breaker open and
    strands mid-batch persist/ack calls until the visibility timeout.
    The soak verifies riding THROUGH transient faults; hard-outage breaker
    behavior is the unit tests' and the flap e2e test's subject."""
    from vilbert_multitask_tpu.resilience import FaultPlan, FaultRule

    return FaultPlan(seed, [
        FaultRule("remote.post", "error", rate=0.15, max_injections=25),
        FaultRule("engine.dispatch", "delay", rate=0.25, delay_s=0.05),
        FaultRule("queue.claim", "delay", rate=0.3, delay_s=0.02),
        FaultRule("worker.intake", "error", rate=0.05),
    ])


def _threadkill_plan(seed: int):
    """One-shot thread assassination through the real fault path: the
    first ``queue.claim`` after install raises FaultInjected. The claim
    at the top of the scheduler's intake pump sits outside the intake
    try/except (the exc tier's VMT137 witness), so the injection rides
    the exact path that used to kill the thread silently — now the
    crash guard must turn it into a ``thread_died`` bundle and an
    unready ``/healthz`` while the surviving intake threads drain the
    burst."""
    from vilbert_multitask_tpu.resilience import FaultPlan, FaultRule

    return FaultPlan(seed, [
        FaultRule("queue.claim", "error", rate=1.0, max_injections=1),
    ])


def _chaos_worker(app, retry_budget_hint: float = 1e6):
    """A remote-mode ServeWorker against the app's own HTTP face: injected
    remote.post faults exercise the REAL RetryPolicy + breaker path."""
    from vilbert_multitask_tpu.resilience import (
        CircuitBreaker,
        RetryBudget,
        RetryPolicy,
    )
    from vilbert_multitask_tpu.serve.remote import (
        RemoteHub,
        RemoteQueue,
        RemoteStore,
        WorkerApiClient,
    )
    from vilbert_multitask_tpu.serve.worker import ServeWorker

    client = WorkerApiClient(
        f"http://127.0.0.1:{app.http_port}",
        retry=RetryPolicy(max_attempts=6, base_delay_s=0.02,
                          max_delay_s=0.2,
                          budget=RetryBudget(rate_per_s=50.0,
                                             capacity=500.0)),
        # Threshold above the plan's bounded flap burst (25 injections):
        # the breaker must ride THROUGH scripted flaps and only open on a
        # truly dead web host.
        breaker=CircuitBreaker(name="remote.transport",
                               failure_threshold=50, window_s=5.0,
                               reset_timeout_s=0.3))
    return ServeWorker(app.engine, RemoteQueue(client), RemoteStore(client),
                       RemoteHub(client), app.cfg.serving)


# ----------------------------------------------------- replica-pool soak
class _DryPrepared:
    """The prepared-request surface the scheduler/worker touch: task spec,
    row count, and (for grounding only, unused here) source images."""

    __slots__ = ("spec", "n_images", "images", "question")

    def __init__(self, spec, n_images, question):
        self.spec = spec
        self.n_images = n_images
        self.images = []
        self.question = question


class _DryResult:
    kind = "vqa"

    def __init__(self, question):
        self.question = question

    def to_json(self):
        return {"answers": [{"answer": "dry", "confidence": 1.0}]}


class DryrunEngine:
    """A stub replica whose per-row service time is a GIL-releasing sleep.

    The pool soak's subject is the SERVING planes — pool routing, the
    scheduler's per-replica executor, failover, the swap drain — not the
    forward. A sleep models a device wait accurately for that purpose: it
    releases the GIL, so two replicas genuinely overlap on a 1-core box
    and the >=1.5x scaling criterion measures the dispatch plane, not
    XLA's thread pool.
    """

    def __init__(self, cfg, name: str, service_ms_per_row: float = 12.0):
        from vilbert_multitask_tpu.config import TASK_REGISTRY

        self._registry = TASK_REGISTRY
        self.cfg = cfg
        self.replica_id = name
        self.killed = False
        self.mesh = None
        self.pallas_enabled = False
        self.input_cache_stats = {}
        self.service_s = service_ms_per_row / 1e3
        self.jobs_served = 0
        self.batches = 0
        self.loads = 0
        self._lock = threading.Lock()

    def warmup(self, buckets=None, parallel=None):
        pass

    def prepare_from_store(self, task_id, question, image_paths):
        return _DryPrepared(self._registry[int(task_id)],
                            max(len(image_paths), 1), question)

    def chunk_plan(self, n_images):
        max_rows = self.cfg.engine.max_batch_rows()
        chunks, cur, rows = [], [], 0
        for i, n in enumerate(n_images):
            if cur and rows + n > max_rows:
                chunks.append(cur)
                cur, rows = [], 0
            cur.append(i)
            rows += n
        if cur:
            chunks.append(cur)
        return chunks

    def _gate(self):
        if self.killed:
            from vilbert_multitask_tpu.resilience import ReplicaKilled

            raise ReplicaKilled(
                f"replica {self.replica_id} killed (chaos)")

    def run(self, req, **kwargs):
        self._gate()
        time.sleep(self.service_s * req.n_images)
        self._gate()
        with self._lock:
            self.jobs_served += 1
        return None, _DryResult(req.question)

    def run_many(self, reqs, on_result=None, **kwargs):
        self._gate()
        time.sleep(self.service_s * sum(r.n_images for r in reqs))
        # Second gate AFTER the service wait: a kill landing mid-batch
        # fails the whole batch before any member streams — the failover
        # path the chaos burst exists to exercise.
        self._gate()
        results = [_DryResult(r.question) for r in reqs]
        with self._lock:
            self.jobs_served += len(reqs)
            self.batches += 1
        if on_result is not None:
            for i, res in enumerate(results):
                on_result(i, res)
        return results

    def live_stats(self):
        return {"dry_jobs_served": float(self.jobs_served)}

    def load_params(self, params):
        with self._lock:
            self.loads += 1


def _pool_burst(jobs: int, replicas: int, *, seed: int = 0,
                kill: bool = False, swap: bool = False,
                service_ms: float = 12.0, label: str = "") -> dict:
    """One burst against a fresh app over ``replicas`` dryrun engines.

    Returns the burst report; ``kill``/``swap`` inject their chaos once
    the terminal count crosses a threshold, so the event always lands
    mid-burst with traffic in flight.
    """
    import random

    from vilbert_multitask_tpu.serve.app import ServeApp

    root = tempfile.mkdtemp(prefix="serve_soak_pool_")
    cfg = _build_cfg(root, False)
    engines = [DryrunEngine(cfg, f"r{i}", service_ms_per_row=service_ms)
               for i in range(replicas)]
    app = ServeApp(cfg, engine=engines)
    app.start()
    pool = app.engine
    sock = f"pool-{label}"
    sub = app.hub.subscribe(sock)
    terminals: dict = {}
    dup_terminals: list = []
    done = threading.Event()

    def consume():
        try:
            while len(terminals) < jobs:
                frame = sub.get(timeout=90)
                if "result" in frame:
                    q = frame["result"]["question"]
                elif (frame.get("dead_letter")
                      or frame.get("deadline_exceeded")
                      or "error" in frame):
                    q = frame.get("question", "")
                else:
                    continue  # progress / requeued notices are not terminal
                if q in terminals:
                    dup_terminals.append(q)
                else:
                    terminals[q] = time.perf_counter()
        except queue_mod.Empty:
            pass
        finally:
            done.set()

    reader = threading.Thread(target=consume, daemon=True)
    reader.start()

    conn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                      timeout=30)
    t_burst = time.perf_counter()
    for i in range(jobs):
        task_id, q_t, n_img = PATTERN[i % len(PATTERN)]
        body = json.dumps({
            "task_id": task_id, "socket_id": sock,
            "question": q_t.format(i=i),
            "image_list": [f"img_{k}.jpg" for k in range(n_img)],
        })
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        resp.read()

    def _wait_terminals(n):
        while len(terminals) < n and not done.is_set():
            time.sleep(0.01)

    swap_report = None
    if swap:
        _wait_terminals(max(1, jobs // 4))
        swap_report = app.rolling_swap(params={"soak": "v2"})

    kill_info = None
    if kill:
        victim = random.Random(seed).choice(
            [r.name for r in pool.replicas])
        _wait_terminals(max(1, jobs // 2))
        t_kill = time.perf_counter()
        pool.kill(victim)
        dead_visible_s = None
        hconn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                           timeout=10)
        while time.perf_counter() - t_kill < 10.0:
            hconn.request("GET", "/healthz")
            payload = json.loads(hconn.getresponse().read())
            states = {r["name"]: r["state"]
                      for r in payload.get("replicas", [])}
            if states.get(victim) == "dead":
                dead_visible_s = round(time.perf_counter() - t_kill, 3)
                break
            time.sleep(0.01)
        hconn.close()
        kill_info = {"victim": victim, "seed": seed,
                     "dead_visible_s": dead_visible_s,
                     "sampler_cadence_s":
                         cfg.serving.sampler_cadence_s}

    all_done = done.wait(timeout=180)
    makespan_s = ((max(terminals.values()) - t_burst)
                  if terminals else time.perf_counter() - t_burst)
    app.stop()
    qps = round(len(terminals) / makespan_s, 2) if makespan_s > 0 else 0.0
    report = {
        "label": label,
        "replicas": replicas,
        "jobs": jobs,
        "completed": len(terminals),
        "all_completed": bool(all_done and len(terminals) == jobs),
        "duplicate_terminals": dup_terminals,
        "qps": qps,
        "makespan_s": round(makespan_s, 2),
        "service_ms_per_row": service_ms,
        "failovers_total": sum(r.failovers for r in pool.replicas),
        "per_replica": {
            r.name: {
                "state": r.state,
                "jobs_served": r.engine.jobs_served,
                "qps": (round(r.engine.jobs_served / makespan_s, 2)
                        if makespan_s > 0 else 0.0),
                "batches": r.engine.batches,
                "failovers": r.failovers,
                "param_loads": r.engine.loads,
            } for r in pool.replicas
        },
    }
    if swap_report is not None:
        report["swap"] = {
            "replicas_swapped":
                [r["name"] for r in swap_report["replicas"]],
            "min_ready_seen": swap_report["min_ready_seen"],
            "total_s": swap_report["total_s"],
            # Zero-downtime verdict: every submitted job still reached a
            # terminal state despite the mid-burst drain/load/ready walk.
            "requests_lost": jobs - len(terminals),
        }
    if kill_info is not None:
        report["kill"] = kill_info
    return report


def run_pool_soak(args) -> int:
    """The replica-pool soak: baseline burst, scaled burst with a rolling
    swap mid-burst, and (``--kill-replica``) a seeded chaos burst."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    baseline = _pool_burst(args.jobs, 1, seed=args.seed,
                           label="baseline-1x")
    pool_run = _pool_burst(args.jobs, args.replicas, seed=args.seed,
                           swap=True, label=f"pool-{args.replicas}x")
    ratio = (round(pool_run["qps"] / baseline["qps"], 2)
             if baseline["qps"] else None)
    checks = {
        "pool_all_completed": pool_run["all_completed"],
        "pool_exactly_one_terminal":
            not pool_run["duplicate_terminals"],
        "swap_zero_requests_lost":
            pool_run["swap"]["requests_lost"] == 0,
        "swap_never_zero_ready": pool_run["swap"]["min_ready_seen"] >= 1,
    }
    if args.replicas >= 2:
        checks["scaling_at_least_1_5x"] = (ratio is not None
                                           and ratio >= 1.5)
    report = {
        "metric": "serve_soak_pool_qps",
        "value": pool_run["qps"],
        "unit": "jobs/s",
        "baseline_qps": baseline["qps"],
        "qps_ratio_vs_1_replica": ratio,
        "phases": {"baseline": baseline, "pool": pool_run},
        "backend": "dryrun",
    }
    if args.kill_replica:
        chaos = _pool_burst(args.jobs, args.replicas, seed=args.seed,
                            kill=True,
                            label=f"kill-{args.replicas}x")
        report["phases"]["kill"] = chaos
        dead_s = chaos["kill"]["dead_visible_s"]
        cadence = chaos["kill"]["sampler_cadence_s"]
        checks.update({
            "kill_all_completed": chaos["all_completed"],
            "kill_exactly_one_terminal":
                not chaos["duplicate_terminals"],
            "kill_no_double_execution":
                not chaos["duplicate_terminals"],
            "kill_failover_happened": chaos["failovers_total"] >= 1,
            # One sampler cadence, plus scheduling slack for the 1-core
            # box (discovery is usually instant via dispatch failure).
            "kill_dead_in_healthz_within_cadence":
                dead_s is not None and dead_s <= cadence + 0.5,
        })
    report["checks"] = checks
    verdict = all(checks.values())
    return _finish(report, verdict, args.out)


# ----------------------------------------------------- duplicate-traffic soak
def _is_terminal_frame(frame: dict) -> bool:
    """A submit's terminal frames, by shape: a result payload, a dead-letter
    error, or a deadline push. Progress text ('Running…', the completion
    banner) and requeued notices are not terminal."""
    return bool("result" in frame or "error" in frame
                or frame.get("deadline_exceeded")
                or frame.get("dead_letter"))


def run_zipf_soak(args) -> int:
    """The duplicate-traffic soak (``--zipf``): cache, coalescing, QoS.

    Real production VQA traffic is zipf-shaped — a few hot
    (image, question) pairs dominate. This soak phase-separates that shape
    so every assertion is deterministic rather than sampled:

    1. **coalesce** — with the worker parked, N identical submits from N
       sockets: exactly 1 leads (``cache: miss``), N-1 attach
       (``cache: coalesced``). The worker then drains ONE forward and every
       socket must receive exactly one terminal frame. ``--chaos`` kills
       the leader instead (seeded ``worker.intake`` fault plan → the job
       dead-letters) and the same exactly-one-terminal bar applies.
    2. **forward** — W distinct submits measure the real queue→forward→push
       path: ``forward_qps``.
    3. **hit** — the same W submits again: every response must return the
       stored result inline (``cache: hit``, no queue, no forward), and
       ``hit_qps >= 10 x forward_qps``.
    4. **swap** — a rolling checkpoint swap bumps the model generation;
       re-submitting a warmed request must be a MISS (stale results never
       survive a swap).

    Engines are dryrun stubs (GIL-releasing sleep per row): the subject is
    the dedup planes, not the forward.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")

    from vilbert_multitask_tpu.resilience import (
        FaultPlan,
        FaultRule,
        clear_plan,
        install_plan,
    )
    from vilbert_multitask_tpu.serve.app import ServeApp

    root = tempfile.mkdtemp(prefix="serve_soak_zipf_")
    # Unequal weights so the burst exercises the deficit tier's real math
    # (equal weights degenerate to round-robin).
    cfg = _build_cfg(root, False,
                     tenant_weights={"gold": 3.0, "bronze": 1.0})
    # 40 ms/row puts the uncached path near 25 jobs/s — far enough below
    # the sqlite+HTTP hit ceiling (~300+ jobs/s) that the 10x gate has
    # real headroom on a loaded CI box, while still finishing fast.
    eng = DryrunEngine(cfg, "r0", service_ms_per_row=40.0)
    app = ServeApp(cfg, engine=[eng])
    # Worker parked: the coalesce phase needs the leader still in flight
    # while the duplicates arrive, so attach-vs-hit is deterministic.
    app.start(worker=False)
    conn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                      timeout=30)

    def _post(body: dict) -> dict:
        conn.request("POST", "/", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        payload = resp.read()
        assert resp.status == 200, payload
        return json.loads(payload)

    def _tenant(i: int) -> str:
        return "gold" if i % 2 == 0 else "bronze"

    # -- phase 1: coalesce (worker off → every duplicate must attach) -----
    n_co = max(2, min(16, args.jobs // 6))
    co_subs = [app.hub.subscribe(f"zipf-co-{i}") for i in range(n_co)]
    co_markers = []
    for i in range(n_co):
        r = _post({"task_id": 1, "socket_id": f"zipf-co-{i}",
                   "question": "which landmarks appear in this scene",
                   "image_list": ["img_0.jpg"], "tenant": _tenant(i)})
        co_markers.append(r.get("cache"))
    co_misses = co_markers.count("miss")
    co_attached = co_markers.count("coalesced")

    plan = None
    if args.chaos:
        # Kill the leader through the real retry path: every intake claim
        # faults, so the one queued job burns its attempts and
        # dead-letters — the fan-out must still close EVERY follower.
        plan = install_plan(FaultPlan(args.seed, [
            FaultRule("worker.intake", "error", rate=1.0,
                      max_injections=32),
        ]))

    wstop = threading.Event()
    wthread = threading.Thread(
        target=app.worker.run_forever,
        kwargs={"poll_interval_s": 0.02, "stop_event": wstop},
        daemon=True, name="zipf-worker")
    wthread.start()

    def _await_terminal(sub, timeout_s: float = 60.0):
        """First terminal frame on ``sub`` plus how many EXTRA terminals
        land in a grace window after it (the exactly-one bar)."""
        first, extras = None, 0
        deadline_t = time.perf_counter() + timeout_s
        while time.perf_counter() < deadline_t:
            try:
                frame = sub.get(timeout=0.1)
            except queue_mod.Empty:
                if first is not None:
                    break  # grace window drained dry
                continue
            if not _is_terminal_frame(frame):
                continue
            if first is None:
                first = frame
                # A duplicate terminal would ride the same fan loop as the
                # first — half a second of silence clears the socket.
                deadline_t = min(deadline_t,
                                 time.perf_counter() + 0.5)
            else:
                extras += 1
        return first, extras

    co_terminals = [_await_terminal(sub) for sub in co_subs]
    co_closed = sum(1 for first, _ in co_terminals if first is not None)
    co_dupes = sum(extras for _, extras in co_terminals)
    co_states = sorted({("result" if "result" in (f or {}) else "error")
                        for f, _ in co_terminals if f is not None})
    if plan is not None:
        clear_plan()  # one leader assassinated; later phases run clean

    # -- phase 2: forward (distinct submits = the uncached baseline) ------
    n_fwd = max(8, args.jobs // 2)
    fwd_sub = app.hub.subscribe("zipf-fwd")
    fwd_bodies = [{"task_id": 1, "socket_id": "zipf-fwd",
                   "question": f"what is in frame {i}",
                   "image_list": [f"img_{i % 4}.jpg"],
                   "tenant": _tenant(i)} for i in range(n_fwd)]
    fwd_markers = []
    t0 = time.perf_counter()
    for body in fwd_bodies:
        fwd_markers.append(_post(body).get("cache"))
    fwd_done, t_last = 0, t0
    while fwd_done < n_fwd:
        try:
            frame = fwd_sub.get(timeout=60)
        except queue_mod.Empty:
            break
        if "result" in frame:
            fwd_done += 1
            t_last = time.perf_counter()
    forward_qps = round(fwd_done / max(t_last - t0, 1e-9), 2)

    # -- phase 3: hit (same submits again → inline results, no queue) -----
    hit_ok = 0
    t0 = time.perf_counter()
    for body in fwd_bodies:
        r = _post(dict(body, socket_id="zipf-hit"))
        if r.get("cache") == "hit" and "result" in r:
            hit_ok += 1
    hit_qps = round(n_fwd / max(time.perf_counter() - t0, 1e-9), 2)

    # -- phase 4: swap → generation bump → warmed entries all stale -------
    swap_report = app.rolling_swap(params={"zipf": "v2"})
    post_swap = _post(dict(fwd_bodies[0], socket_id="zipf-swap"))

    cost_attrib = {"enabled": app.attrib is not None}
    if app.attrib is not None:
        cons = app.attrib.conservation()
        cost_attrib.update(busy_s=cons["busy_s"],
                           attributed_s=cons["attributed_s"],
                           device_s_conservation=cons["ratio"])
    wstop.set()
    wthread.join(timeout=30)
    app.stop()

    coalesce_ratio = (round(n_co / co_misses, 2) if co_misses else None)
    checks = {
        # Worker was parked, so attach-vs-hit has no race: exactly one
        # leader, everyone else coalesced onto it.
        "coalesce_one_leader": co_misses == 1,
        "coalesce_all_attached": co_attached == n_co - 1,
        "coalesce_collapses_to_one_forward":
            coalesce_ratio is not None and coalesce_ratio > 1,
        "coalesce_exactly_one_terminal_per_submit":
            co_closed == n_co and co_dupes == 0,
        "forward_all_missed": fwd_markers.count("miss") == n_fwd,
        "hit_all_inline": hit_ok == n_fwd,
        "hit_qps_at_least_10x_forward": hit_qps >= 10 * forward_qps,
        "swap_invalidated_entries":
            swap_report.get("cache_invalidated", 0) > 0,
        "post_swap_submit_is_miss": post_swap.get("cache") == "miss",
        # Hits and followers charge only their push wall — never a device
        # share — so the double-entry ledgers must agree EXACTLY.
        "device_s_conservation_exact":
            (not cost_attrib["enabled"]
             or cost_attrib["device_s_conservation"] == 1.0),
    }
    report = {
        "metric": "serve_soak_zipf",
        "value": hit_qps,
        "unit": "jobs/s",
        "hit_qps": hit_qps,
        "forward_qps": forward_qps,
        "coalesce_ratio": coalesce_ratio,
        "hit_speedup": (round(hit_qps / forward_qps, 1)
                        if forward_qps else None),
        "coalesce": {
            "submits": n_co,
            "leaders": co_misses,
            "attached": co_attached,
            "closed": co_closed,
            "duplicate_terminals": co_dupes,
            "terminal_kinds": co_states,
        },
        "forward_jobs": n_fwd,
        "swap": {"cache_invalidated": swap_report.get("cache_invalidated"),
                 "post_swap_marker": post_swap.get("cache")},
        "cost_attrib": cost_attrib,
        "tenant_weights": {"gold": 3.0, "bronze": 1.0},
        "backend": "dryrun",
        "checks": checks,
    }
    if args.chaos:
        report["chaos"] = {
            "seed": args.seed,
            "injections": plan.injections() if plan is not None else {},
            # Under the intake kill the leader cannot produce a result:
            # every socket's terminal must be the dead-letter error fan.
            "leader_dead_lettered": co_states == ["error"],
        }
        checks["chaos_leader_dead_lettered"] = co_states == ["error"]
    verdict = all(checks.values())
    return _finish(report, verdict, args.out)


# ----------------------------------------------------- autoscale soak
# The promptness bar: capacity must exist within one warm AOT-cache
# replica boot (taken as 2.6 s; not measured on the chip) of the
# sustained-breach decision.
_AOT_BOOT_BAR_S = 2.6


def run_autoscale_soak(args) -> int:
    """The closed-loop autoscaler soak (``--autoscale``): a diurnal +
    flash-crowd load shape against dryrun replicas.

    Phases: **ramp** (gentle trickle — the pool must stay at one
    replica), **spike** (a flash crowd floods the queue — queue-wait p95
    breaches the target band, the controller must grow the pool within
    one AOT-boot latency of the sustained-breach decision, and nothing
    with deadline slack may shed), **trough** (traffic stops — sustained
    slack must retire capacity back down to ``autoscale_min_replicas``).
    Every submitted job must reach EXACTLY ONE terminal frame across all
    three phases, and ``GET /debug/autoscale`` must replay the decision
    history with inputs/thresholds/cooldown attached.

    ``--chaos`` runs the poison-storm variant instead: a seeded
    ``worker.intake`` fault plan dead-letters every job while slow claims
    pile queue wait above the breach band — the classic trap where load
    signals scream "scale out" but the work is poison. The controller
    must hold (``poison_storm`` decisions), never add a replica, and the
    dead-letter fan must still close every socket exactly once.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")

    from vilbert_multitask_tpu.resilience import (
        FaultPlan,
        FaultRule,
        clear_plan,
        install_plan,
    )
    from vilbert_multitask_tpu.obs import percentile
    from vilbert_multitask_tpu.serve.app import ServeApp
    from vilbert_multitask_tpu.serve.autoscale import (
        ACTION_SCALE_IN,
        ACTION_SCALE_OUT,
    )

    service_ms = 40.0
    overrides = dict(
        autoscale_enabled=True,
        autoscale_min_replicas=1,
        autoscale_max_replicas=3,
        # 150 ms target, band 75..180 ms: the ramp trickle sits far below,
        # the spike backlog sits seconds above — both classifications are
        # deterministic, not sampled.
        autoscale_target_queue_wait_p95_ms=150.0,
        autoscale_band_high=1.2,
        autoscale_band_low=0.5,
        autoscale_breach_ticks=2,
        autoscale_slack_ticks=4,
        autoscale_cooldown_out_s=1.0,
        autoscale_cooldown_in_s=1.5,
        autoscale_window_s=4.0,
        autoscale_max_poison_rate_per_s=0.5,
        # The whole run is ~150 ticks at the 0.25 s cadence; the ring must
        # hold ALL of them so the scale-out record can't roll off before
        # the trough-phase assertions read it back.
        autoscale_decision_history=1024,
        slo_fast_window_s=5.0,
        slo_slow_window_s=15.0,
    )
    root = tempfile.mkdtemp(prefix="serve_soak_autoscale_")
    cfg = _build_cfg(root, False, extra_serving=overrides)
    eng = DryrunEngine(cfg, "r0", service_ms_per_row=service_ms)
    app = ServeApp(cfg, engine=[eng],
                   engine_factory=lambda: DryrunEngine(
                       cfg, None, service_ms_per_row=service_ms))
    app.start()
    pool = app.engine
    sock = "autoscale"
    sub = app.hub.subscribe(sock)
    terminals: dict = {}
    dup_terminals: list = []
    lock = threading.Lock()
    stop_consume = threading.Event()

    def consume():
        while not stop_consume.is_set():
            try:
                frame = sub.get(timeout=0.2)
            except queue_mod.Empty:
                continue
            if not _is_terminal_frame(frame):
                continue
            if "result" in frame:
                q, kind = frame["result"]["question"], "result"
            else:
                q = frame.get("question", "")
                kind = ("deadline" if frame.get("deadline_exceeded")
                        else "error")
            with lock:
                if q in terminals:
                    dup_terminals.append(q)
                else:
                    terminals[q] = (time.perf_counter(), kind)

    reader = threading.Thread(target=consume, daemon=True,
                              name="autoscale-consume")
    reader.start()

    conn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                      timeout=30)
    submit_t: dict = {}

    def post(phase: str, i: int) -> str:
        task_id, q_t, n_img = PATTERN[i % len(PATTERN)]
        q = q_t.format(i=f"{phase}-{i}")
        body = json.dumps({
            "task_id": task_id, "socket_id": sock, "question": q,
            "image_list": [f"img_{k}.jpg" for k in range(n_img)],
        })
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        resp.read()
        submit_t[q] = time.perf_counter()
        return q

    def live_count() -> int:
        return sum(1 for r in pool.replicas_info()
                   if r["state"] != "dead")

    def decisions(action=None, reason=None):
        ds = app.autoscaler.decisions_list()
        if action is not None:
            ds = [d for d in ds if d["action"] == action]
        if reason is not None:
            ds = [d for d in ds if d["reason"] == reason]
        return ds

    if args.chaos:
        # ---- poison-storm variant: loud signals, poisoned work --------
        n_jobs = 24
        plan = install_plan(FaultPlan(args.seed, [
            # Every intake attempt faults → every job burns its delivery
            # attempts and dead-letters (bounded: attempts × jobs, plus
            # margin — the site only fires with a claimed job in hand).
            FaultRule("worker.intake", "error", rate=1.0,
                      max_injections=3 * n_jobs + 16),
            # Slow claims pile queue wait above the breach band while the
            # storm runs: the load signal SCREAMS scale-out; only the
            # poison gate stands between the controller and feeding a
            # flapping pool.
            FaultRule("queue.claim", "delay", rate=1.0, delay_s=0.05),
        ]))
        max_live = 1
        try:
            for i in range(n_jobs):
                post("storm", i)
            deadline_t = time.perf_counter() + 90.0
            while time.perf_counter() < deadline_t:
                max_live = max(max_live, live_count())
                with lock:
                    done = len(terminals)
                if done >= n_jobs:
                    break
                time.sleep(0.05)
            # A few more control ticks with the poison window still hot:
            # the hold decisions the variant exists to witness.
            settle_t = time.perf_counter() + 1.5
            while time.perf_counter() < settle_t:
                max_live = max(max_live, live_count())
                time.sleep(0.05)
        finally:
            clear_plan()
        with lock:
            kinds = sorted({k for _, k in terminals.values()})
            closed = len(terminals)
        poison_holds = decisions(reason="poison_storm")
        scale_outs = decisions(action=ACTION_SCALE_OUT)
        injections = plan.injections()
        stop_consume.set()
        reader.join(timeout=5)
        app.stop()
        checks = {
            "chaos_all_terminal": closed == n_jobs,
            "chaos_exactly_one_terminal": not dup_terminals,
            "chaos_all_dead_lettered": kinds == ["error"],
            # THE bar: breach-shaped signals + poisoned work → hold.
            "chaos_never_scaled_out": not scale_outs and max_live == 1,
            "chaos_poison_gate_fired": len(poison_holds) >= 1,
        }
        report = {
            "metric": "serve_soak_autoscale",
            "jobs": n_jobs,
            "completed": closed,
            "terminal_kinds": kinds,
            "max_live_replicas": max_live,
            "poison_hold_decisions": len(poison_holds),
            "max_poison_rate_per_s": round(max(
                (d["inputs"]["poison_rate_per_s"]
                 for d in app.autoscaler.decisions_list()), default=0.0), 2),
            "chaos": {"seed": args.seed, "injections": injections},
            "backend": "dryrun",
            "checks": checks,
        }
        verdict = all(checks.values())
        return _finish(report, verdict, args.out)

    # ---- phase 1: ramp (trickle below the band — no scale motion) -------
    n_ramp = 8
    for i in range(n_ramp):
        post("ramp", i)
        time.sleep(0.12)
    ramp_live = live_count()
    ramp_scale_outs = len(decisions(action=ACTION_SCALE_OUT))

    # ---- phase 2: spike (flash crowd — must grow within the boot bar) ---
    n_spike = 60
    spike_qs = []
    t_spike = time.perf_counter()
    for i in range(n_spike):
        spike_qs.append(post("spike", i))
    total = n_ramp + n_spike
    t_live2 = None
    max_live = 1
    deadline_t = time.perf_counter() + 90.0
    while time.perf_counter() < deadline_t:
        lv = live_count()
        max_live = max(max_live, lv)
        if t_live2 is None and lv >= 2:
            t_live2 = time.perf_counter()
        with lock:
            done = len(terminals)
        if done >= total and t_live2 is not None:
            break
        if done >= total and time.perf_counter() - t_spike > 20.0:
            break  # drained without ever scaling: let the checks fail it
        time.sleep(0.02)
    with lock:
        all_done = len(terminals) == total
        spike_lat_ms = [(terminals[q][0] - submit_t[q]) * 1e3
                        for q in spike_qs if q in terminals]
        shed_kinds = sorted({k for _, k in terminals.values()
                             if k != "result"})
    spike_p95_ms = percentile(spike_lat_ms, 0.95)
    time_to_scale_out_s = (round(t_live2 - t_spike, 3)
                           if t_live2 is not None else None)
    scale_outs = decisions(action=ACTION_SCALE_OUT)
    first_boot_s = None
    if scale_outs:
        first_boot_s = (scale_outs[0].get("actuated") or {}).get("boot_s")

    # ---- phase 3: trough (traffic stops — retire back down to min) ------
    t_trough = time.perf_counter()
    final_live = live_count()
    while time.perf_counter() - t_trough < 30.0:
        final_live = live_count()
        if final_live <= 1:
            break
        time.sleep(0.05)
    trough_s = round(time.perf_counter() - t_trough, 2)
    scale_ins = decisions(action=ACTION_SCALE_IN)

    hconn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                       timeout=10)
    hconn.request("GET", "/healthz")
    health = json.loads(hconn.getresponse().read())
    hconn.request("GET", "/debug/autoscale?limit=200")
    debug = json.loads(hconn.getresponse().read())
    hconn.close()
    stop_consume.set()
    reader.join(timeout=5)
    app.stop()

    last_decisions = debug.get("decisions") or []
    record_ok = bool(last_decisions) and all(
        k in last_decisions[-1]
        for k in ("t", "action", "reason", "inputs", "thresholds",
                  "cooldown"))
    checks = {
        "all_completed": all_done,
        "exactly_one_terminal": not dup_terminals,
        "no_scale_out_during_ramp": ramp_live == 1
        and ramp_scale_outs == 0,
        "scaled_out_under_spike": max_live >= 2 and len(scale_outs) >= 1,
        # Capacity within one AOT-boot latency of the sustained-breach
        # decision (actuation is inline with the decision tick, so the
        # add_replica wall IS that latency).
        "scale_out_within_aot_boot": first_boot_s is not None
        and first_boot_s <= _AOT_BOOT_BAR_S,
        "spike_to_capacity_bounded": time_to_scale_out_s is not None
        and time_to_scale_out_s <= 10.0,
        # Every terminal in the whole run is a result frame: nothing with
        # deadline slack was shed while the pool was reshaping.
        "no_sheds_during_scale_out": shed_kinds == [],
        "scaled_in_at_trough": final_live == 1 and len(scale_ins) >= 1,
        "healthz_reports_target_and_actual":
            "pool_target_replicas" in health
            and "pool_ready_replicas" in health,
        "target_tracks_actual_at_rest":
            health.get("pool_target_replicas")
            == health.get("pool_ready_replicas") == 1,
        "debug_endpoint_serves_decisions":
            bool(debug.get("enabled")) and record_ok,
    }
    report = {
        "metric": "serve_soak_autoscale",
        "value": time_to_scale_out_s,
        "unit": "s",
        "jobs": total,
        "completed": len(terminals),
        "autoscale": {
            "time_to_scale_out_s": time_to_scale_out_s,
            "spike_p95_ms": (round(spike_p95_ms, 1)
                             if spike_p95_ms is not None else None),
        },
        "phases": {
            "ramp": {"jobs": n_ramp, "live_replicas": ramp_live},
            "spike": {"jobs": n_spike, "max_live_replicas": max_live,
                      "first_boot_s": first_boot_s,
                      "scale_out_decisions": len(scale_outs)},
            "trough": {"final_live_replicas": final_live,
                       "scale_in_decisions": len(scale_ins),
                       "settle_s": trough_s},
        },
        "decision_ring": len(last_decisions),
        "aot_boot_bar_s": _AOT_BOOT_BAR_S,
        "backend": "dryrun",
        "checks": checks,
    }
    verdict = all(checks.values())
    return _finish(report, verdict, args.out)


# Mixed burst: single-image tasks, an NLVR2 pair, and a retrieval set —
# the ragged backlog shape run_many's chunk packing exists for.
PATTERN = [
    (1, "what is in image number {i}", 1),
    (15, "is the bowl right of the mug {i}", 1),
    (13, "two dogs play in the snow {i}", 1),
    (12, "both images contain wolves {i}", 2),
    (7, "a dog catching a frisbee {i}", 4),
]


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--jobs", type=int, default=96)
    p.add_argument("--out", default=None,
                   help="also write the report to this file")
    p.add_argument("--full", action="store_true",
                   help="serving-size model on whatever backend jax picks")
    p.add_argument("--chaos", action="store_true",
                   help="run under a seeded FaultPlan (remote worker mode) "
                        "and assert exactly-one-terminal-state per job")
    p.add_argument("--seed", type=int, default=0,
                   help="FaultPlan / chaos schedule seed (same seed → same "
                        "schedule, same kill victim)")
    p.add_argument("--replicas", type=int, default=1,
                   help="replica-pool size; >1 switches to the pool soak "
                        "(dryrun stub engines)")
    p.add_argument("--dryrun", action="store_true",
                   help="pool soak with stub engines (GIL-releasing sleep "
                        "per row) — measures the serving planes, no model")
    p.add_argument("--kill-replica", action="store_true",
                   help="pool soak: add a seeded chaos burst that kills "
                        "one replica mid-burst and asserts failover "
                        "invariants")
    p.add_argument("--zipf", action="store_true",
                   help="duplicate-traffic soak: result-cache hits, "
                        "in-flight coalescing, swap invalidation, and the "
                        "tenant-weighted scheduler under a hot-key burst; "
                        "--chaos kills the coalesced leader and asserts "
                        "every follower still gets exactly one terminal")
    p.add_argument("--autoscale", action="store_true",
                   help="closed-loop autoscaler soak: ramp → flash-crowd "
                        "spike → trough against dryrun replicas; asserts "
                        "the pool grows within one AOT-boot latency of "
                        "sustained breach, nothing sheds during "
                        "scale-out, and capacity retires at the trough; "
                        "--chaos runs the poison-storm variant (the "
                        "controller must hold, never scale out)")
    p.add_argument("--kill-thread", action="store_true",
                   help="kill one scheduler intake thread mid-burst via a "
                        "one-shot queue.claim fault; asserts /healthz "
                        "turns unready within a sampler cadence, the "
                        "thread_died bundle lands, and the surviving "
                        "threads still drain every job to exactly one "
                        "terminal")
    args = p.parse_args(argv)
    assert not (args.chaos and args.kill_thread), \
        "--kill-thread drains through the in-process scheduler; --chaos " \
        "drains through a remote worker — pick one"

    if args.autoscale:
        # Autoscale mode is dryrun by definition: the subject is the
        # control loop and the pool actuators, not the forward.
        return run_autoscale_soak(args)
    if args.zipf:
        # Duplicate-traffic mode is dryrun by definition too: hit/attach
        # semantics are host-side, the forward is a stub service time.
        return run_zipf_soak(args)
    if args.dryrun or args.replicas > 1 or args.kill_replica:
        # Pool mode is dryrun by definition: replica scaling on a shared
        # host only measures the dispatch plane with stub service times.
        return run_pool_soak(args)

    if not args.full:
        import jax

        jax.config.update("jax_platforms", "cpu")

    # The browser transport when available; otherwise read frames straight
    # off the in-process PushHub subscription (the ws bridge only forwards
    # hub traffic, so the frames — and the terminal classification — are
    # identical). No hard dep: the container may lack the client lib.
    try:
        from websockets.sync.client import connect
    except ImportError:
        connect = None

    from vilbert_multitask_tpu.obs import (
        BATCH_FILL,
        BATCHES_DISPATCHED,
        DEADLINE_SLACK,
        Histogram,
        QUEUE_WAIT,
        SHED_COUNTER,
        percentile,
        watchdog,
    )
    from vilbert_multitask_tpu.resilience import clear_plan, install_plan
    from vilbert_multitask_tpu.serve.app import ServeApp

    root = tempfile.mkdtemp(prefix="serve_soak_")
    cfg = _build_cfg(root, args.full)
    feat = _make_features(root, cfg.model.v_feature_size)
    t0 = time.perf_counter()
    app = ServeApp(cfg, feature_root=feat)
    app.warm()
    # Chaos mode drains through a remote-mode worker so transport faults
    # hit the real retry/breaker path; the in-process worker stays off.
    app.start(worker=not args.chaos)
    boot_s = time.perf_counter() - t0
    print(f"# boot {boot_s:.1f}s: {app.boot_info}", file=sys.stderr)

    plan = None
    wstop = threading.Event()
    wthread = None
    worker = app.worker
    if args.chaos:
        # Installed AFTER warm/boot: chaos targets steady-state serving,
        # not compilation.
        plan = install_plan(_chaos_plan(args.seed))
        worker = _chaos_worker(app)
        wthread = threading.Thread(
            target=worker.run_forever,
            kwargs={"poll_interval_s": 0.05, "stop_event": wstop},
            daemon=True, name="chaos-worker")
        wthread.start()

    sock = "soak-sock"
    arrivals: dict = {}       # question → result-frame arrival stamp
    terminals: dict = {}      # question → first terminal state
    dup_terminals: list = []  # (question, second_state) — must stay empty
    done = threading.Event()

    def _classify(frame):
        """A job's terminal states, by frame shape: result payload,
        dead-letter error, or deadline-exceeded. Progress frames
        ('Running…', 'completed in…', requeued notices) return None."""
        if "result" in frame:
            return "result", frame["result"]["question"]
        if frame.get("deadline_exceeded"):
            return "deadline", frame.get("question", "")
        if "error" in frame:
            return "dead", frame.get("question", "")
        return None

    def _consume(recv):
        while len(terminals) < args.jobs:
            frame = recv()
            state_q = _classify(frame)
            if state_q is None:
                continue
            state, q = state_q
            if state == "result":
                # Question text round-trips through the pipeline
                # lowercased; the embedded index makes each job's
                # result attributable for per-job latency.
                arrivals[q] = time.perf_counter()
            if q in terminals:
                dup_terminals.append((q, state))
            else:
                terminals[q] = state

    def ws_reader():
        # done fires on ANY exit — a dropped frame or an error-only job
        # must degrade to a partial report with real timestamps, not leave
        # main() blocked on the full wait while makespan inflates.
        try:
            if connect is not None:
                with connect(
                        f"ws://127.0.0.1:{app.ws.bound_port}/chat/") as ws:
                    ws.send(sock)
                    ready.set()
                    _consume(lambda: json.loads(ws.recv(timeout=120)))
            else:
                sub = app.hub.subscribe(sock)
                ready.set()
                _consume(lambda: sub.get(timeout=120))
        except (TimeoutError, queue_mod.Empty):
            pass  # recv window expired: report whatever arrived (partial)
        finally:
            done.set()

    ready = threading.Event()
    reader = threading.Thread(target=ws_reader, daemon=True)
    reader.start()
    assert ready.wait(timeout=30), "websocket never connected"

    conn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                      timeout=30)
    submitted: dict = {}
    trace_by_q: dict = {}  # question → trace_id (the attribution key)
    t_burst = time.perf_counter()
    t_kill = None
    for i in range(args.jobs):
        if args.kill_thread and plan is None and i == max(1, args.jobs // 2):
            # Mid-burst assassination: the next intake claim anywhere
            # dies. Installed between submits so jobs are in flight on
            # both sides of the death.
            plan = install_plan(_threadkill_plan(args.seed))
            t_kill = time.perf_counter()
        task_id, q_t, n_img = PATTERN[i % len(PATTERN)]
        q = q_t.format(i=i)
        body = json.dumps({
            "task_id": task_id, "socket_id": sock, "question": q,
            "image_list": [f"img_{k}.jpg" for k in range(n_img)],
        })
        # Submit time is captured BEFORE the request goes out: e2e latency
        # must include HTTP handling + durable-queue publish, and a fast
        # worker could otherwise deliver the result frame before the stamp
        # existed, yielding a negative latency sample (ADVICE r5).
        t_submit = time.perf_counter()
        conn.request("POST", "/", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200, resp.read()
        trace_by_q[q.lower()] = json.loads(resp.read()).get("trace_id", "")
        submitted[q.lower()] = t_submit

    tk_detect: dict = {}
    if args.kill_thread:
        # Detection race: crash_guard files the death synchronously with
        # the injected claim, so /healthz must flip 503 with the dead
        # thread named well inside one sampler cadence. Poll on a fresh
        # connection (the main one is reserved for /debug/slo later).
        cadence = cfg.serving.sampler_cadence_s
        hconn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                           timeout=5)
        deadline_t = t_kill + cadence + 2.0  # poll past the bar; gate below
        while time.perf_counter() < deadline_t:
            hconn.request("GET", "/healthz")
            r = hconn.getresponse()
            body = json.loads(r.read())
            dead = (body.get("threads") or {}).get("dead") or {}
            if r.status == 503 and dead:
                tk_detect = {
                    "detect_s": round(time.perf_counter() - t_kill, 3),
                    "dead": dead,
                    "reason": body.get("reason"),
                }
                break
            time.sleep(0.01)
        hconn.close()
        clear_plan()  # one-shot already spent; teardown stays fault-free

    ok = done.wait(timeout=600)
    if args.chaos:
        # Teardown must not be injected: drain verification and app.stop()
        # run fault-free.
        clear_plan()
        wstop.set()
        if wthread is not None:
            wthread.join(timeout=30)
    # The SLO verdict is read off the live endpoint BEFORE the drain — the
    # same JSON an operator's probe would see while the burst was served.
    try:
        conn.request("GET", "/debug/slo")
        body = json.loads(conn.getresponse().read())
        slo_verdict = {
            "worst": body.get("worst"),
            "states": {r["slo"]: r["state"] for r in body.get("slos", [])},
        }
    except Exception as e:  # degraded report beats a crashed soak
        slo_verdict = {"error": repr(e)}
    app.stop()

    # Same histogram + percentile code as serve/metrics — the
    # soak's numbers are computed the one shared way.
    e2e = Histogram("soak_e2e_ms", "Submit→result-frame latency (ms).")
    for q, t in submitted.items():
        if q in arrivals:
            e2e.observe((arrivals[q] - t) * 1e3)
    lat_ms = e2e.samples()
    n_done = len(lat_ms)
    # Throughput over the time results actually flowed: on a partial run
    # the wait timeout must not land in the denominator. The window opens
    # at the FIRST SUBMIT (t_burst), strictly after boot/warm/start — the
    # reported boot_s never leaks into serve_soak_qps, so soak numbers
    # stay comparable across rounds regardless of compile-time drift.
    makespan_s = ((max(arrivals.values()) - t_burst)
                  if arrivals else time.perf_counter() - t_burst)
    report = {
        "metric": "serve_soak_qps",
        "value": round(n_done / makespan_s, 2),
        "unit": "jobs/s",
        "jobs": args.jobs,
        "completed": n_done,
        "all_completed": bool(ok and n_done == args.jobs),
        "e2e_p50_ms": (round(percentile(lat_ms, 0.5), 1)
                       if lat_ms else None),
        "e2e_p95_ms": (round(percentile(lat_ms, 0.95), 1)
                       if lat_ms else None),
        "makespan_s": round(makespan_s, 2),
        "boot_s": round(boot_s, 1),
        "model": "full" if args.full else "tiny",
        "backend": __import__("jax").default_backend(),
        # Per-task request counts prove every family in the burst ran
        # (chaos mode drains through the scripted remote worker, so read
        # the metrics of whichever worker actually served).
        "tasks_served": sorted(
            int(k) for k in worker.metrics.snapshot()["by_task"]),
        "slo_verdict": slo_verdict,
    }
    # Deadline headroom under load: how much budget each claimed job had
    # left when the worker picked it up (worker.py observes this per claim).
    slack = DEADLINE_SLACK.all_samples()
    report["deadline_slack_ms_p50"] = (round(percentile(slack, 0.5), 1)
                                       if slack else None)
    report["deadline_slack_ms_p95"] = (round(percentile(slack, 0.95), 1)
                                       if slack else None)
    # Publish→claim delay: the scheduler latency Metrics.record's
    # intake-anchored e2e hides (stamped at POST /, observed at claim).
    qwait = QUEUE_WAIT.all_samples()
    report["queue_wait_ms_p50"] = (round(percentile(qwait, 0.5), 1)
                                   if qwait else None)
    report["queue_wait_ms_p95"] = (round(percentile(qwait, 0.95), 1)
                                   if qwait else None)
    # Continuous-batching scheduler verdict: how full the dispatched
    # chunks ran, how many device dispatches the burst cost, and how many
    # jobs were shed at their deadline before burning a forward.
    fills = BATCH_FILL.all_samples()
    report["scheduler"] = {
        "batch_fill_p50": (round(percentile(fills, 0.5), 3)
                           if fills else None),
        "batch_fill_p95": (round(percentile(fills, 0.95), 3)
                           if fills else None),
        "batches_dispatched": int(BATCHES_DISPATCHED.value()),
        "shed_expired": int(SHED_COUNTER.value(reason="deadline")),
    }
    # Cost-attribution verdict: the double-entry ledgers must agree — the
    # sum of per-job device shares stays within 10% of the engine busy
    # wall on a plain run (chaos legitimately strands shares on failed
    # batches, so there it is reported, not gated).
    cost_attrib = {"enabled": app.attrib is not None}
    if app.attrib is not None:
        cons = app.attrib.conservation()
        cost_attrib.update(
            busy_s=cons["busy_s"], attributed_s=cons["attributed_s"],
            device_s_conservation=cons["ratio"],
            tail_kept_frac=app.tracestore.stats()["tail_kept_frac"])
    report["cost_attrib"] = cost_attrib
    if args.chaos:
        state_counts: dict = {}
        for state in terminals.values():
            state_counts[state] = state_counts.get(state, 0) + 1
        no_job_lost = bool(ok and len(terminals) == args.jobs)
        exactly_one = not dup_terminals
        faulted = sorted(s for s, n in plan.injections().items() if n > 0)
        # Flight-recorder acceptance: app.stop() closed the recorder, so
        # every triggered bundle is flushed. At least one bundle must be a
        # fault_injected postmortem whose detail carries the fault's
        # trace_id AND whose captured span window contains that trace —
        # i.e. the recorder binds the incident to the request that hit it.
        bundles = app.recorder.bundles()
        fault_bundle = None
        trace_in_spans = False
        for path in bundles:
            try:
                with open(path) as f:
                    b = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue
            if b.get("event") != "fault_injected":
                continue
            tid = (b.get("detail") or {}).get("trace_id")
            if not tid:
                continue  # untraced site (e.g. the claim poll) — keep looking
            if tid in {s.get("trace_id") for s in b.get("spans", [])}:
                fault_bundle = os.path.basename(path)
                trace_in_spans = True
                break
        # Tail-sampling acceptance: every job that died (dead-letter or
        # deadline shed) is a non-ok verdict the store keeps at 100% —
        # each must be readable back as a stored trace for its autopsy.
        # app.stop() ran the final flush above, so the rows are on disk.
        unstored = []
        if app.tracestore is not None:
            for q, state in terminals.items():
                if state in ("dead", "deadline"):
                    tid = trace_by_q.get(q, "")
                    if not tid or app.tracestore.get(tid) is None:
                        unstored.append(q)
        failed_traces_stored = app.tracestore is not None and not unstored
        report["chaos"] = {
            "seed": args.seed,
            "injections": plan.injections(),
            "fault_calls": plan.calls(),
            "faulted_sites": faulted,
            "terminal_states": state_counts,
            "no_job_lost": no_job_lost,
            "exactly_one_terminal": exactly_one,
            "duplicates": dup_terminals,
            "failed_jobs_without_stored_trace": unstored,
            "flight_recorder": {
                "bundles": len(bundles),
                "fault_bundle": fault_bundle,
                "fault_trace_in_spans": trace_in_spans,
            },
        }
        # Chaos acceptance: faults actually fired at ≥3 sites, every
        # submit reached exactly one terminal state (result, dead-letter,
        # or deadline push) — dead-letters are an ACCEPTED outcome under
        # injected intake faults, so all_completed is not the bar here —
        # and the flight recorder captured an injected fault's trace.
        verdict = (no_job_lost and exactly_one and len(faulted) >= 3
                   and trace_in_spans and failed_traces_stored)
    elif args.kill_thread:
        # Thread-kill acceptance: exactly one intake thread died through
        # the guarded fault path, /healthz named it within one sampler
        # cadence (+0.5s poll slack), the flight recorder flushed its
        # thread_died bundle (app.stop() closed the recorder above), and
        # the surviving intake threads still drained every job to
        # exactly one terminal.
        dead_after = watchdog().dead_threads()
        intake_dead = sorted(n for n in dead_after
                             if n.startswith("sched-intake-"))
        tk_bundle = None
        for path in app.recorder.bundles():
            try:
                with open(path) as f:
                    b = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue
            if b.get("event") == "thread_died":
                tk_bundle = os.path.basename(path)
                break
        no_job_lost = bool(ok and len(terminals) == args.jobs)
        exactly_one = not dup_terminals
        detected = bool(
            tk_detect
            and tk_detect["detect_s"] <= cfg.serving.sampler_cadence_s + 0.5
            and any(n.startswith("sched-intake-") for n in tk_detect["dead"]))
        report["threadkill"] = {
            "seed": args.seed,
            "injections": plan.injections() if plan is not None else {},
            "sampler_cadence_s": cfg.serving.sampler_cadence_s,
            "detect_s": tk_detect.get("detect_s"),
            "healthz_reason": tk_detect.get("reason"),
            "dead_thread": ",".join(intake_dead),
            "dead_threads": dead_after,
            "thread_died_bundle": tk_bundle,
            "no_job_lost": no_job_lost,
            "exactly_one_terminal": exactly_one,
            "duplicates": dup_terminals,
        }
        verdict = (no_job_lost and exactly_one and detected
                   and len(intake_dead) == 1 and tk_bundle is not None)
    else:
        cons_ok = (not cost_attrib["enabled"]
                   or abs(cost_attrib["device_s_conservation"] - 1.0)
                   <= 0.10)
        verdict = report["all_completed"] and cons_ok
    return _finish(report, verdict, args.out)


if __name__ == "__main__":
    sys.exit(main())
