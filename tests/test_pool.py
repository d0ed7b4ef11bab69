"""Replica-pool tests: health-gated routing, breaker-driven degradation and
recovery, kill→failover with exactly-one-terminal, rolling checkpoint swaps
under live load, poison-job quarantine, and crash-recovery redelivery.

Most tests run against fake engines (the pool only needs the dispatch
surface: run/run_many/warmup/live_stats plus the ``killed`` flag contract
from engine/runtime.py); the failover and crash-recovery integration tests
wrap the shared tiny real engine so the full worker pipeline runs.
"""

import dataclasses
import threading
import time

import pytest

from vilbert_multitask_tpu.config import ServingConfig
from vilbert_multitask_tpu.resilience import ReplicaKilled
from vilbert_multitask_tpu.serve import (
    DurableQueue,
    NoReadyReplica,
    PushHub,
    ReplicaPool,
    ResultStore,
    ServeWorker,
    make_job_message,
)
from vilbert_multitask_tpu.serve.pool import (
    STATE_DEAD,
    STATE_DEGRADED,
    STATE_READY,
)


class FakeEngine:
    """The dispatch surface the pool programs against, nothing else."""

    def __init__(self, service_s=0.0, fail_with=None):
        self.killed = False
        self.service_s = service_s
        self.fail_with = fail_with  # exception instance raised per call
        self.calls = 0
        self.loads = 0

    def _dispatch(self):
        if self.killed:
            raise ReplicaKilled("replica killed (chaos)")
        if self.fail_with is not None:
            raise self.fail_with
        if self.service_s:
            time.sleep(self.service_s)  # GIL-releasing, like a device wait
        self.calls += 1

    def run(self, req, **kwargs):
        self._dispatch()
        return ("ok", req)

    def run_many(self, reqs, on_result=None, **kwargs):
        self._dispatch()
        return [("ok", r) for r in reqs]

    def warmup(self, buckets=None, parallel=None):
        pass

    def live_stats(self):
        return {"fake_calls": float(self.calls)}

    def load_params(self, params):
        self.loads += 1


def make_pool(n=2, serving=None, **serving_overrides):
    serving = serving or ServingConfig(**serving_overrides)
    pool = ReplicaPool([FakeEngine() for _ in range(n)], serving=serving)
    pool.mark_ready()
    return pool


# ------------------------------------------------------------------- boot
def test_warmup_raises_when_no_replica_comes_up():
    """One bad replica does not sink the boot; a pool in which NONE booted
    re-raises the warmup error (a compiler refusing a kernel, say) instead
    of starting a server that can answer nothing."""
    class Refused(FakeEngine):
        def warmup(self, buckets=None, parallel=None):
            raise RuntimeError("Mosaic rejected the kernel (simulated)")

    mixed = ReplicaPool([Refused(), FakeEngine()], serving=ServingConfig())
    mixed.warmup()
    assert [r.state for r in mixed.replicas] == [STATE_DEAD, STATE_READY]

    dead = ReplicaPool([Refused(), Refused()], serving=ServingConfig())
    with pytest.raises(RuntimeError, match="Mosaic rejected the kernel"):
        dead.warmup()
    assert all(r.state == STATE_DEAD for r in dead.replicas)


# ---------------------------------------------------------------- routing
def test_routing_skips_non_ready_replicas():
    pool = make_pool(3, pool_checkout_timeout_s=0.2)
    # r1 never becomes admissible while draining/booting-like.
    pool.replicas[1].state = "draining"
    names = set()
    for _ in range(6):
        rep = pool.checkout()
        names.add(rep.name)
        pool.checkin(rep, ok=True)
    assert names == {"r0", "r2"}


def test_checkout_is_least_loaded_and_caps_inflight():
    pool = make_pool(2, pool_max_inflight_per_replica=1,
                     pool_checkout_timeout_s=0.05)
    a = pool.checkout()
    b = pool.checkout()
    assert {a.name, b.name} == {"r0", "r1"}  # spread, not pile-up
    with pytest.raises(NoReadyReplica):  # both at the inflight cap
        pool.checkout(timeout_s=0.05)
    pool.checkin(a, ok=True)
    assert pool.checkout().name == a.name  # freed slot is admissible again
    pool.checkin(a, ok=True)
    pool.checkin(b, ok=True)


def test_checkout_times_out_when_nothing_ready():
    serving = ServingConfig()
    pool = ReplicaPool([FakeEngine()], serving=serving)  # still booting
    with pytest.raises(NoReadyReplica):
        pool.checkout(timeout_s=0.05)


# ------------------------------------------------- breaker-gated health
def test_breaker_open_degrades_then_half_open_probe_recovers():
    pool = make_pool(2, pool_breaker_failure_threshold=2,
                     pool_breaker_window_s=30.0,
                     pool_breaker_reset_timeout_s=0.05,
                     pool_checkout_timeout_s=0.5)
    flaky = pool.replicas[0]
    flaky.engine.fail_with = RuntimeError("transient device loss")
    # Drive failures onto r0 specifically (checkout is least-loaded, so
    # dispatching through run() could land either side).
    for _ in range(2):
        rep = pool.checkout()
        while rep.name != "r0":
            pool.checkin(rep, ok=True)
            rep = pool.checkout()
        pool.checkin(rep, ok=False, error=RuntimeError("boom"))
    assert flaky.state == STATE_DEGRADED
    assert flaky.breaker.state == "open"
    # While open, checkout never routes to the degraded replica.
    for _ in range(4):
        rep = pool.checkout()
        assert rep.name == "r1"
        pool.checkin(rep, ok=True)
    # After the reset timeout the breaker half-opens: the next checkout IS
    # the recovery probe, and its success flips the replica back to ready.
    flaky.engine.fail_with = None
    deadline = time.monotonic() + 2.0
    while flaky.breaker.state != "half_open":
        assert time.monotonic() < deadline, "breaker never half-opened"
        time.sleep(0.01)
    out = pool.run("probe-req")
    assert out[0] == "ok"
    assert flaky.state == STATE_READY
    assert flaky.breaker.state == "closed"


def test_kill_is_silent_until_dispatch_then_fails_over():
    """kill() must NOT un-route the replica — the next dispatch has to hit
    the corpse and fail over, like a real silent hardware loss."""
    from vilbert_multitask_tpu.serve.pool import ReplicaFailover

    pool = make_pool(2, pool_checkout_timeout_s=0.5)
    pool.kill("r0")
    assert pool.replicas[0].state == STATE_READY  # not discovered yet
    failovers = 0
    served = 0
    for i in range(6):
        try:
            pool.run(i)
            served += 1
        except ReplicaFailover as e:
            assert e.replica == "r0"
            failovers += 1
    assert failovers == 1  # exactly one dispatch died discovering the kill
    assert served == 5
    assert pool.replicas[0].state == STATE_DEAD
    assert pool.replicas[1].engine.calls == 5


def test_probe_discovers_kill_without_dispatch():
    pool = make_pool(2)
    pool.kill("r1")
    sample = pool.probe()
    assert pool.replicas[1].state == STATE_DEAD
    assert sample["replica_r1_state"] == 5.0
    assert sample["pool_dead_replicas"] == 1.0
    assert sample["pool_ready_replicas"] == 1.0
    # /healthz payload: the dead replica is visible per-replica.
    info = {r["name"]: r for r in pool.replicas_info()}
    assert info["r1"]["state"] == STATE_DEAD


# ----------------------------------------------------------- rolling swap
def test_rolling_swap_updates_all_replicas_never_zero_ready():
    pool = make_pool(2, pool_swap_drain_timeout_s=2.0)
    ready_during_load = []

    def load(engine):
        ready_during_load.append(pool.ready_count())
        engine.load_params({"v": 2})

    report = pool.rolling_swap(load)
    assert [r["name"] for r in report["replicas"]] == ["r0", "r1"]
    assert report["min_ready_seen"] >= 1
    assert all(n >= 1 for n in ready_during_load)
    assert all(r.engine.loads == 1 for r in pool.replicas)
    assert all(r.swaps == 1 for r in pool.replicas)
    assert pool.ready_count() == 2


def test_rolling_swap_skips_dead_replicas():
    pool = make_pool(3, pool_swap_drain_timeout_s=2.0,
                     pool_checkout_timeout_s=0.5)
    pool.kill("r1")
    pool.probe()  # discover the corpse
    report = pool.rolling_swap(lambda eng: eng.load_params({}))
    assert report["skipped"] == ["r1"]
    assert [r["name"] for r in report["replicas"]] == ["r0", "r2"]


def test_rolling_swap_under_live_load_loses_no_requests():
    """The acceptance invariant: swap while dispatches are in flight — every
    request completes (no NoReadyReplica, no failure) and at least one
    replica stays ready throughout."""
    pool = make_pool(2, serving=ServingConfig(
        pool_checkout_timeout_s=10.0, pool_swap_drain_timeout_s=10.0))
    for rep in pool.replicas:
        rep.engine.service_s = 0.002
    stop = threading.Event()
    outcomes = {"ok": 0, "errors": []}
    lock = threading.Lock()

    def hammer():
        while not stop.is_set():
            try:
                pool.run("req")
            except Exception as e:  # noqa: BLE001 — the assertion target
                with lock:
                    outcomes["errors"].append(repr(e))
                return
            with lock:
                outcomes["ok"] += 1

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.05)  # load established before the swap starts
    report = pool.rolling_swap(lambda eng: eng.load_params({"v": 2}))
    time.sleep(0.05)  # and keeps flowing after
    stop.set()
    for t in threads:
        t.join(timeout=5.0)
    assert outcomes["errors"] == []
    assert outcomes["ok"] > 0
    assert report["min_ready_seen"] >= 1
    assert all(r.swaps == 1 for r in pool.replicas)
    assert pool.ready_count() == 2


# ------------------------------------------------------ poison quarantine
def test_delivery_count_dead_letters_released_jobs(tmp_path):
    """release() charges no attempt — delivery_count must still bound a job
    that fails over forever (the reference's redeliver-forever loop)."""
    q = DurableQueue(str(tmp_path / "q.sqlite3"), max_deliveries=2)
    q.publish({"poison": True})
    for _ in range(2):
        job = q.claim()
        assert job is not None
        q.release(job.id)  # failover path: no attempt charged
    assert q.claim() is None  # quarantined despite attempts == 0
    dead = q.dead_jobs()
    assert len(dead) == 1 and dead[0].body == {"poison": True}
    assert dead[0].attempts == 0 and dead[0].deliveries == 2


def test_poison_quarantine_notifies_client_exactly_once(tmp_path):
    serving = ServingConfig()
    hub = PushHub()
    sub = hub.subscribe("sockP")
    q = DurableQueue(str(tmp_path / "q.sqlite3"), max_deliveries=1)
    store = ResultStore(str(tmp_path / "r.sqlite3"))
    worker_a = ServeWorker(FakeEngine(), q, store, hub, serving)
    worker_b = ServeWorker(FakeEngine(), q, store, hub, serving)
    q.publish(make_job_message(["img_a.jpg"], "poison?", 1, "sockP"))
    q.release(q.claim().id)  # one delivery burned via failover
    assert q.claim() is None  # sweep quarantines it
    # Both workers poll; the dead_notified column hands the terminal frame
    # to exactly one of them.
    worker_a._notify_dead_letters()
    worker_b._notify_dead_letters()
    frames = []
    while not sub.empty():
        frames.append(sub.get_nowait())
    dead_frames = [f for f in frames if f.get("dead_letter")]
    assert len(dead_frames) == 1
    assert "delivered 1 times" in dead_frames[0]["terminal"]
    assert dead_frames[0]["question"] == "poison?"


def test_abandon_inflight_stamps_replica_provenance(tmp_path):
    serving = ServingConfig()
    hub = PushHub()
    sub = hub.subscribe("sockD")
    q = DurableQueue(str(tmp_path / "q.sqlite3"))
    store = ResultStore(str(tmp_path / "r.sqlite3"))
    eng = FakeEngine()
    eng.replica_id = "r7"
    worker = ServeWorker(eng, q, store, hub, serving)
    q.publish(make_job_message(["img_a.jpg"], "q", 1, "sockD"))
    assert worker._claim() is not None
    assert worker.abandon_inflight() == 1
    frame = sub.get_nowait()
    assert frame["requeued"] is True
    assert frame["abandoned_by"] == "r7"
    # Released, not charged: the job is claimable again at attempt 1.
    again = q.claim()
    assert again is not None and again.attempts == 1


# ------------------------------------- integration: worker over the pool
class WrapEngine:
    """A killable replica that delegates real inference to the shared tiny
    engine — so the full worker pipeline (intake → batch forward → persist
    → push) runs while chaos stays per-replica."""

    def __init__(self, host, name):
        self._host = host
        self.replica_id = name
        self.killed = False
        self.cfg = host.cfg
        self.calls = 0

    def _gate(self):
        if self.killed:
            raise ReplicaKilled(f"replica {self.replica_id} killed (chaos)")

    def run(self, req, **kwargs):
        self._gate()
        self.calls += 1
        return self._host.run(req, **kwargs)

    def run_many(self, reqs, on_result=None, **kwargs):
        self._gate()
        self.calls += 1
        return self._host.run_many(reqs, on_result=on_result, **kwargs)

    def prepare(self, *args, **kwargs):
        return self._host.prepare(*args, **kwargs)

    def prepare_from_store(self, *args, **kwargs):
        return self._host.prepare_from_store(*args, **kwargs)

    def chunk_plan(self, *args, **kwargs):
        return self._host.chunk_plan(*args, **kwargs)

    def decode(self, *args, **kwargs):
        return self._host.decode(*args, **kwargs)

    def warmup(self, buckets=None, parallel=None):
        pass

    def live_stats(self):
        return {}

    @property
    def input_cache_stats(self):
        return self._host.input_cache_stats

    @property
    def mesh(self):
        return self._host.mesh


@pytest.fixture()
def pool_stack(tiny_framework_cfg, engine, tmp_path):
    s = dataclasses.replace(
        tiny_framework_cfg.serving,
        queue_db_path=str(tmp_path / "q.sqlite3"),
        results_db_path=str(tmp_path / "r.sqlite3"),
        media_root=str(tmp_path / "media"),
        pool_replicas=2,
        pool_checkout_timeout_s=2.0,
    )
    pool = ReplicaPool(
        [WrapEngine(engine, "r0"), WrapEngine(engine, "r1")], serving=s)
    pool.mark_ready()
    hub = PushHub()
    q = DurableQueue(s.queue_db_path,
                     max_delivery_attempts=s.max_delivery_attempts,
                     max_deliveries=s.queue_max_deliveries)
    store = ResultStore(s.results_db_path)
    worker = ServeWorker(pool, q, store, hub, s)
    return s, hub, q, store, worker, pool


def _drain_frames(sub):
    frames = []
    while not sub.empty():
        frames.append(sub.get_nowait())
    return frames


def test_replica_kill_fails_over_with_exactly_one_terminal(pool_stack):
    """The chaos acceptance path: a batch lands on a silently-killed
    replica, every member is released (no attempt charged), redelivery runs
    them on the survivor, and each job ends with exactly one result."""
    s, hub, q, store, worker, pool = pool_stack
    subs = {f"sock{i}": hub.subscribe(f"sock{i}") for i in range(2)}
    for i in range(2):
        q.publish(make_job_message(["img_a.jpg"], f"q{i}", 1, f"sock{i}"))
    pool.kill("r0")
    # Batches pin to one replica; least-loaded checkout sends the first
    # batch to the corpse → ReplicaFailover → release (attempt un-charged).
    deadline = time.monotonic() + 60.0
    while q.counts() and time.monotonic() < deadline:
        worker.step_batch()
    assert q.counts() == {}, "jobs left behind after failover"
    for name, sub in subs.items():
        frames = _drain_frames(sub)
        results = [f for f in frames if "result" in f]
        assert len(results) == 1, (name, frames)  # exactly-one-terminal
        requeued = [f for f in frames if f.get("requeued")]
        assert all(f["replica"] == "r0" for f in requeued)
    assert pool.replicas[0].state == STATE_DEAD
    assert pool.replicas[1].engine.calls >= 1
    assert pool.replicas[0].failovers >= 1
    # No delivery attempt was charged for the failed-over landing.
    info = {r["name"]: r for r in pool.replicas_info()}
    assert info["r0"]["failures"] >= 1


def test_crash_recovery_via_visibility_timeout(tiny_framework_cfg, engine,
                                               tmp_path):
    """Worker A claims mid-batch and dies before ack; the visibility
    timeout redelivers to worker B, which completes each job exactly
    once."""
    s = dataclasses.replace(
        tiny_framework_cfg.serving,
        queue_db_path=str(tmp_path / "q.sqlite3"),
        results_db_path=str(tmp_path / "r.sqlite3"),
        media_root=str(tmp_path / "media"),
    )
    hub = PushHub()
    sub = hub.subscribe("sockC")
    q = DurableQueue(s.queue_db_path, visibility_timeout_s=0.05)
    store = ResultStore(s.results_db_path)
    for i in range(2):
        q.publish(make_job_message(["img_a.jpg"], f"q{i}", 1, "sockC"))
    # Worker A: claims both jobs "mid-batch", then the process dies — no
    # ack, no nack, no release.
    assert q.claim() is not None
    assert q.claim() is not None
    assert q.claim() is None  # nothing deliverable while claims are live
    time.sleep(0.06)  # visibility timeout lapses
    worker_b = ServeWorker(engine, q, store, hub, s)
    deadline = time.monotonic() + 60.0
    while q.counts() and time.monotonic() < deadline:
        worker_b.step_batch()
    assert q.counts() == {}
    frames = _drain_frames(sub)
    results = [f for f in frames if "result" in f]
    assert len(results) == 2  # one terminal per job, despite redelivery
    questions = {f["result"]["question"] for f in results}
    assert questions == {"q0", "q1"}


# ------------------------------------------------- retire (scale-in path)
def test_retire_unnamed_picks_least_loaded_ready():
    pool = make_pool(3)
    # r1 is busiest, r2 has history; r0 is the cheapest to drain.
    pool.replicas[1].inflight = 2
    pool.replicas[2].dispatches = 5
    info = pool.retire_replica()
    assert info["name"] == "r0"
    assert [r.name for r in pool.replicas] == ["r1", "r2"]


def test_retire_withdraws_state_gauge_and_healthz_block():
    from vilbert_multitask_tpu import obs

    pool = make_pool(2)
    pool.probe()  # publish both series
    assert obs.REPLICA_STATE.value(replica="r1") is not None
    pool.retire_replica("r1")
    # No ghost replica: the gauge series is gone and stays gone through
    # the next probe sweep (which only walks surviving replicas).
    assert obs.REPLICA_STATE.value(replica="r1") is None
    pool.probe()
    assert obs.REPLICA_STATE.value(replica="r1") is None
    assert [r["name"] for r in pool.replicas_info()] == ["r0"]


def test_retire_refuses_below_min_replicas():
    pool = make_pool(2, autoscale_min_replicas=2)
    with pytest.raises(ValueError, match="autoscale_min_replicas"):
        pool.retire_replica()
    assert len(pool.replicas) == 2


def test_retire_refuses_last_ready_replica():
    pool = make_pool(2)
    pool.replicas[1].state = STATE_DEGRADED
    with pytest.raises(ValueError, match="last READY"):
        pool.retire_replica("r0")
    assert len(pool.replicas) == 2


def test_retire_waits_for_inflight_drain():
    pool = make_pool(2, pool_checkout_timeout_s=1.0)
    rep = pool.checkout()  # one dispatch in flight on some replica
    victim = rep.name
    done = []

    def finish():
        time.sleep(0.1)
        pool.checkin(rep, ok=True)
        done.append(True)

    threading.Thread(target=finish, daemon=True).start()
    info = pool.retire_replica(victim, drain_timeout_s=5.0)
    assert done  # the retire blocked until the in-flight call finished
    assert info["name"] == victim
    assert victim not in {r.name for r in pool.replicas}


def test_retire_drain_timeout_restores_replica():
    pool = make_pool(2)
    rep = pool.replicas[0]
    rep.inflight = 1  # a dispatch that never finishes
    with pytest.raises(TimeoutError):
        pool.retire_replica("r0", drain_timeout_s=0.1)
    # Abandoned retirement, not a stranded replica: back in rotation.
    assert rep.state == STATE_READY
    assert len(pool.replicas) == 2


def test_add_then_retire_roundtrip_keeps_pool_consistent():
    pool = make_pool(1)
    pool.add_replica(FakeEngine(), warm=True)
    assert pool.ready_count() == 2
    info = pool.retire_replica()
    assert pool.ready_count() == 1
    assert info["name"] not in {r.name for r in pool.replicas}


# ------------------------------- residency is every replica's (ISSUE 35)
@pytest.fixture(scope="module")
def two_engines(tiny_framework_cfg, features_dir, tmp_path_factory):
    """Two real tiny engines over one parameter tree and one store (a
    directory of this module's own: other modules rewrite img_a)."""
    import shutil

    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.features.store import FeatureStore

    root = tmp_path_factory.mktemp("pool_features")
    for name in ("img_a", "img_b"):
        shutil.copy(f"{features_dir}/{name}.npy", root)
    store = FeatureStore(str(root))
    r0 = InferenceEngine(tiny_framework_cfg, feature_store=store,
                         replica_id="r0")
    r1 = InferenceEngine(tiny_framework_cfg, params=r0.params,
                         feature_store=store, replica_id="r1")
    return r0, r1


def _evict(engine, key):
    """What an LRU eviction leaves behind, for one key (held or not)."""
    with engine._input_cache_lock:
        if key in engine._input_cache:
            engine._slab_free.append(engine._input_cache.pop(key))
            del engine._input_frames[key]
    assert engine.resident_frames([key]) == [None]


@pytest.mark.parametrize("case", ["one_holds", "one_holds_other_dead",
                                  "both_hold", "both_hold_then_one_evicts"])
def test_pool_calls_a_row_resident_only_if_every_live_replica_holds_it(
        two_engines, case):
    """``ReplicaPool.prepare_from_store`` prepares on replica 0 and the
    batch may land on either: a row only replica 0 holds is read (a late
    read must not become the rule), unless the other replica is dead; a
    row both hold is not; and should the replica a batch lands on have
    lost the row since, the pack reads it late and the answer stands."""
    from vilbert_multitask_tpu import obs

    r0, r1 = two_engines
    pool = ReplicaPool([r0, r1], serving=ServingConfig())
    pool.mark_ready()
    image = {"one_holds": "img_a.jpg", "one_holds_other_dead": "img_a.jpg",
             "both_hold": "img_b.jpg",
             "both_hold_then_one_evicts": "img_b.jpg"}[case]
    want = r0.predict(1, "what is this", [image])  # r0 holds the row now
    if case.startswith("both_hold"):
        assert r1.predict(1, "what is this", [image]) == want
    else:
        _evict(r1, r0.feature_store.identity(image))
    if case == "one_holds_other_dead":
        pool.replicas[1].state = STATE_DEAD
    req = pool.prepare_from_store(1, "what is this", [image])
    if case == "one_holds":
        assert req.frames is None and req.features is not None
    else:
        assert req.frames is not None and req.features is None
    late = obs.INTAKE_ROWS_LATE.value()
    if case == "both_hold_then_one_evicts":
        _evict(r1, req.cache_keys[0])
        assert r1.run(req)[1] == want
        assert obs.INTAKE_ROWS_LATE.value() == late + 1
    else:
        for rep in pool.replicas:
            if rep.state != STATE_DEAD:
                assert rep.engine.run(req)[1] == want
        assert obs.INTAKE_ROWS_LATE.value() == late
