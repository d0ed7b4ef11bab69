"""The served path's own spans and counters (ISSUE 24): one request through
``ServeApp`` is one trace with the span tree ARCHITECTURE.md draws, and the
counters at the feature store, the device cache, the intake poll and the
scheduler's fire count what their names say. CPU, tiny model; no timing
here is a measurement."""

import dataclasses
import http.client
import json
import os
import shutil
import threading
import time

import numpy as np
import pytest

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.features.store import FeatureStore
from vilbert_multitask_tpu.obs import Tracer
from vilbert_multitask_tpu.obs.timeseries import GIL_WAIT
from vilbert_multitask_tpu.serve.scheduler import (
    ContinuousScheduler,
    ReadyItem,
)

# span -> the parent the table in ARCHITECTURE.md "Observability" names.
SPAN_PARENTS = {
    "http.request": None,
    "http.submit": "http.request",
    "http.admission": "http.submit",
    "cache.admit": "http.submit",
    "queue.publish": "http.submit",
    "cache.set_leader": "http.submit",
    "engine.features": "worker.intake",
    "engine.tokenize": "worker.intake",
    "engine.encode": "worker.intake",
    "engine.run_many": "worker.batch_forward",
    "engine.dispatch": "engine.run_many",
    "engine.slab_insert": "engine.dispatch",
    "engine.result_wait": "engine.run_many",
    "engine.decode": "engine.run_many",
}


@pytest.fixture(scope="module")
def fresh_engine(tiny_framework_cfg, features_dir):
    """An engine of this module's own: nothing is slab-resident yet, so the
    first request about an image is a miss and an insert."""
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine

    return InferenceEngine(tiny_framework_cfg,
                           feature_store=FeatureStore(features_dir))


@pytest.fixture(scope="module")
def served(tiny_framework_cfg, fresh_engine, features_dir, tmp_path_factory):
    """One request through a whole ``ServeApp`` (HTTP, queue, scheduler,
    engine, websocket push); what the tracer recorded meanwhile."""
    pytest.importorskip("websockets")
    from websockets.sync.client import connect

    from vilbert_multitask_tpu.serve.app import ServeApp

    root = tmp_path_factory.mktemp("served_path")
    cfg = dataclasses.replace(
        tiny_framework_cfg,
        engine=dataclasses.replace(tiny_framework_cfg.engine,
                                   aot_cache_dir=str(root / "aot")),
        serving=dataclasses.replace(
            tiny_framework_cfg.serving,
            queue_db_path=str(root / "q.sqlite3"),
            results_db_path=str(root / "r.sqlite3"),
            media_root=str(root / "media"), http_port=0, ws_port=0))
    app = ServeApp(cfg, engine=[fresh_engine], feature_root=features_dir)
    tracer = obs.default_tracer()
    rows_counted = (obs.INPUT_CACHE_HITS.value()
                    + obs.INPUT_CACHE_MISSES.value())
    probed = GIL_WAIT.count()
    app.start()
    try:
        with connect(f"ws://127.0.0.1:{app.ws.bound_port}/chat/") as ws:
            ws.send("sockSpan")
            deadline = time.monotonic() + 10
            while not app.hub.publish("sockSpan", {"info": "ready?"}):
                assert time.monotonic() < deadline, "socket never registered"
                time.sleep(0.02)
            tracer.clear()
            conn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                              timeout=10)
            conn.request("POST", "/", body=json.dumps({
                "task_id": 1, "socket_id": "sockSpan",
                "question": "what is this", "image_list": ["img_a.jpg"],
            }), headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 200, body
            frame = {}
            while "result" not in frame:
                frame = json.loads(ws.recv(timeout=60))
        # worker.push closes after the frame is on the wire.
        deadline = time.monotonic() + 10
        while not any(s.name == "worker.push" for s in tracer.spans()):
            assert time.monotonic() < deadline, "worker.push never closed"
            time.sleep(0.02)
    finally:
        app.stop()
    return {"trace_id": body["trace_id"], "job_id": body["job_id"],
            "spans": [s for s in tracer.spans()
                      if s.trace_id == body["trace_id"]],
            "rows_counted": (obs.INPUT_CACHE_HITS.value()
                             + obs.INPUT_CACHE_MISSES.value()
                             - rows_counted),
            "probed": GIL_WAIT.count() - probed,
            "threads_after_stop": {t.name for t in threading.enumerate()}}


@pytest.mark.parametrize("name", sorted(SPAN_PARENTS))
def test_served_request_span_appears_once_under_its_parent(served, name):
    by_name = {}
    for s in served["spans"]:
        by_name.setdefault(s.name, []).append(s)
    assert name in by_name, f"{name} not in {sorted(by_name)}"
    (span,) = by_name[name]  # once, in the request's own trace
    parent_name = SPAN_PARENTS[name]
    if parent_name is None:
        assert span.parent_id is None
        return
    (parent,) = by_name[parent_name]
    assert span.parent_id == parent.span_id
    assert parent.start_s <= span.start_s
    assert (span.start_s + span.dur_s
            <= parent.start_s + parent.dur_s + 1e-6)


def test_the_app_starts_and_stops_the_probe_with_its_sampler(served):
    assert served["probed"] >= 1
    assert not {obs.GIL_PROBE_THREAD_NAME, obs.SAMPLER_THREAD_NAME} \
        & served["threads_after_stop"]


def test_a_served_span_reads_its_cpu_and_the_rows_counted_are_dispatched(
        served):
    """``host_cpu_ms_per_row`` divides by hits + misses of the device
    cache: one a keyed row packed into a dispatch."""
    spans = {s.name: s for s in served["spans"]}
    for name in ("http.submit", "worker.intake", "engine.dispatch"):
        assert 0.0 <= spans[name].cpu_s <= spans[name].dur_s + 1e-3
    assert spans["worker.claim"].cpu_s is None   # recorded after the fact
    assert served["rows_counted"] == spans["engine.dispatch"].attrs["rows"]


def test_the_trace_id_is_minted_once_and_rides_in_the_job_body(served):
    by_name = {s.name: s for s in served["spans"]}
    # worker.claim is recorded under the id the claimed job's body carries:
    # it can only be in this trace if that is the root's id.
    assert by_name["worker.claim"].attrs["job_id"] == served["job_id"]
    assert by_name["queue.publish"].attrs["job_id"] == served["job_id"]
    assert by_name["http.request"].attrs["status"] == 200
    assert by_name["http.request"].attrs["bytes"] > 0
    assert by_name["http.admission"].attrs["admitted"] is True
    assert by_name["cache.admit"].attrs["verdict"] == "lead"
    assert {"worker.intake", "worker.persist", "worker.push",
            "worker.infer"} <= set(by_name)


def test_store_read_and_host_encode_are_two_spans_apart(served):
    by_name = {s.name: s for s in served["spans"]}
    read, encode = by_name["engine.features"], by_name["engine.encode"]
    # One name, one kind of work: the attribute that told two uses of
    # ``engine.features`` apart went with the second use.
    assert "source" not in read.attrs and "source" not in encode.attrs
    assert read.attrs["n_images"] == encode.attrs["n_images"] == 1
    # Nothing was on the device yet: the one row was read, none resident.
    assert (read.attrs["resident"], read.attrs["read"]) == (0, 1)
    assert read.start_s + read.dur_s <= encode.start_s
    dispatch = by_name["engine.dispatch"]
    assert (dispatch.attrs["rows"], dispatch.attrs["bucket"]) == (1, 1)
    assert (dispatch.attrs["hits"], dispatch.attrs["misses"]) == (0, 1)
    assert by_name["engine.result_wait"].attrs["rows"] == 1


def test_a_direct_submit_still_mints_its_own_id(stack):
    from vilbert_multitask_tpu.serve import ApiServer

    s, hub, q, store, worker = stack
    api = ApiServer(q, store, hub, s)
    code, body = api.submit_job({"task_id": 1, "socket_id": "x",
                                 "question": "q", "image_list": ["img_a.jpg"]})
    assert code == 200
    assert q.claim().body["trace_id"] == body["trace_id"]


# ------------------------------------------------------------ feature store
def _store_counters():
    return {name: getattr(obs, name).value() for name in (
        "FEATURE_STORE_HITS", "FEATURE_STORE_MISSES",
        "FEATURE_STORE_READ_BYTES", "FEATURE_STORE_LOAD_SECONDS")}


def test_feature_store_counts_a_read_a_repeat_and_an_eviction(
        features_dir, tmp_path):
    for name in ("img_a", "img_b"):
        shutil.copy(os.path.join(features_dir, name + ".npy"), tmp_path)
    size = {n: os.path.getsize(tmp_path / f"{n}.npy")
            for n in ("img_a", "img_b")}
    store = FeatureStore(str(tmp_path), max_cached=1)

    def delta(fn):
        before = _store_counters()
        fn()
        after = _store_counters()
        return {k: after[k] - before[k] for k in after}

    d = delta(lambda: store.get("img_a.jpg"))  # a read: the file is loaded
    assert (d["FEATURE_STORE_HITS"], d["FEATURE_STORE_MISSES"]) == (0, 1)
    assert d["FEATURE_STORE_READ_BYTES"] == size["img_a"]
    assert d["FEATURE_STORE_LOAD_SECONDS"] > 0
    d = delta(lambda: store.get("img_a.jpg"))  # a repeat: the host LRU
    assert d == {"FEATURE_STORE_HITS": 1, "FEATURE_STORE_MISSES": 0,
                 "FEATURE_STORE_READ_BYTES": 0,
                 "FEATURE_STORE_LOAD_SECONDS": 0}
    d = delta(lambda: (store.get("img_b.jpg"),   # evicts img_a (one slot)
                       store.get("img_a.jpg")))  # ...so this loads again
    assert (d["FEATURE_STORE_HITS"], d["FEATURE_STORE_MISSES"]) == (0, 2)
    assert d["FEATURE_STORE_READ_BYTES"] == size["img_a"] + size["img_b"]


# ------------------------------------------------------------- device cache
def test_input_cache_counters_equal_input_cache_stats(fresh_engine):
    from vilbert_multitask_tpu.features.pipeline import RegionFeatures

    eng = fresh_engine
    counters = (obs.INPUT_CACHE_HITS, obs.INPUT_CACHE_MISSES,
                obs.INPUT_CACHE_INSERTS)
    c0 = [c.value() for c in counters]
    s0 = eng.input_cache_stats
    keyed = eng.prepare_from_store(1, "what is this", ["img_b.jpg"])
    eng.run(keyed)       # a keyed miss (img_b was never served here)
    eng.run(keyed)       # a hit
    eng.run_many([keyed, keyed])  # two more hits through the batched path
    rng = np.random.default_rng(3)
    dim = eng.cfg.model.v_feature_size
    boxes = np.array([[1, 1, 20, 20], [5, 5, 40, 40]], np.float32)
    keyless = eng.prepare(1, "what is this", [RegionFeatures(
        rng.normal(size=(2, dim)).astype(np.float32), boxes, 64, 64)])
    assert keyless.cache_keys is None
    eng.run(keyless)     # keyless rows insert and are neither hit nor miss
    eng.run(keyless)
    hits, misses, inserts = (c.value() - b for c, b in zip(counters, c0))
    s1 = eng.input_cache_stats
    assert (hits, misses) == (s1["hits"] - s0["hits"],
                              s1["misses"] - s0["misses"]) == (3, 1)
    assert inserts == misses + 2


# ------------------------------------------- the intake's rows, by path
def test_the_features_span_and_the_counters_say_resident_or_read(
        fresh_engine, features_dir):
    """``engine.features`` carries how many of a request's rows the intake
    found on the device and how many it read, the three
    ``vmt_intake_rows_*`` counters count the same rows, and a request whose
    rows are all resident opens no ``engine.encode``. A resident row is
    neither a hit nor a miss of the store's host LRU."""
    eng = fresh_engine
    for name in ("img_a.jpg", "img_b.jpg"):  # on the device, whoever ran
        eng.predict(1, "what is this", [name])
    tracer = obs.default_tracer()
    names = ("INTAKE_ROWS_RESIDENT", "INTAKE_ROWS_READ", "INTAKE_ROWS_LATE")

    def prepared(paths):
        tracer.clear()
        before = [getattr(obs, n).value() for n in names]
        store = _store_counters()
        eng.prepare_from_store(7, "a caption", paths)
        rose = tuple(getattr(obs, n).value() - b
                     for n, b in zip(names, before))
        spans = {s.name: s for s in tracer.spans()}
        return rose, spans, {k: v - store[k]
                             for k, v in _store_counters().items()}

    rose, spans, store = prepared(["img_a.jpg", "img_b.jpg"])
    assert rose == (2, 0, 0)
    attrs = spans["engine.features"].attrs
    assert (attrs["n_images"], attrs["resident"], attrs["read"]) == (2, 2, 0)
    assert "engine.encode" not in spans and "engine.tokenize" in spans
    assert not any(store.values())

    copy = os.path.join(features_dir, "img_a_again.npy")
    shutil.copy(os.path.join(features_dir, "img_a.npy"), copy)
    try:  # a file the device has not seen, beside one it holds
        rose, spans, store = prepared(["img_a_again.jpg", "img_b.jpg"])
    finally:
        os.remove(copy)
    assert rose == (1, 1, 0)
    attrs = spans["engine.features"].attrs
    assert (attrs["resident"], attrs["read"]) == (1, 1)
    assert spans["engine.encode"].attrs["n_images"] == 1
    assert (store["FEATURE_STORE_HITS"]
            + store["FEATURE_STORE_MISSES"]) == 1


@pytest.mark.parametrize("name,cell,moves", [
    ("intake_resident_row_share.saturated", "base.saturated", "rows_per_s"),
    ("intake_resident_row_share.interactive", "base.interactive",
     "latency_p50_ms"),
])
def test_the_benchmark_reads_the_intake_counters(name, cell, moves):
    """Both ``per_layer`` entries resolve to the one reader file, and every
    counter it names is in the registry once the engine is imported: the
    harness leaves a metric out whose counter it cannot find."""
    from benchmark.harness.spec import ROOT, reader_file
    from benchmark.reduce import readers
    from vilbert_multitask_tpu.engine import runtime  # noqa: F401

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert (entry["workloads"], entry["moves"], entry["layer"],
            entry["source"], entry["better"], entry["unit"]) == (
        [cell], moves, "engine", "program_counter", "higher", "%")
    path = reader_file(name)
    assert os.path.basename(path) == "intake_resident_row_share.json"
    with open(path) as f:
        reader = json.load(f)
    assert readers.find_kind(reader["kind"]) is readers.counter_ratio
    counters = {i.name for i in obs.REGISTRY.instruments()
                if i.kind == "counter"}
    params = reader["params"]
    assert set(params["numerator"]) == {"vmt_intake_rows_resident_total"}
    assert set(params["denominator"]) == {
        "vmt_intake_rows_resident_total", "vmt_intake_rows_read_total",
        "vmt_intake_rows_late_total"} <= counters
    # 3 resident, 1 read, 0 late: 75%; nothing counted: left out.
    ctx = {"counters": {"before": dict.fromkeys(counters, 0.0),
                        "after": {**dict.fromkeys(counters, 0.0),
                                  "vmt_intake_rows_resident_total": 3.0,
                                  "vmt_intake_rows_read_total": 1.0}}}
    assert readers.counter_ratio(ctx, **params) == 75.0
    ctx["counters"]["after"] = ctx["counters"]["before"]
    assert readers.counter_ratio(ctx, **params) is None


# ------------------------------------------------------------ intake polls
class _CountingStop(threading.Event):
    """The scheduler's stop event with its clock taken out: ``wait`` never
    sleeps, counts the polls it was asked for and sets itself after
    ``polls`` of them."""

    def __init__(self, polls):
        super().__init__()
        self.polls, self.waits = polls, []

    def wait(self, timeout=None):
        self.waits.append(timeout)
        if len(self.waits) >= self.polls:
            self.set()
        return self.is_set()


class _SilentQueue:
    """A queue nothing is ever published to: ``wait_for_work`` sleeps its
    timeout out, notes it, and sets ``stop`` after ``polls`` waits."""

    def __init__(self, stop, polls):
        self.stop, self.polls, self.timeouts = stop, polls, []

    def work_seq(self):
        return 0

    def wait_for_work(self, seen_seq, timeout_s):
        self.timeouts.append(timeout_s)
        time.sleep(timeout_s)
        if len(self.timeouts) >= self.polls:
            self.stop.set()
        return False, seen_seq


class _IdleWorker:
    """A worker whose queue is empty; only what ``_intake_pump`` touches."""

    def __init__(self, worker, queue=None):
        self.serving, self.engine = worker.serving, worker.engine
        self.queue, self.claims = queue, 0

    def _claim(self):
        self.claims += 1
        return None


def _poll_counters():
    return (obs.INTAKE_EMPTY_POLLS.value(),
            obs.INTAKE_BACKPRESSURE_POLLS.value())


@pytest.mark.parametrize("signals", [True, False])
def test_an_empty_queue_counts_empty_polls_only(stack, signals):
    """Every empty claim is counted once and nothing else is. The wait
    after it is the queue's own, no longer than ``poll_interval_s``; on a
    queue that cannot signal (``serve/remote.py``) it is the sleep on the
    stop event that it always was."""
    stop = _CountingStop(polls=5)
    queue = _SilentQueue(stop, polls=5) if signals else object()
    worker = _IdleWorker(stack[4], queue)
    sched = ContinuousScheduler(worker, stop_event=stop,
                                poll_interval_s=0.05)
    empty0, back0 = _poll_counters()
    sched._intake_pump()
    empty1, back1 = _poll_counters()
    assert (empty1 - empty0, back1 - back0) == (worker.claims, 0)
    if signals:
        # One claim a wait: a wait ends when the timed claim is due.
        assert worker.claims == 5 and stop.waits == []
        assert len(queue.timeouts) == 5
        assert all(0.0 < t <= 0.05 for t in queue.timeouts)
    else:
        assert worker.claims == 5 and stop.waits == [0.05] * 5


def test_a_full_ready_queue_counts_backpressure_polls_only(stack):
    worker = _IdleWorker(stack[4])
    stop = _CountingStop(polls=4)
    sched = ContinuousScheduler(worker, stop_event=stop,
                                poll_interval_s=0.05)
    sched._ready.extend(
        ReadyItem(None, 1, None, 0.0, None, 0.0)
        for _ in range(worker.serving.sched_ready_depth))
    empty0, back0 = _poll_counters()
    sched._intake_pump()
    empty1, back1 = _poll_counters()
    assert (empty1 - empty0, back1 - back0) == (0, 4)
    assert worker.claims == 0  # it never reached the claim


class _Rows:
    """Stands in for PreparedRequest: only n_images matters to packing."""

    n_images = 1


def test_ready_jobs_are_observed_once_a_fire(stack):
    s, hub, q, store, worker = stack
    now = [100.0]
    sched = ContinuousScheduler(worker, clock=lambda: now[0])
    hist = obs.SCHED_READY_JOBS
    n0 = hist.count()
    for ready in (3, 2):
        sched._ready.extend(ReadyItem(None, 1, _Rows(), 0.0, None, now[0])
                            for _ in range(ready))
        now[0] += 1.0  # the oldest member waited out any window: a fire
        batch, expired = sched._next_batch()
        assert len(batch) == ready and not expired
        assert hist.samples()[-1] == float(ready)
    assert hist.count() == n0 + 2  # one observation a fire, none besides


# ----------------------------------------------------------------- overhead
def test_enabled_span_stays_under_40us():
    """The other side of the disabled-mode guard in test_obs.py: the served
    path opens about fifteen spans a request with the tracer ON (the
    benchmark never turns it off), so an enabled span with the
    ``vmt_span_ms`` observer attached has to stay cheap. 13 us measured on
    the CPU this was written on; the bar leaves a CI host three times
    that."""
    tr = Tracer()
    tr.set_observer(obs._observe_span)
    n = 5_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot", task_id=1):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 40e-6, f"enabled span() costs {best * 1e6:.2f} us"
    assert len(tr.spans()) == 4096  # the ring's bound held
