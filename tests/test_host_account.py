"""The host's time by what each thread was doing (ISSUE 36): a span's CPU
seconds, the interpreter lock's wait as the probe reads it, the process's
CPU, and the dispatch thread's hand-overs and account. CPU; no timing here
is a measurement of the chip."""

import json
import os
import queue as stdlib_queue
import threading
import time
import types

import pytest

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.obs import Tracer
from vilbert_multitask_tpu.obs.timeseries import GIL_WAIT
from vilbert_multitask_tpu.serve.scheduler import ContinuousScheduler

NEW_METRICS = {
    # name: (reader file, kind, moves, workloads)
    "host_cpu_ms_per_row.saturated": (
        "host_cpu_ms_per_row", "counter_ratio", "rows_per_s",
        ["base.saturated"]),
    "gil_wait_p50_ms.saturated": (
        "gil_wait_p50_ms", "histogram_percentile", "rows_per_s",
        ["base.saturated"]),
    "gil_wait_p50_ms.interactive": (
        "gil_wait_p50_ms", "histogram_percentile", "latency_p50_ms",
        ["base.interactive"]),
    "dispatch_on_cpu_share.saturated": (
        "dispatch_on_cpu_share", "span_cpu_share", "rows_per_s",
        ["base.saturated"]),
    "intake_on_cpu_share.saturated": (
        "intake_on_cpu_share", "span_cpu_share", "rows_per_s",
        ["base.saturated"]),
    "submit_on_cpu_share.saturated": (
        "submit_on_cpu_share", "span_cpu_share", "rows_per_s",
        ["base.saturated"]),
    "ready_wait_p50_ms.saturated": (
        "ready_wait_p50_ms", "histogram_percentile", "rows_per_s",
        ["base.saturated"]),
    "ready_wait_p50_ms.interactive": (
        "ready_wait_p50_ms", "histogram_percentile", "latency_p50_ms",
        ["base.interactive"]),
    "completion_wait_p50_ms.interactive": (
        "completion_wait_p50_ms", "histogram_percentile", "latency_p50_ms",
        ["base.interactive"]),
    "dispatch_starved_share.saturated": (
        "dispatch_starved_share", "counter_rate", "rows_per_s",
        ["base.saturated"]),
    "dispatch_blocked_share.saturated": (
        "dispatch_blocked_share", "counter_rate", "rows_per_s",
        ["base.saturated"]),
}


# ------------------------------------------------------------ span CPU
def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_a_busy_span_reads_its_wall_time_on_the_core():
    tr = Tracer()
    with tr.span("busy"):
        _spin(0.1)
    (s,) = tr.spans()
    # A shared CI host may take the core away for part of it.
    assert 0.5 * s.dur_s <= s.cpu_s <= s.dur_s + 1e-3


def test_a_sleeping_span_reads_near_no_cpu():
    tr = Tracer()
    with tr.span("asleep"):
        time.sleep(0.1)
    (s,) = tr.spans()
    assert s.dur_s >= 0.1 and s.cpu_s < 0.01


def test_cpu_is_observed_per_name_and_not_for_after_the_fact_spans():
    tr = Tracer()
    tr.set_observer(obs._observe_span)
    hist = obs.SPAN_CPU_HISTOGRAM
    n0 = hist.count(name="acct.measured")
    m0 = hist.count(name="acct.recorded")
    with tr.span("acct.measured", task_id=3):
        _spin(0.01)
    tr.record_span("acct.recorded", time.perf_counter(), 0.02)
    assert hist.count(name="acct.measured") == n0 + 1
    assert hist.samples(name="acct.measured")[-1] > 0.0
    assert hist.count(name="acct.recorded") == m0
    assert [s.cpu_s for s in tr.spans()][-1] is None
    # The wall histogram still sees both.
    assert obs.SPAN_HISTOGRAM.count(name="acct.recorded", task="") >= 1


def test_the_span_histograms_hold_a_benchmark_window():
    """base.saturated makes about 47 of a name a second: a reservoir of
    2048 would keep 43 s of a 51 s window, and the two histograms a reader
    divides would then cover different spans."""
    for hist in (obs.SPAN_HISTOGRAM, obs.SPAN_CPU_HISTOGRAM, obs.READY_WAIT,
                 obs.COMPLETION_WAIT, GIL_WAIT):
        assert hist._reservoir == obs.WINDOW_RESERVOIR >= 51 * 80


# ------------------------------------------------------------- one clock
def test_spans_and_the_benchmark_marks_share_one_clock():
    """Spans stamp ``perf_counter``; ``run.py`` takes its profiler marks on
    ``monotonic`` and ``clock_offset`` aligns the two through them. A
    platform on which they differ would misplace every idle gap."""
    perf = time.get_clock_info("perf_counter")
    mono = time.get_clock_info("monotonic")
    assert perf.implementation == mono.implementation
    assert perf.monotonic and not perf.adjustable


# ------------------------------------------------------------ the probe
def _probe_samples(seconds: float) -> list:
    """The samples a probe of its own put into ``vmt_gil_wait_ms`` over
    ``seconds`` (other probes in this process only add samples like
    them)."""
    n0 = GIL_WAIT.count()
    probe = obs.GilProbe()
    probe.start()
    try:
        time.sleep(seconds)
    finally:
        probe.stop()
    new = GIL_WAIT.count() - n0
    return GIL_WAIT.samples()[-new:] if new else []


def test_the_probe_records_stops_and_leaves_no_thread():
    samples = _probe_samples(0.3)
    assert len(samples) >= 3 and all(v >= 0.0 for v in samples)
    assert not any(t.name == obs.GIL_PROBE_THREAD_NAME
                   for t in threading.enumerate())


def test_the_sampler_starts_and_stops_the_probe():
    samp = obs.Sampler(obs.TimeSeriesStore(), dict, cadence_s=10.0)
    samp.start()
    try:
        assert any(t.name == obs.GIL_PROBE_THREAD_NAME
                   for t in threading.enumerate())
    finally:
        samp.stop()
    assert not any(t.name in (obs.GIL_PROBE_THREAD_NAME,
                              obs.SAMPLER_THREAD_NAME)
                   for t in threading.enumerate())


def test_the_probe_reads_a_pure_python_thread_holding_the_lock():
    """Idle, the probe wakes about on time; beside a thread that runs
    Python without a pause it waits for the interpreter lock, up to the
    switch interval (5 ms) a wake-up. The margin asked is 5x."""
    idle = _probe_samples(0.6)
    stop = threading.Event()

    def busy():
        x = 0
        while not stop.is_set():
            x += 1

    t = threading.Thread(target=busy, name="Thread-busy", daemon=True)
    t.start()
    try:
        loaded = _probe_samples(0.6)
    finally:
        stop.set()
        t.join(5)
    assert not t.is_alive()
    assert len(idle) >= 8 and len(loaded) >= 8
    idle_p50 = obs.percentile(idle, 0.5)
    loaded_p50 = obs.percentile(loaded, 0.5)
    assert loaded_p50 >= 5 * max(idle_p50, 0.05), (idle_p50, loaded_p50)


# ---------------------------------------------------------- process CPU
def test_process_cpu_is_read_when_collected():
    c = obs.PROCESS_CPU_SECONDS
    assert c.kind == "counter"
    ((key, before),) = c.collect().items()
    assert key == ()
    _spin(0.05)
    after = c.value()
    assert after - before >= 0.02
    assert not hasattr(c, "inc")
    # What the benchmark's counters snapshot reads (run.py:counters_now).
    snap = {i.name: float(sum(i.collect().values()))
            for i in obs.REGISTRY.instruments() if i.kind == "counter"}
    assert snap["vmt_process_cpu_seconds_total"] >= after


# ------------------------------------------------ the scheduler's account
class _Prepared:
    n_images = 1
    spec = types.SimpleNamespace(task_id=1)


class _Engine:
    cfg = types.SimpleNamespace(engine=types.SimpleNamespace(
        max_batch_rows=lambda: 8, row_bucket_for=lambda rows: 8))

    def chunk_plan(self, counts):
        return [list(range(len(counts)))]

    def run_many(self, reqs, on_result=None):
        for pos in range(len(reqs)):
            on_result(pos, {"answer": pos})


class _SlowIntakeWorker:
    """Prepares a job in 50 ms, like an intake that reads and encodes."""

    serving = types.SimpleNamespace(
        sched_window_min_s=0.002, sched_window_max_s=0.05,
        tenant_weights=None, sched_ready_depth=64)

    def __init__(self):
        self.engine = _Engine()

    def _check_deadline(self, job):
        return False

    def _deadline_of(self, job):
        return None

    def _intake(self, job):
        time.sleep(0.05)
        return 1, _Prepared(), time.perf_counter()


def _job(trace_id):
    return types.SimpleNamespace(id=1, body={"trace_id": trace_id},
                                 more=None)


def test_ready_wait_leaves_out_the_intake_that_sched_wait_holds():
    attrib = obs.CostAttributor()
    prev = obs.get_attributor()
    obs.set_attributor(attrib)
    try:
        sched = ContinuousScheduler(_SlowIntakeWorker())
        attrib.begin("acct-ready")
        sched._park(_job("acct-ready"))
        batch, expired = [], []
        while not batch:
            batch, expired = sched._next_batch()
        sched._dispatch(batch)
    finally:
        obs.set_attributor(prev)
    sched_ms = obs.SCHED_WAIT.samples()[-1]
    ready_ms = obs.READY_WAIT.samples()[-1]
    assert sched_ms >= 50.0
    assert ready_ms < 40.0 and ready_ms <= sched_ms - 45.0
    # The attributor's ready_wait is the same wait: the intake is not in
    # it (the worker charges the intake), so a job's total counts it once.
    charged_ms = attrib.get("acct-ready").stages["ready_wait"]
    assert charged_ms < 40.0
    # The result went to the completion stage stamped with its put time.
    item, result, put_t = sched._completions.get_nowait()
    assert result == {"answer": 0} and put_t <= time.perf_counter()


def test_the_dispatch_thread_counts_starved_seconds():
    sched = ContinuousScheduler(_SlowIntakeWorker(), poll_interval_s=0.02)
    c0 = obs.DISPATCH_STARVED.value()
    threading.Timer(0.15, sched.stop.set).start()
    assert sched._next_batch() == ([], [])  # nothing ready until the stop
    assert obs.DISPATCH_STARVED.value() - c0 >= 0.1


def test_the_dispatch_thread_counts_blocked_seconds():
    sched = ContinuousScheduler(_SlowIntakeWorker())
    sched._completions = stdlib_queue.Queue(maxsize=1)
    item = object()
    sched._complete(item, "first")          # fits: not blocked
    c0 = obs.DISPATCH_BLOCKED.value()
    drained = []

    def drain():
        time.sleep(0.1)
        drained.append(sched._completions.get())

    t = threading.Thread(target=drain, name="Thread-drain")
    t.start()
    sched._complete(item, "second")         # blocks until the drain
    t.join(5)
    assert not t.is_alive() and drained[0][1] == "first"
    assert obs.DISPATCH_BLOCKED.value() - c0 >= 0.05
    c1 = obs.DISPATCH_BLOCKED.value()
    sched._completions.get_nowait()
    sched._complete(item, "third")          # room again: nothing counted
    assert obs.DISPATCH_BLOCKED.value() == c1


def test_the_completion_loop_observes_how_long_a_result_waited():
    sched = ContinuousScheduler(_SlowIntakeWorker())
    finished = []
    sched.worker.queue = types.SimpleNamespace(ack=finished.append)
    sched.worker._finish_job = lambda *a: None
    sched.worker._untrack = lambda job_id: None
    item = types.SimpleNamespace(job=_job("acct-done"), qa_id=1,
                                 prepared=_Prepared(), t0=0.0)
    n0 = obs.COMPLETION_WAIT.count()
    sched._complete(item, "r")
    time.sleep(0.03)
    sched._completions.put(None)
    sched._completion_pump()
    assert finished == [1]
    assert obs.COMPLETION_WAIT.count() == n0 + 1
    assert obs.COMPLETION_WAIT.samples()[-1] >= 25.0


# ----------------------------------------------------------- the readers
def _ctx(cpu, wall):
    return {"histograms": {"vmt_span_cpu_ms": cpu, "vmt_span_ms": wall}}


def test_span_cpu_share_divides_cpu_by_wall_over_every_task():
    from benchmark.reduce.kinds.span_cpu_share import read

    ctx = _ctx({("engine.dispatch",): [1.0, 2.0], ("other",): [9.0]},
               {("engine.dispatch", ""): [3.0, 3.0],
                ("engine.dispatch", "7"): [2.0], ("other", ""): [9.0]})
    assert read(ctx, span="engine.dispatch") == pytest.approx(37.5)
    assert read(ctx, span="other") == pytest.approx(100.0)


@pytest.mark.parametrize("ctx", [
    {"histograms": {}},                                 # the parent
    _ctx({}, {("engine.dispatch", ""): [3.0]}),         # no CPU samples
    _ctx({("engine.dispatch",): [1.0]}, {}),            # no wall samples
    _ctx({("engine.dispatch",): []}, {("engine.dispatch", ""): []}),
])
def test_span_cpu_share_is_none_where_there_is_nothing_to_read(ctx):
    from benchmark.reduce.kinds.span_cpu_share import read

    assert read(ctx, span="engine.dispatch") is None


def test_the_new_readers_read_nothing_from_a_parent_without_them():
    """The driver runs these readers on the parent too: without the
    instruments each returns None and the line leaves it out."""
    from benchmark.harness.spec import reader_file
    from benchmark.reduce import readers

    ctx = {"histograms": {}, "seconds": 51.0,
           "counters": {"before": {"vmt_input_cache_hits_total": 0.0},
                        "after": {"vmt_input_cache_hits_total": 10.0}}}
    for name in NEW_METRICS:
        with open(reader_file(name)) as f:
            assert readers.read(json.load(f), ctx) is None, name


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_every_new_metric_resolves_to_its_reader(name):
    from benchmark.harness.spec import ROOT, reader_file
    from benchmark.reduce import readers

    file, kind, moves, cells = NEW_METRICS[name]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        (entry,) = [m for m in json.load(f)["per_layer"]
                    if m["name"] == name]
    assert (entry["moves"], entry["workloads"]) == (moves, cells)
    path = reader_file(name)
    assert os.path.basename(path) == file + ".json"
    with open(path) as f:
        reader = json.load(f)
    assert reader["kind"] == kind and callable(readers.find_kind(kind))
    for instrument in [v for k, v in reader["params"].items()
                       if k in ("instrument", "counter")]:
        assert instrument in {i.name for i in obs.REGISTRY.instruments()}


# ------------------------------------------------------ the trace store
def test_the_store_copies_spans_only_for_a_trace_it_keeps(tmp_path):
    from vilbert_multitask_tpu.obs.attrib import JobCost
    from vilbert_multitask_tpu.obs.tracestore import TraceStore

    store = TraceStore(str(tmp_path / "t.sqlite3"), "w0", keep_top_k=1,
                       sample_rate=0.0)
    # The one slow slot taken by a slower job: an ordinary one is dropped.
    assert store.offer(JobCost(trace_id="slow", task="vqa", verdict="ok",
                               stages={"forward": 1e3})) == "slow"
    tr = Tracer()
    for tid in ("keep-me", "drop-me"):
        with tr.trace(tid), tr.span("worker.persist"):
            pass
    asked = []

    def spans_of(tid):
        def copy():
            asked.append(tid)
            return tr.spans_of(tid)
        return copy

    assert store.offer(JobCost(trace_id="drop-me", task="vqa",
                               verdict="ok"), spans_of("drop-me")) is None
    assert store.offer(JobCost(trace_id="keep-me", task="vqa",
                               verdict="error"),
                       spans_of("keep-me")) == "verdict"
    assert asked == ["keep-me"]
    store.flush()
    kept = store.get("keep-me")
    assert [s["trace_id"] for s in kept["spans"]] == ["keep-me"]
    assert store.get("drop-me") is None
