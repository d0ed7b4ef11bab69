"""obs/ unit tests: tracer semantics (nesting, cross-thread resumption,
ring eviction), the shared percentile, Prometheus exposition format, the
Chrome-trace schema, and the disabled-mode overhead guard."""

import json
import threading
import time

import pytest

from vilbert_multitask_tpu.obs import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    Tracer,
    chrome_trace,
    log_buckets,
    new_trace_id,
    percentile,
    render_prometheus,
)


# ------------------------------------------------------------------ tracer
def test_span_nesting_and_parenting():
    tr = Tracer()
    with tr.span("outer", task_id=4) as outer:
        with tr.span("inner"):
            pass
        with tr.span("inner2"):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"outer", "inner", "inner2"}
    assert spans["outer"].parent_id is None
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner2"].parent_id == spans["outer"].span_id
    # all three share the root's minted trace id
    assert {s.trace_id for s in spans.values()} == {spans["outer"].trace_id}
    assert spans["outer"].attrs == {"task_id": 4}
    assert spans["inner"].dur_s <= spans["outer"].dur_s


def test_sibling_roots_get_distinct_traces():
    tr = Tracer()
    with tr.span("a"):
        pass
    with tr.span("b"):
        pass
    a, b = tr.spans()
    assert a.trace_id != b.trace_id


def test_error_annotation():
    tr = Tracer()
    with pytest.raises(ValueError):
        with tr.span("boom"):
            raise ValueError("bad input")
    (s,) = tr.spans()
    assert s.attrs["error"] == "ValueError: bad input"


def test_cross_thread_trace_resumption():
    """The serve contract in miniature: a trace id minted on the 'HTTP'
    thread rides in a fake queue job body and is re-entered by a 'worker'
    thread — every span lands in ONE trace."""
    tr = Tracer()
    fake_queue = []

    trace_id = new_trace_id()
    with tr.trace(trace_id):
        with tr.span("http.submit"):
            fake_queue.append({"task_id": "1", "trace_id": trace_id})

    def worker():
        job = fake_queue.pop()
        with tr.trace(job["trace_id"]):
            with tr.span("worker.job"):
                with tr.span("engine.forward"):
                    pass

    t = threading.Thread(target=worker, name="worker-0")
    t.start()
    t.join()

    spans = {s.name: s for s in tr.spans()}
    assert set(spans) == {"http.submit", "worker.job", "engine.forward"}
    assert {s.trace_id for s in spans.values()} == {trace_id}
    # resumption adopts the id but not a cross-thread parent: the worker's
    # root is a root
    assert spans["worker.job"].parent_id is None
    assert spans["engine.forward"].parent_id == spans["worker.job"].span_id
    # and the scope is restored after exit
    assert tr.current_trace_id() is None


def test_ring_eviction_under_concurrent_writers():
    tr = Tracer(max_spans=64)
    n_threads, per_thread = 4, 100

    def writer(k):
        for i in range(per_thread):
            with tr.span(f"w{k}.{i}"):
                pass

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == 64  # bounded, newest kept
    assert tr.spans(limit=10) == spans[-10:]


def test_record_span_joins_given_trace():
    tr = Tracer()
    tr.record_span("worker.claim", 1.0, 0.25, trace_id="abc123", job_id=7)
    (s,) = tr.spans()
    assert (s.trace_id, s.dur_s, s.attrs["job_id"]) == ("abc123", 0.25, 7)


def test_disabled_mode_overhead_under_5us():
    """Tier-1 guard: instrumentation stays on prod paths because disabling
    the tracer makes span() effectively free."""
    tr = Tracer(enabled=False)
    n = 10_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with tr.span("hot", task_id=1):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, f"disabled span() costs {best * 1e6:.2f} us"
    assert tr.spans() == []


def test_observer_sees_spans_and_cannot_break_recording():
    tr = Tracer()
    seen = []
    tr.set_observer(lambda s: (seen.append(s.name),
                               1 / 0))  # observer raises every time
    with tr.span("a"):
        pass
    assert seen == ["a"]
    assert [s.name for s in tr.spans()] == ["a"]  # recording survived


# -------------------------------------------------------------- percentile
def test_percentile_linear_interpolation():
    assert percentile([], 0.5) is None
    assert percentile([7.0], 0.9) == 7.0
    # THE satellite bug: nearest-rank int(p*n) gave p50([1,2]) == 2
    assert percentile([1.0, 2.0], 0.5) == 1.5
    xs = list(range(1, 101))  # 1..100
    assert percentile(xs, 0.0) == 1
    assert percentile(xs, 1.0) == 100
    assert percentile(xs, 0.5) == 50.5
    assert abs(percentile(xs, 0.95) - 95.05) < 1e-9
    # order-independent
    assert percentile(list(reversed(xs)), 0.5) == 50.5


def test_metrics_snapshot_uses_shared_percentile():
    from vilbert_multitask_tpu.serve.metrics import Metrics

    m = Metrics()
    m.record(1, 1.0)
    m.record(1, 2.0)
    snap = m.snapshot()
    assert snap["latency_ms"]["p50"] == 1.5  # was 2.0 pre-fix
    assert snap["by_task"] == {"1": 2}
    m.record_failure(3)
    assert m.snapshot()["failures"] == {"3": 1}


# ------------------------------------------------------------- instruments
def test_counter_gauge_labels():
    c = Counter("jobs_total", labelnames=("state",))
    c.inc(state="ok")
    c.inc(2, state="ok")
    c.inc(state="err")
    assert c.value(state="ok") == 3.0
    g = Gauge("depth")
    g.set(7)
    assert g.value() == 7.0
    with pytest.raises(ValueError):
        c.inc(wrong_label="x")


def test_histogram_buckets_and_reservoir():
    h = Histogram("lat_ms", buckets=(1.0, 10.0, 100.0), reservoir=4)
    for v in (0.5, 5.0, 50.0, 500.0, 5000.0):
        h.observe(v)
    (series,) = h.collect().values()
    # cumulative counts per bound, +Inf last and equal to the total
    assert [c for _, c in series["buckets"]] == [1, 2, 3, 5]
    assert series["count"] == 5
    assert series["sum"] == pytest.approx(5555.5)
    # reservoir is bounded and keeps the newest
    assert h.samples() == [5.0, 50.0, 500.0, 5000.0]
    # boundary semantics match Prometheus le (inclusive upper bound)
    h2 = Histogram("edge", buckets=(1.0, 10.0))
    h2.observe(1.0)
    (s2,) = h2.collect().values()
    assert [c for _, c in s2["buckets"]] == [1, 1, 1]


def test_log_buckets_shape():
    bs = log_buckets()
    assert bs[0] == pytest.approx(0.1)
    assert bs[-1] >= 60_000.0
    assert all(b2 > b1 for b1, b2 in zip(bs, bs[1:]))


def test_registry_type_conflicts():
    reg = Registry()
    c = reg.counter("x_total")
    assert reg.counter("x_total") is c
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("a",))


# -------------------------------------------------------------- prometheus
def test_prometheus_exposition_format():
    reg = Registry()
    reg.counter("vmt_jobs_total", "Jobs.", labelnames=("state",)).inc(
        3, state="ok")
    reg.gauge("vmt_depth", "Depth.").set(2)
    h = reg.histogram("vmt_lat_ms", "Latency.", labelnames=("task",),
                      buckets=(1.0, 10.0))
    h.observe(0.5, task="1")
    h.observe(100.0, task="1")
    text = render_prometheus(reg)
    lines = text.splitlines()
    assert "# TYPE vmt_jobs_total counter" in lines
    assert "vmt_jobs_total{state=\"ok\"} 3" in lines
    assert "# TYPE vmt_depth gauge" in lines
    assert "vmt_depth 2" in lines
    assert "# TYPE vmt_lat_ms histogram" in lines
    # cumulative buckets end at +Inf == _count
    assert 'vmt_lat_ms_bucket{task="1",le="1"} 1' in lines
    assert 'vmt_lat_ms_bucket{task="1",le="10"} 1' in lines
    assert 'vmt_lat_ms_bucket{task="1",le="+Inf"} 2' in lines
    assert 'vmt_lat_ms_sum{task="1"} 100.5' in lines
    assert 'vmt_lat_ms_count{task="1"} 2' in lines
    # every non-comment line is `name{labels} value`
    for ln in lines:
        if not ln.startswith("#"):
            assert len(ln.rsplit(" ", 1)) == 2


def test_prometheus_label_escaping():
    reg = Registry()
    reg.counter("c_total", labelnames=("path",)).inc(
        path='a"b\\c\nnext')
    text = render_prometheus(reg)
    assert 'path="a\\"b\\\\c\\nnext"' in text


def test_prometheus_bucket_cumulativity_is_monotone():
    reg = Registry()
    h = reg.histogram("m_ms", buckets=(1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 3.0, 6.0, 100.0, 0.1, 7.0):
        h.observe(v)
    (series,) = h.collect().values()
    counts = [c for _, c in series["buckets"]]
    assert counts == sorted(counts)
    assert counts[-1] == series["count"]


# ------------------------------------------------------------ chrome trace
def test_chrome_trace_schema():
    tr = Tracer()
    with tr.trace("t" * 16):
        with tr.span("worker.job", task_id=4):
            with tr.span("engine.forward", bucket=8):
                pass
    doc = chrome_trace(tracer=tr)
    # must survive a JSON round trip (what /debug/trace serves)
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    ms = [e for e in events if e["ph"] == "M"]
    assert len(xs) == 2 and len(ms) == 1  # one thread -> one metadata event
    assert ms[0]["name"] == "thread_name"
    for e in xs:
        assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["args"]["trace_id"] == "t" * 16
    fwd = next(e for e in xs if e["name"] == "engine.forward")
    job = next(e for e in xs if e["name"] == "worker.job")
    assert fwd["args"]["parent_id"] == job["args"]["span_id"]
    # child nests inside the parent on the timeline
    assert fwd["ts"] >= job["ts"]
    assert fwd["ts"] + fwd["dur"] <= job["ts"] + job["dur"] + 1e-3


def test_chrome_trace_limit():
    tr = Tracer()
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    doc = chrome_trace(tracer=tr, limit=3)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["s7", "s8", "s9"]


# ------------------------------------------------- sliding windows (SLIs)
def test_histogram_window_percentile_ages_out():
    """Windowed aggregation is the SLI substrate: old samples must leave
    the window as the (injectable) clock advances — no sleeping."""
    h = Histogram("win_ms", reservoir=64)
    now = [1000.0]
    h.clock = lambda: now[0]
    for _ in range(10):
        h.observe(100.0)           # slow burst at t=1000
    now[0] += 30.0
    for _ in range(10):
        h.observe(1.0)             # fresh fast samples at t=1030
    assert h.window_count(60.0) == 20
    assert h.window_percentile(0.95, 60.0) == pytest.approx(100.0)
    # the slow burst ages past the 60 s window; only fresh samples remain
    now[0] += 45.0
    assert h.window_count(60.0) == 10
    assert h.window_percentile(0.95, 60.0) == pytest.approx(1.0)
    assert h.count() == 20         # the lifetime view is untouched
    assert h.window_sum(60.0) == pytest.approx(10.0)


def test_timeseries_store_ring_and_window():
    from vilbert_multitask_tpu.obs import TimeSeriesStore

    ts = TimeSeriesStore(points=4)
    for i in range(8):
        ts.record("qps", float(i), ts=float(i))
    # bounded ring: only the newest `points` samples survive
    assert ts.points("qps") == [(4.0, 4.0), (5.0, 5.0),
                                (6.0, 6.0), (7.0, 7.0)]
    assert ts.latest("qps") == 7.0
    ts.record_many({"a": 1.0, "b": 2.0}, ts=9.0)
    assert ts.names() == ["a", "b", "qps"]
    assert ts.snapshot()["a"] == [(9.0, 1.0)]


def test_sampler_tick_derives_rates_from_counters():
    from vilbert_multitask_tpu.obs import Sampler, TimeSeriesStore

    store = TimeSeriesStore()
    probe = {"sheds_total": 0.0, "depth": 3.0}
    samp = Sampler(store, lambda: dict(probe), cadence_s=60.0)
    first = samp.tick()
    assert "sheds_per_s" not in first      # no previous sample yet
    probe["sheds_total"] = 30.0
    second = samp.tick()
    assert second["sheds_per_s"] > 0.0     # delta / monotonic dt
    assert "depth_per_s" not in second     # only *_total keys derive rates
    assert "sheds_per_s" in store.names()


def test_sampler_thread_lifecycle_and_probe_errors():
    from vilbert_multitask_tpu.obs import Sampler, TimeSeriesStore

    calls = []

    def probe():
        calls.append(1)
        raise RuntimeError("flaky probe")

    samp = Sampler(TimeSeriesStore(), probe, cadence_s=0.01)
    samp.start()
    samp.start()                            # idempotent
    deadline = time.monotonic() + 5.0
    while not calls and time.monotonic() < deadline:
        time.sleep(0.01)
    samp.stop()
    assert calls                            # probe ran and errors were eaten
    assert not any(t.name == "obs-sampler" for t in threading.enumerate())


# ------------------------------------------------------------ burn rates
def test_slo_page_requires_both_windows_and_decays():
    """The acceptance property: states come from SLIDING windows — a burst
    of old slow samples outside the fast window must not hold a PAGE."""
    from vilbert_multitask_tpu.obs import SloEvaluator, latency_slo

    h = Histogram("slo_fixture_ms", reservoir=256)
    now = [5000.0]
    h.clock = lambda: now[0]
    ev = SloEvaluator([latency_slo("lat", h, 100.0, error_budget=0.05)],
                      fast_window_s=60.0, slow_window_s=600.0)
    # empty windows: burn 0, never a page
    assert ev.states() == {"lat": "ok"}
    # an all-bad burst saturates BOTH windows -> page
    for _ in range(20):
        h.observe(400.0)
    assert ev.states() == {"lat": "page"}
    # 2 minutes later the burst left the fast window: min(fast, slow)
    # gates paging, so the state decays even though slow burn is still hot
    now[0] += 120.0
    (report,) = ev.evaluate()
    assert report["state"] == "ok"
    assert report["burn"]["fast"] == 0.0
    assert report["burn"]["slow"] > 0.0


def test_availability_slo_counts_failures_in_window():
    from vilbert_multitask_tpu.obs import SloEvaluator, availability_slo

    ok_h = Histogram("avail_ok_ms", reservoir=64)
    fail_h = Histogram("avail_fail", reservoir=64)
    now = [100.0]
    ok_h.clock = fail_h.clock = lambda: now[0]
    ev = SloEvaluator(
        [availability_slo("avail", ok_h, fail_h, error_budget=0.02)],
        fast_window_s=60.0, slow_window_s=600.0)
    for _ in range(8):
        ok_h.observe(5.0)
    fail_h.observe(-1.0)
    fail_h.observe(-1.0)
    (report,) = ev.evaluate()
    # 2 failures / 10 events = 20% error rate over a 2% budget: burn 10
    assert report["burn"]["fast"] == pytest.approx(10.0)
    assert report["state"] == "page"


# --------------------------------------------------------- flight recorder
def test_recorder_bundle_binds_trace_and_rotates(tmp_path):
    from vilbert_multitask_tpu import obs

    rec = obs.FlightRecorder(str(tmp_path), max_bundles=2,
                             min_interval_s=0.0,
                             sources={"timeseries": lambda: {"qps": 1},
                                      "bad": lambda: 1 / 0})
    tid = obs.new_trace_id()
    with obs.trace_scope(tid), obs.span("unit.op"):
        pass
    assert rec.trigger("fault_injected", site="worker.intake",
                       trace_id=tid)
    rec.close()
    (path,) = rec.bundles()
    with open(path) as f:
        bundle = json.load(f)
    assert bundle["event"] == "fault_injected"
    assert bundle["detail"]["trace_id"] == tid
    assert tid in {s["trace_id"] for s in bundle["spans"]}
    assert tid in bundle["trace_ids"]
    assert bundle["timeseries"] == {"qps": 1}
    # a broken source loses its own section only, never the bundle
    assert "error" in bundle["bad"]
    # rotation: oldest bundles beyond max_bundles are removed
    rec2 = obs.FlightRecorder(str(tmp_path), max_bundles=2,
                              min_interval_s=0.0)
    for event in ("breaker_open", "drain", "worker_exception"):
        assert rec2.trigger(event)
        time.sleep(0.002)          # distinct ms -> distinct filenames
    rec2.close()
    assert len(rec2.bundles()) == 2
    assert not any(t.name == "flight-recorder"
                   for t in threading.enumerate())


def test_recorder_min_interval_rate_limits(tmp_path):
    from vilbert_multitask_tpu import obs

    rec = obs.FlightRecorder(str(tmp_path), min_interval_s=300.0)
    assert rec.trigger("breaker_open") is True
    assert rec.trigger("breaker_open") is False   # inside the interval
    assert rec.trigger("slo_page") is True        # per-event limiter
    rec.close()


def test_recorder_spike_fires_at_threshold(tmp_path):
    from vilbert_multitask_tpu import obs

    rec = obs.FlightRecorder(str(tmp_path), min_interval_s=0.0)
    fired = [rec.spike("deadline_spike", threshold=3, window_s=60.0)
             for _ in range(3)]
    assert fired == [False, False, True]
    # the window clears on fire: the count restarts
    assert rec.spike("deadline_spike", threshold=3, window_s=60.0) is False
    rec.close()


def test_record_event_routes_to_installed_recorder(tmp_path):
    from vilbert_multitask_tpu import obs

    rec = obs.install_recorder(
        obs.FlightRecorder(str(tmp_path), min_interval_s=0.0))
    try:
        assert obs.active_recorder() is rec
        assert obs.record_event("fault_injected", site="x") is True
    finally:
        obs.clear_recorder()
    assert obs.active_recorder() is None
    assert len(rec.bundles()) == 1
    # with no recorder installed the plane is inert
    assert obs.record_event("fault_injected", site="x") is False
    assert obs.record_spike("deadline_spike") is False


class TestRecorderOfAWiderFixture:
    """The thread check of ``tests/conftest.py`` blames a test for every
    project thread born while it ran. A recorder's writer starts lazily, at
    the first trigger, so a module's ``ServeApp`` whose sampler tick pages
    an SLO on a loaded host gives birth to one inside whichever test is
    running (``tests/test_generate_serve.py`` failed that way under six
    workers). A recorder installed before the test began is its fixture's
    to close, not the test's."""

    @pytest.fixture(scope="class")
    def installed(self, tmp_path_factory):
        from vilbert_multitask_tpu import obs

        rec = obs.install_recorder(obs.FlightRecorder(
            str(tmp_path_factory.mktemp("postmortem")), min_interval_s=0.0))
        yield rec
        obs.clear_recorder()
        rec.close()

    def test_its_writer_may_start_inside_a_test(self, installed):
        from vilbert_multitask_tpu import obs

        assert obs.record_event("slo_page", slo="e2e_latency") is True
        assert any(t.name == "flight-recorder"
                   for t in threading.enumerate())


def test_recorder_disabled_mode_overhead_under_5us():
    """Tier-1 guard (mirrors the tracer's): trigger sites live on prod
    paths because an uninstalled recorder costs a global read + compare."""
    from vilbert_multitask_tpu import obs

    assert obs.active_recorder() is None
    n = 10_000
    best_event = best_spike = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            obs.record_event("breaker_open", breaker="b")
        best_event = min(best_event, (time.perf_counter() - t0) / n)
        t0 = time.perf_counter()
        for _ in range(n):
            obs.record_spike("deadline_spike", trace_id="t")
        best_spike = min(best_spike, (time.perf_counter() - t0) / n)
    assert best_event < 5e-6, f"record_event costs {best_event * 1e6:.2f} us"
    assert best_spike < 5e-6, f"record_spike costs {best_spike * 1e6:.2f} us"
