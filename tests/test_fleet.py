"""Fleet observability: process identity, the shared metrics spine,
and cross-process trace stitching.

The two-OS-process tests are the contract the whole tentpole exists
for: a REAL second python process (subprocess, its own registry and
tracer) flushes into the same ``fleet.sqlite3``, and this process's
spine must merge it — both identities visible, counters summed, one
stitched Chrome-trace timeline — and must evict it once its heartbeat
goes stale after a SIGKILL (the crash case ``retire()`` never sees).
"""

import os
import signal
import subprocess
import sys
import time

import pytest

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.obs.fleet import FleetSpine, default_spine_path
from vilbert_multitask_tpu.obs.identity import (
    mint_identity,
    process_identity,
    reset_process_identity,
)
from vilbert_multitask_tpu.obs.instruments import Registry
from vilbert_multitask_tpu.obs.timeseries import TimeSeriesStore
from vilbert_multitask_tpu.obs.trace import Tracer
from vilbert_multitask_tpu.obs.tracestore import TraceStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ID = "feedface00000000"


# ------------------------------------------------------------------ identity
def test_identity_fields_and_canonical_key():
    me = mint_identity(role="bench")
    assert me.ident == f"{me.host}:{me.pid}:{me.boot_nonce}"
    assert me.pid == os.getpid()
    assert len(me.boot_nonce) == 8
    assert me.labels() == {"instance": me.ident, "role": "bench"}
    d = me.as_dict()
    assert d["ident"] == me.ident and d["role"] == "bench"


def test_two_incarnations_differ_only_by_nonce():
    # Same host+pid (a crash-looping worker) must still be two identities.
    a, b = mint_identity(), mint_identity()
    assert (a.host, a.pid) == (b.host, b.pid)
    assert a.ident != b.ident


def test_process_identity_minted_once_first_role_wins():
    reset_process_identity()
    try:
        first = process_identity("serve")
        assert first.role == "serve"
        # Later callers share the object; a different role never re-mints.
        assert process_identity("worker") is first
        assert process_identity() is first
    finally:
        reset_process_identity()


# ------------------------------------------------- identity stamping planes
def test_registry_default_labels_applied_at_exposition_only():
    reg = Registry()
    c = reg.counter("vmt_stamp_total", "stamped")
    c.inc(2)
    reg.set_default_labels(instance="h:1:abc", role="serve")
    text = obs.render_prometheus(registry=reg)
    assert 'vmt_stamp_total{instance="h:1:abc",role="serve"} 2' in text
    # The instrument itself keeps its declared (empty) label schema —
    # stamping happens in the renderer, not at observe time.
    assert c.labelnames == ()
    assert c.collect() == {(): 2.0}
    reg.set_default_labels()  # no kwargs clears
    assert "vmt_stamp_total 2" in obs.render_prometheus(registry=reg)


def test_default_labels_never_shadow_declared_labels():
    reg = Registry()
    g = reg.gauge("vmt_stamp_gauge", "g", labelnames=("role",))
    g.set(1.0, role="declared")
    reg.set_default_labels(instance="h:1:abc", role="default")
    line = next(ln for ln in obs.render_prometheus(registry=reg).splitlines()
                if ln.startswith("vmt_stamp_gauge{"))
    assert 'role="declared"' in line and 'role="default"' not in line
    assert 'instance="h:1:abc"' in line


def test_tracer_default_attrs_merged_span_local_wins():
    tr = Tracer()
    tr.set_default_attrs(instance="h:1:abc", role="serve")
    with tr.span("a"):
        pass
    with tr.span("b", role="override"):
        pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["a"].attrs["instance"] == "h:1:abc"
    assert spans["a"].attrs["role"] == "serve"
    assert spans["b"].attrs["role"] == "override"


# ------------------------------------------------------------- fleet spine
def _spine(db, role, *, stale_s=15.0):
    reg, tr = Registry(), Tracer()
    me = mint_identity(role=role)
    ts = TimeSeriesStore()
    return FleetSpine(db, me, heartbeat_stale_s=stale_s, registry=reg,
                      tracer=tr, timeseries=ts), reg, tr, ts


def test_default_spine_path_sits_next_to_queue_db(tmp_path):
    q = str(tmp_path / "queue.sqlite3")
    assert default_spine_path(q) == str(tmp_path / "fleet.sqlite3")


def test_retire_withdraws_presence_but_keeps_spans(tmp_path):
    db = str(tmp_path / "fleet.sqlite3")
    spine, reg, tr, _ = _spine(db, "serve")
    reg.counter("vmt_fleet_test_total").inc()
    with tr.trace(TRACE_ID):
        with tr.span("submit.work"):
            pass
    spine.flush({"phase": "ready"})
    reader, _, _, _ = _spine(db, "reader")
    reader.flush()
    assert spine.identity.ident in {p["ident"] for p in reader.peers()}
    spine.retire()
    assert spine.identity.ident not in {p["ident"] for p in reader.peers()}
    assert "vmt_fleet_test_total" not in reader.render_prometheus()
    # The retired submitter's half of the trace stays stitchable.
    names = {e["name"] for e in reader.chrome_trace(TRACE_ID)["traceEvents"]}
    assert "submit.work" in names


def test_timeseries_merge_keys_by_ident(tmp_path):
    db = str(tmp_path / "fleet.sqlite3")
    a, _, _, ts_a = _spine(db, "serve")
    b, _, _, ts_b = _spine(db, "worker")
    ts_a.record("vmt_qps", 10.0)
    ts_b.record("vmt_qps", 20.0)
    a.flush()
    b.flush()
    series = a.timeseries()["series"]
    assert [v for _, v in series[f"{a.identity.ident}:vmt_qps"]] == [10.0]
    assert [v for _, v in series[f"{b.identity.ident}:vmt_qps"]] == [20.0]


# --------------------------------------------------- two REAL OS processes
_PEER_SRC = r"""
import sys, time
from vilbert_multitask_tpu.obs.fleet import FleetSpine
from vilbert_multitask_tpu.obs.identity import mint_identity
from vilbert_multitask_tpu.obs.instruments import Registry
from vilbert_multitask_tpu.obs.trace import Tracer

db, mode = sys.argv[1], sys.argv[2]
reg, tr = Registry(), Tracer()
reg.counter("vmt_fleet_test_total", "cross-process sum subject").inc(5)
reg.gauge("vmt_fleet_test_depth", "per-ident subject").set(7)
reg.histogram("vmt_fleet_test_ms", "bucket-merge subject").observe(3.0)
with tr.trace("feedface00000000"):
    with tr.span("peer.work"):
        time.sleep(0.01)
me = mint_identity(role="peer")
spine = FleetSpine(db, me, registry=reg, tracer=tr)
spine.flush({"phase": "ready"})
# A tail-kept trace on the same spine db: the crash-autopsy subject the
# SIGKILL test reads back after this process is dead and evicted.
from vilbert_multitask_tpu.obs.attrib import JobCost
from vilbert_multitask_tpu.obs.tracestore import TraceStore
store = TraceStore(db, me.ident)
cost = JobCost(trace_id="feedface00000000", task="vqa", tenant="acme",
               verdict="ok")
cost.stages["forward"] = 250.0
cost.finished_unix = time.time()
store.offer(cost, tr.spans())
store.flush()
print("IDENT " + me.ident, flush=True)
if mode == "linger":
    time.sleep(120)
"""


def _spawn_peer(db, mode):
    proc = subprocess.Popen(
        [sys.executable, "-c", _PEER_SRC, db, mode],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    line = proc.stdout.readline().strip()
    assert line.startswith("IDENT "), (line, proc.stderr.read())
    return proc, line.split(" ", 1)[1]


def test_two_processes_merge_on_one_spine(tmp_path):
    db = str(tmp_path / "fleet.sqlite3")
    spine, reg, tr, _ = _spine(db, "serve")
    reg.counter("vmt_fleet_test_total", "cross-process sum subject").inc(3)
    reg.gauge("vmt_fleet_test_depth", "per-ident subject").set(2)
    reg.histogram("vmt_fleet_test_ms", "bucket-merge subject").observe(9.0)
    with tr.trace(TRACE_ID):
        with tr.span("local.submit"):
            pass
    proc, peer_ident = _spawn_peer(db, "once")
    try:
        assert proc.wait(timeout=60) == 0
        spine.flush({"phase": "ready"})

        health = spine.health()
        idents = {p["ident"] for p in health["processes"]}
        assert {spine.identity.ident, peer_ident} <= idents
        assert health["fleet_ready"] and health["alive"] == 2

        text = spine.render_prometheus()
        # Counters: summed across identities into ONE sample.
        assert "vmt_fleet_test_total 8" in text
        # Gauges: one line per identity, instance label tells them apart.
        assert f'vmt_fleet_test_depth{{instance="{spine.identity.ident}"}} 2' \
            in text
        assert f'vmt_fleet_test_depth{{instance="{peer_ident}"}} 7' in text
        # Histograms: bucket-merged — both observations in one _count.
        assert "vmt_fleet_test_ms_count 2" in text
        assert 'vmt_fleet_test_ms_bucket{le="+Inf"} 2' in text

        # ONE stitched timeline: spans recorded in different processes,
        # correlated by trace_id, one Chrome-trace pid per process.
        trace = spine.chrome_trace(TRACE_ID)
        events = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        assert {e["name"] for e in events} == {"local.submit", "peer.work"}
        assert len({e["pid"] for e in events}) == 2
        assert {e["args"]["ident"] for e in events} == \
            {spine.identity.ident, peer_ident}
        pnames = [e["args"]["name"] for e in trace["traceEvents"]
                  if e.get("name") == "process_name"]
        assert any(peer_ident in n for n in pnames)
    finally:
        proc.kill()
        proc.wait()


def test_sigkilled_peer_evicted_after_heartbeat_staleness(tmp_path):
    db = str(tmp_path / "fleet.sqlite3")
    spine, reg, _, _ = _spine(db, "serve", stale_s=0.5)
    reg.counter("vmt_fleet_test_total", "cross-process sum subject").inc(3)
    proc, peer_ident = _spawn_peer(db, "linger")
    try:
        spine.flush({"phase": "ready"})
        assert peer_ident in {p["ident"] for p in spine.peers()}
        assert "vmt_fleet_test_total 8" in spine.render_prometheus()

        os.kill(proc.pid, signal.SIGKILL)  # no retire(), no goodbye
        proc.wait(timeout=30)
        time.sleep(0.7)  # > heartbeat_stale_s with no fresh heartbeat
        spine.flush({"phase": "ready"})  # keep OUR heartbeat live

        health = spine.health()
        assert health["alive"] == 1 and health["stale"] == 1
        stale = {p["ident"]: p for p in health["processes"]}[peer_ident]
        assert stale["alive"] is False
        # Evicted from the merged exposition: only the live counter shows.
        assert "vmt_fleet_test_total 3" in spine.render_prometheus()
        assert peer_ident not in spine.live_idents()

        # Span-retention asymmetry: eviction withdraws the peer from the
        # health/metrics merges ONLY. Its spans still stitch into the
        # fleet timeline, and its tail-kept trace is still readable from
        # the survivor — the crash autopsy the store exists for.
        events = [e for e in spine.chrome_trace(TRACE_ID)["traceEvents"]
                  if e.get("ph") == "X"]
        assert "peer.work" in {e["name"] for e in events}
        assert peer_ident in {e["args"]["ident"] for e in events}
        survivor = TraceStore(db, spine.identity.ident)
        rows = survivor.list(verdict="slow", task="vqa", scope="fleet")
        assert TRACE_ID in {r["trace_id"] for r in rows}
        stored = survivor.get(TRACE_ID)
        assert stored["ident"] == peer_ident
        assert stored["cost"]["total_ms"] == 250.0
        assert "peer.work" in {s["name"] for s in stored["spans"]}
        # scope=local on the survivor excludes the dead peer's rows —
        # the asymmetry is an explicit choice, not a missed filter.
        assert TRACE_ID not in {
            r["trace_id"] for r in survivor.list(scope="local")}
    finally:
        proc.kill()
        proc.wait()


def test_fleet_flush_errors_instrument_registered():
    # The sampler ride-along counts failed spine flushes here; the serve
    # app and the fleet-scope HTTP handlers share the one instrument.
    c = obs.REGISTRY.counter("vmt_fleet_flush_errors_total")
    assert c.kind == "counter"


# -------------------------------------------- identity on the queue plane
def test_queue_claim_rows_carry_claimed_by(tmp_path):
    from vilbert_multitask_tpu.serve.queue import (
        DurableQueue,
        make_job_message,
    )

    q = DurableQueue(str(tmp_path / "q.sqlite3"))
    q.publish(make_job_message(["a.jpg"], "what is this", 1, "sock"))
    me = mint_identity(role="worker")
    job = q.claim(claimed_by=me.ident)
    assert job is not None
    claims = q.inflight_claims()
    assert [c["claimed_by"] for c in claims] == [me.ident]
    q.ack(job.id)
    assert q.inflight_claims() == []
