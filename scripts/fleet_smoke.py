"""Fleet observability smoke: two OS processes, one spine, one answer.

Bounded CI gate for the fleet plane (obs/identity.py, obs/fleet.py, the
``?scope=fleet`` HTTP surface): boot a ServeApp over a dryrun replica,
spawn a REAL second python process that flushes its own registry/tracer
into the same ``fleet.sqlite3``, then interrogate the app's HTTP face:

- ``/healthz?scope=fleet`` lists both identities and reports fleet_ready
- ``/metrics?scope=fleet`` shows both instances and SUMS the counter the
  two processes incremented independently (3 here + 5 in the peer = 8)
- ``/debug/trace?scope=fleet&trace_id=`` returns ONE stitched timeline
  carrying spans recorded in both processes

CPU-only dryrun: this process pins the CPU platform before ServeApp touches
JAX, and the peer process imports no JAX at all — neither ever holds (or
waits for) a chip.

Usage: python scripts/fleet_smoke.py [--out FLEET_SMOKE.json]
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from serve_soak import DryrunEngine, _build_cfg  # noqa: E402

TRACE_ID = "f1ee7f1ee7f1ee70"

# The second OS process: its own registry and tracer (nothing shared with
# the parent but the spine db path on argv), one counter increment, one
# span under the agreed trace id, one flush, exit. Its heartbeat stays
# fresh for fleet_heartbeat_stale_s, which is the window this smoke
# queries in.
_PEER_SRC = r"""
import sys, time
from vilbert_multitask_tpu.obs.fleet import FleetSpine
from vilbert_multitask_tpu.obs.identity import mint_identity
from vilbert_multitask_tpu.obs.instruments import Registry
from vilbert_multitask_tpu.obs.trace import Tracer

reg, tr = Registry(), Tracer()
reg.counter("vmt_fleet_smoke_total", "cross-process sum subject").inc(5)
reg.gauge("vmt_fleet_smoke_up", "per-process presence subject").set(1)
with tr.trace(sys.argv[2]):
    with tr.span("peer.work"):
        time.sleep(0.01)
spine = FleetSpine(sys.argv[1], mint_identity(role="peer"),
                   registry=reg, tracer=tr)
spine.flush({"phase": "ready"})
print("IDENT " + spine.identity.ident, flush=True)
"""


def _get(conn, path):
    conn.request("GET", path)
    resp = conn.getresponse()
    return resp.status, resp.read().decode()


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="FLEET_SMOKE.json")
    args = p.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")

    from vilbert_multitask_tpu import obs
    from vilbert_multitask_tpu.serve.app import ServeApp

    root = tempfile.mkdtemp(prefix="fleet_smoke_")
    cfg = _build_cfg(root, False)
    t0 = time.perf_counter()
    app = ServeApp(cfg, engine=DryrunEngine(cfg, "r0"))
    app.start(worker=False)
    boot_s = time.perf_counter() - t0

    failures = []
    report = {"metric": "fleet_smoke", "boot_s": round(boot_s, 3)}
    peer_ident = None
    try:
        # This process's half of the evidence: the shared counter and a
        # span under the agreed trace id, both on the app's GLOBAL
        # registry/tracer, which its spine flushes on every fleet query.
        obs.REGISTRY.counter(
            "vmt_fleet_smoke_total", "cross-process sum subject").inc(3)
        # Counters merge into ONE un-labelled sample; the per-process
        # gauge is what makes each identity visible as an instance label.
        obs.REGISTRY.gauge(
            "vmt_fleet_smoke_up", "per-process presence subject").set(1)
        with obs.trace_scope(TRACE_ID), obs.span("smoke.submit"):
            time.sleep(0.005)

        peer = subprocess.run(
            [sys.executable, "-c", _PEER_SRC,
             app.fleet.path, TRACE_ID],
            capture_output=True, text=True, timeout=60,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if peer.returncode != 0:
            failures.append(f"peer process failed: {peer.stderr[-500:]}")
        else:
            peer_ident = peer.stdout.split("IDENT ", 1)[1].strip()
        report["peer_ident"] = peer_ident
        report["local_ident"] = app.identity.ident

        conn = http.client.HTTPConnection("127.0.0.1", app.http_port,
                                          timeout=30)
        t_q = time.perf_counter()

        status, body = _get(conn, "/healthz?scope=fleet")
        health = json.loads(body)
        report["fleet_health"] = health
        if status != 200 or not health.get("fleet_ready"):
            failures.append(f"fleet health not ready: {status} {body[:200]}")
        idents = {pr["ident"] for pr in health.get("processes", [])}
        if peer_ident and not {app.identity.ident, peer_ident} <= idents:
            failures.append(f"identities missing from fleet health: {idents}")

        status, text = _get(conn, "/metrics?scope=fleet")
        if status != 200:
            failures.append(f"/metrics?scope=fleet -> {status}")
        if "vmt_fleet_smoke_total 8" not in text:
            line = [ln for ln in text.splitlines()
                    if ln.startswith("vmt_fleet_smoke_total")]
            failures.append(f"counter not summed across processes: {line}")
        for ident in filter(None, (app.identity.ident, peer_ident)):
            if ident not in text:
                failures.append(f"identity {ident} absent from exposition")

        status, body = _get(
            conn, f"/debug/trace?scope=fleet&trace_id={TRACE_ID}")
        trace = json.loads(body) if status == 200 else {}
        spans = [e for e in trace.get("traceEvents", [])
                 if e.get("ph") == "X"]
        names = {e["name"] for e in spans}
        pids = {e["pid"] for e in spans}
        report["stitched_span_names"] = sorted(names)
        report["stitched_pids"] = len(pids)
        if not {"smoke.submit", "peer.work"} <= names or len(pids) < 2:
            failures.append(
                f"trace not stitched across processes: {names} pids={pids}")
        report["fleet_query_ms"] = round(
            (time.perf_counter() - t_q) * 1e3, 1)
        conn.close()
    finally:
        app.stop()

    verdict = not failures
    report["failures"] = failures
    report["verdict"] = verdict
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report), flush=True)
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
