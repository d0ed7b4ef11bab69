#!/usr/bin/env bash
# Single local CI entry point: static analysis + the fast test profile.
#
#     scripts/check.sh            # vmtlint (JSON) + tier-1 pytest
#     scripts/check.sh --lint     # vmtlint only (sub-second, AST-only)
#
# Exits non-zero if EITHER gate fails. The lint gate runs first because
# it is ~4 s against the whole repo and catches the classes of bug the
# test tier can't see on CPU (host transfers inside jit, donation
# escapes, lock-discipline races, layer violations).
set -euo pipefail
cd "$(dirname "$0")/.."

fail=0
out=$(mktemp -d)  # the smokes' reports: under $TMPDIR, never in the checkout

echo "== vmtlint (strict, changed-closure scan; VMT_FULL=1 for whole repo) =="
# --strict: warnings gate too, and stale baseline entries fail — debt
# that got paid must leave vmtlint_baseline.json (use --prune-baseline).
# Default is --changed: the diff vs HEAD plus its import closure, which
# falls back to a full scan by itself when the closure is most of the
# project. VMT_FULL=1 forces the whole-repo scan (CI, pre-merge).
if [[ "${VMT_FULL:-}" == "1" ]]; then
  python -m vilbert_multitask_tpu.analysis --strict --format json || fail=1
else
  python -m vilbert_multitask_tpu.analysis --strict --format json --changed \
    || fail=1
fi

echo "== baseline hygiene (no stale suppressions ride along) =="
# A baseline entry whose finding no longer fires is a dead suppression:
# it hides any future finding with the same fingerprint. Fail fast here;
# the fix is `--prune-baseline` (without --check) after reviewing.
python -m vilbert_multitask_tpu.analysis --prune-baseline --check || fail=1

echo "== compile surface (COMPILE_SURFACE.json vs the tree) =="
# The committed manifest enumerates the AOT key universe (family x bucket
# x param_dtype x fused x topology x attn). Drift means someone changed
# the compile surface without regenerating the manifest — rerun
# `python -m vilbert_multitask_tpu.analysis surface` and commit.
python -m vilbert_multitask_tpu.analysis surface --check || fail=1

echo "== durable-state surface (TXN_SURFACE.json vs the tree) =="
# The committed manifest enumerates the sqlite durable state (tables +
# migrated schema, every transaction site with its mode, the recovered
# status state machines). Drift means someone changed a store without
# regenerating the contract ROADMAP item 3's multi-process work reads —
# rerun `python -m vilbert_multitask_tpu.analysis txn` and commit.
python -m vilbert_multitask_tpu.analysis txn --check || fail=1

echo "== protocol surface (PROTOCOL_SURFACE.json vs the tree) =="
# The committed manifest enumerates the typestate protocols (job
# claim→terminal, replica checkout→checkin, thread start→join, sqlite
# connect→close): acquire sites, composed wrappers with witnesses, the
# per-function path-proof verdicts, and fault-site chaos coverage.
# Drift means a protocol path changed without regenerating the proof —
# rerun `python -m vilbert_multitask_tpu.analysis proto` and commit.
python -m vilbert_multitask_tpu.analysis proto --check || fail=1

echo "== failure surface (FAILURE_SURFACE.json vs the tree) =="
# The committed manifest enumerates the exception-flow boundaries (thread
# entry points, HTTP verbs, sampler ticks, breaker regions, fault sites)
# with the escaping-exception set and verdict the exc tier proved for
# each. Drift means an error path changed without regenerating the
# contract — rerun `python -m vilbert_multitask_tpu.analysis exc` and
# commit.
python -m vilbert_multitask_tpu.analysis exc --check || fail=1

echo "== exactly-one-terminal invariant (VMT132 clean scan) =="
# The load-bearing serving invariant, proved statically over every CFG
# path: any unbaselined VMT132 finding anywhere in the library tree
# fails the run outright, independent of severity config.
python - <<'PY' || fail=1
import os, sys
from vilbert_multitask_tpu.analysis import baseline as bl
from vilbert_multitask_tpu.analysis.config import load_config
from vilbert_multitask_tpu.analysis.core import analyze_paths
from vilbert_multitask_tpu.analysis.protorules import JobTerminalProtocol

cfg, root = load_config(os.getcwd())
root = root or os.getcwd()
paths = [os.path.join(root, p) for p in cfg.paths]
findings = analyze_paths([p for p in paths if os.path.exists(p)],
                         root=root, rules=[JobTerminalProtocol()],
                         exclude=cfg.exclude,
                         library_roots=cfg.library_roots,
                         layers=cfg.layers)
baseline = {}
bl_path = os.path.join(root, cfg.baseline) if cfg.baseline else None
if bl_path and os.path.exists(bl_path):
    baseline = bl.load_baseline(bl_path)
new, _, _ = bl.split_baselined(findings, baseline)
for f in new:
    print(f"VMT132 invariant: {f.path}:{f.line}: {f.message}",
          file=sys.stderr)
sys.exit(1 if new else 0)
PY

if [[ "${1:-}" == "--lint" ]]; then
  exit "$fail"
fi

echo "== tier-1 tests (fast profile, as the driver runs it) =="
# Six workers, one file to a worker. In one process two tests fail, as
# they did before PR 30 (test_chip_smoke.py::test_cpu_rehearsal_passes;
# test_serve.py::test_serveapp_start_exposes_build_info_uptime_and_recorder
# finds the vmt_build_info lines of apps other files left in the registry).
JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider \
  -p xdist -n 6 --dist loadfile -p no:randomly || fail=1

echo "== conservation smoke (plain soak: attributed device-s vs busy wall) =="
# Short fault-free soak for the cost-attribution double-entry gate: the
# summed per-job device shares must land within 10% of the engine busy
# wall (chaos runs legitimately strand shares on failed batches, so the
# conservation gate only runs here).
JAX_PLATFORMS=cpu python scripts/serve_soak.py --jobs 20 \
  --out "$out"/PLAIN_SOAK.json || fail=1

echo "== chaos smoke (seeded FaultPlan, no-lost-jobs invariant) =="
# Short end-to-end soak under injected faults: every submitted job must
# reach exactly one terminal state (result / dead-letter / deadline push),
# every failed job must have a stored trace for its autopsy, and the
# flight recorder must capture an injected fault's trace.
JAX_PLATFORMS=cpu python scripts/serve_soak.py --chaos --jobs 15 \
  --out "$out"/CHAOS_SOAK.json || fail=1

echo "== thread-kill smoke (seeded intake-thread death, watchdog visibility) =="
# One-shot queue.claim fault kills one scheduler intake thread mid-burst
# through the exc tier's VMT137 witness path. Gate: /healthz names the
# dead thread within one sampler cadence, the thread_died bundle lands,
# and the surviving intake threads drain every job to exactly one
# terminal state.
JAX_PLATFORMS=cpu python scripts/serve_soak.py --kill-thread --jobs 15 \
  --out "$out"/THREADKILL_SOAK.json || fail=1

echo "== scheduler smoke (continuous batching >= solo loop, no lost jobs) =="
# Same burst twice through one engine: serial batch=1 loop vs. the
# continuous-batching scheduler. Gate: scheduler keeps every job (exactly
# one result each, queue drained) and at least matches solo throughput.
JAX_PLATFORMS=cpu python scripts/sched_smoke.py --jobs 32 \
  --out "$out"/SCHED_SMOKE.json || fail=1

echo "== failover smoke (replica pool: seeded kill, exactly-one-terminal) =="
# 2-replica dryrun pool soak with a seeded mid-burst replica kill: >=1.5x
# qps vs 1 replica, rolling swap loses zero requests, the killed replica's
# batch fails over (release, no attempt charged) with exactly one terminal
# per job, and the corpse shows dead in /healthz within a sampler cadence.
JAX_PLATFORMS=cpu python scripts/serve_soak.py --replicas 2 --dryrun \
  --kill-replica --seed 7 --jobs 40 --out "$out"/POOL_SOAK.json || fail=1

echo "== zipf smoke (result cache, coalescing, swap invalidation) =="
# Duplicate-traffic soak: one leader + attached followers collapse to one
# forward, cached hits answer inline at >=10x the forward path's qps, a
# rolling swap turns every warmed key back into a miss, and the device-s
# conservation ledger stays EXACTLY 1.0 with hits/followers in the mix.
JAX_PLATFORMS=cpu python scripts/serve_soak.py --zipf --jobs 48 \
  --out "$out"/ZIPF_SOAK.json || fail=1

echo "== zipf chaos smoke (coalesced leader dies, followers still close) =="
# Same burst, but a seeded worker.intake fault plan dead-letters the
# coalesced leader: every one of the N identical submits must still reach
# exactly one terminal frame (the dead-letter fan-out).
JAX_PLATFORMS=cpu python scripts/serve_soak.py --zipf --chaos --jobs 48 \
  --seed 3 --out "$out"/ZIPF_CHAOS_SOAK.json || fail=1

echo "== autoscale smoke (flash crowd: breach -> grow -> trough -> retire) =="
# Closed-loop autoscaler under a diurnal + flash-crowd shape: the spike
# must add capacity within one AOT-boot latency of the sustained-breach
# decision, nothing with deadline slack sheds during scale-out, and the
# trough retires the pool back to the floor — exactly one terminal per job
# throughout.
JAX_PLATFORMS=cpu python scripts/serve_soak.py --autoscale \
  --out "$out"/AUTOSCALE_SOAK.json || fail=1

echo "== autoscale chaos smoke (poison storm: loud signals, zero scale-out) =="
# Seeded worker.intake storm dead-letters every job while slow claims pile
# queue wait over the breach band: the controller must HOLD (poison_storm
# decisions), never add a replica, and the dead-letter fan still closes
# every socket exactly once.
JAX_PLATFORMS=cpu python scripts/serve_soak.py --autoscale --chaos \
  --seed 11 --out "$out"/AUTOSCALE_CHAOS_SOAK.json || fail=1

echo "== quant smoke (int8 storage parity + roofline-knee plumbing) =="
# Tiny f32 vs int8 engine: quantized tree reads <0.35x the bytes, one
# task per decode family stays within quantization noise through the
# fused head path, and the analytic batch knee (engine/flops.knee_rows)
# shrinks with the storage dtype.
JAX_PLATFORMS=cpu python scripts/quant_smoke.py \
  --out "$out"/QUANT_SMOKE.json || fail=1

echo "== SLO smoke (live-health plane answers under load) =="
# Boot → synthetic load → /debug/slo parses with every SLO evaluated
# (both burn windows) and /healthz reports ready.
JAX_PLATFORMS=cpu python scripts/slo_smoke.py \
  --out "$out"/SLO_SMOKE.json || fail=1

echo "== fleet smoke (two processes, one spine: merged metrics + stitched trace) =="
# A second OS process flushes into the app's fleet spine; ?scope=fleet
# must list both identities, sum the shared counter, and stitch one
# cross-process trace timeline.
JAX_PLATFORMS=cpu python scripts/fleet_smoke.py \
  --out "$out"/FLEET_SMOKE.json || fail=1

echo "== AOT smoke (two boots, one executable cache: warm boot in seconds) =="
# Two fresh-process tiny boots sharing one AOT + XLA cache dir pair. Gate:
# the second boot deserializes every warmup program (zero trace+compiles,
# zero fallbacks) and its wall clock is <50% of the cold boot.
JAX_PLATFORMS=cpu python scripts/aot_smoke.py \
  --out "$out"/AOT_SMOKE.json || fail=1

exit "$fail"
