"""Cross-process claim safety: the runtime twin of VMT128.

Two REAL OS processes (subprocess, own sqlite connections) hammer
claim/nack/release/ack on one WAL queue file. The static tier proves
every read-modify-write takes BEGIN IMMEDIATE; this test is the dynamic
witness ROADMAP item 3(a) needs before the multi-process soak lands:

- no double-claim: every (job, delivery_count) pair is claimed exactly
  once fleet-wide — two processes handed the same delivery would mean
  the claim SELECT→UPDATE pair wasn't atomic;
- no lost attempts update: the attempt balance at each delivery matches
  the charge/un-charge ledger (claim +1, release -1, nack +0) exactly,
  which a lost nack/release write would skew;
- exactly one terminal per job, and the queue drains to empty.
"""

import os
import subprocess
import sys
from collections import defaultdict

from vilbert_multitask_tpu.serve.queue import DurableQueue

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

JOBS = 12

# Each job's scripted life across the fleet, keyed by delivery_count:
# claim #1 -> nack (requeue, attempt stays charged),
# claim #2 -> release (attempt un-charged),
# claim #3 -> ack (terminal). Attempt balance: 1, 2->1, 2.
_WORKER_SRC = r"""
import os, sys, time
from vilbert_multitask_tpu.serve.queue import DurableQueue

db, ident, go_path = sys.argv[1], sys.argv[2], sys.argv[3]
q = DurableQueue(db, max_delivery_attempts=100, max_deliveries=100,
                 visibility_timeout_s=300.0)
print("READY", flush=True)
while not os.path.exists(go_path):
    time.sleep(0.002)
idle = 0
while idle < 40:  # ~200ms with nothing claimable => fleet is drained
    job = q.claim(claimed_by=ident)
    if job is None:
        idle += 1
        time.sleep(0.005)
        continue
    idle = 0
    if job.deliveries == 1:
        action = "nack:" + q.nack(job.id)
    elif job.deliveries == 2:
        q.release(job.id)
        action = "release"
    else:
        q.ack(job.id)
        action = "ack"
    print(f"EV {job.id} {job.deliveries} {job.attempts} {action}",
          flush=True)
"""


def _spawn_worker(db, ident, go_path):
    proc = subprocess.Popen(
        [sys.executable, "-c", _WORKER_SRC, db, ident, go_path],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=REPO, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.stdout.readline().strip() == "READY"
    return proc


def test_two_process_claim_nack_release_ack_exactly_once(tmp_path):
    db = str(tmp_path / "queue.sqlite3")
    go_path = str(tmp_path / "go")
    q = DurableQueue(db, max_delivery_attempts=100, max_deliveries=100,
                     visibility_timeout_s=300.0)
    job_ids = [q.publish({"n": n}) for n in range(JOBS)]

    workers = [_spawn_worker(db, f"stress:{i}", go_path) for i in (0, 1)]
    with open(go_path, "w") as f:
        f.write("go")
    outs = []
    for proc in workers:
        out, err = proc.communicate(timeout=120)
        assert proc.returncode == 0, err
        outs.append(out)

    events = defaultdict(list)  # job id -> [(deliveries, attempts, action)]
    per_worker = []
    for out in outs:
        mine = 0
        for line in out.splitlines():
            if not line.startswith("EV "):
                continue
            _, jid, deliveries, attempts, action = line.split()
            events[int(jid)].append((int(deliveries), int(attempts), action))
            mine += 1
        per_worker.append(mine)

    assert sorted(events) == sorted(job_ids)
    total_claims = sum(per_worker)
    assert total_claims == 3 * JOBS
    # 36 contended claims: a worker that never won a single one would mean
    # the other held the write lock for the whole run.
    assert all(n > 0 for n in per_worker), per_worker

    for jid, evs in events.items():
        evs.sort()  # delivery_count is the fleet-wide claim order
        # No double-claim, no lost delivery: deliveries 1,2,3 exactly once.
        assert [d for d, _, _ in evs] == [1, 2, 3], (jid, evs)
        # No lost attempts update: +1 claim, -1 release, +0 nack.
        assert [a for _, a, _ in evs] == [1, 2, 2], (jid, evs)
        assert [act for _, _, act in evs] == \
            ["nack:pending", "release", "ack"], (jid, evs)

    # Exactly one terminal each: every acked row is gone, nothing lingers.
    assert q.counts() == {}
