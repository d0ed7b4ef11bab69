"""Continuous batching: the deadline-aware cross-request scheduler.

``step_batch`` (worker.py) drains the queue in lockstep — claim N, prep N,
forward once, persist N, repeat — so the device idles through every claim
and every SQLite write, and a job arriving one tick after a batch closed
waits a whole cycle. The soak showed the cost: 44 qps served against a
217-408 qps engine ceiling (ARCHITECTURE "Round-5 hardware findings").
This module replaces that loop with the Orca/vLLM-shaped pipelined data
plane the 12-in-1 shared trunk makes possible (any task mix packs into one
forward):

    intake pool (N threads)        scheduler (dispatch thread)   completion
    claim -> deadline check        adaptive window + EDF pack    _finish_job
    -> feature I/O + prep    ==>   -> chunk_plan -> run_many ==> persist+push
    feeds _ready               results stream out per member     ack

Three rules govern the dispatch stage:

- **window**: fire when a bucket fills, when the oldest ready job has
  lingered a full window, or when any member's deadline slack drops under
  ``NEAR_DEADLINE_S``. The window adapts AIMD-style — a full batch
  doubles it (backlog: linger to pack more), a partial batch halves it
  (idle: fire immediately) — between ``sched_window_min_s`` and
  ``sched_window_max_s``.
- **EDF**: members pack in earliest-deadline-first order (the
  ``resilience.Deadline`` riding every job body is the key); expired
  members shed pre-pack via the worker's normal expiry path, so a forward
  is never burned on a long-gone client.
- **exactly one terminal state**: every claimed job ends in exactly one of
  result / dead-letter / deadline push — results stream member-by-member
  into the completion queue as chunks drain (engine ``on_result``), and a
  mid-batch failure fails only the members that had NOT already streamed.

Lock discipline (vmtlint VMT116 ``blocking-call-under-scheduler-lock``):
``_cond`` guards only the ready list, the window, and the stat counters —
never device dispatch, SQLite I/O, or sleeps. Expiry pushes, intake I/O,
and ``run_many`` all happen outside it; the completion queue's blocking
``put`` is the one intentional backpressure point and sits outside too.
"""

from __future__ import annotations

import math
import queue as stdlib_queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.serve.pool import NoReadyReplica
from vilbert_multitask_tpu.serve.push import log_to_terminal
from vilbert_multitask_tpu.serve.queue import Job

# Intake pool width: threads claiming jobs and running feature I/O + prep
# concurrently with the device forward.
INTAKE_THREADS = 4
# A ready member whose deadline slack drops below this fires the batch at
# once (the EDF front of the queue must not wait out the window).
NEAR_DEADLINE_S = 0.25
# Bound on completed-but-unpersisted results queued to the completion
# stage (persist/push backpressure on the dispatch thread).
COMPLETION_DEPTH = 128


class ReadyItem:
    """One claimed + prepped job parked in the ready-queue.

    ``solo`` marks attention-map requests: they need a per-request forward
    flag, so they skip shared intake here (``step_one`` runs the whole
    pipeline for them) and never pack into a shared chunk.

    ``tenant`` is the job body's billing dimension, reused as the QoS
    class the deficit tier budgets by; ``deferred`` flips when a fire
    passed this item over for tenant-budget reasons (not row pressure
    alone), so an expiry while deferred sheds as ``tenant_budget``
    instead of ``deadline``.

    ``enq_t`` is taken before the intake prepares the job (the window
    policy's age, ``vmt_sched_wait_ms``), ``ready_t`` when the prepared
    item is parked (``vmt_ready_wait_ms``, the attributor's
    ``ready_wait``).
    """

    __slots__ = ("job", "qa_id", "prepared", "t0", "deadline", "enq_t",
                 "solo", "tenant", "deferred", "ready_t")

    def __init__(self, job: Job, qa_id, prepared, t0, deadline, enq_t,
                 solo: bool = False, tenant: str = "anon"):
        self.job = job
        self.qa_id = qa_id
        self.prepared = prepared
        self.t0 = t0
        self.deadline = deadline
        self.enq_t = enq_t
        self.solo = solo
        self.tenant = tenant
        self.deferred = False
        self.ready_t = enq_t

    def rows(self) -> int:
        return self.prepared.n_images if self.prepared is not None else 1

    def expiry(self) -> float:
        """EDF sort key: absolute perf-counter expiry, +inf when the job
        carries no deadline (budgetless jobs pack last, never shed)."""
        return (self.deadline.expires_at() if self.deadline is not None
                else math.inf)


def fire_decision(now: float, *, rows: int, oldest_enq_t: float,
                  nearest_expiry: float, max_rows: int, window_s: float,
                  near_deadline_s: float) -> Tuple[bool, float]:
    """Pure window policy: should a non-empty ready set fire now?

    Returns ``(fire, wait_s)`` — when not firing, ``wait_s`` is how long
    the dispatcher may sleep before one of the fire conditions can first
    become true (new arrivals re-wake it earlier via the condvar).
    ``nearest_expiry`` is +inf when no member carries a deadline.
    """
    if rows >= max_rows:
        return True, 0.0  # a bucket is full — lingering buys nothing
    if nearest_expiry - now <= near_deadline_s:
        return True, 0.0  # EDF front would miss its deadline waiting
    window_wait = (oldest_enq_t + window_s) - now
    if window_wait <= 0.0:
        return True, 0.0  # oldest member waited out the whole window
    deadline_wait = nearest_expiry - now - near_deadline_s
    return False, max(min(window_wait, deadline_wait), 0.0)


def select_batch(ready: List[ReadyItem], now: float, max_rows: int, *,
                 deficits: "Optional[dict]" = None,
                 weights: "Optional[dict]" = None,
                 default_weight: float = 1.0
                 ) -> Tuple[List[ReadyItem], List[ReadyItem],
                            List[ReadyItem]]:
    """Pure packing: ``(batch, expired, rest)``.

    Members sort earliest-deadline-first; already-expired members are
    split out for shedding (the caller expires them OUTSIDE the scheduler
    lock — expiry pushes/acks block). Packing stops charging the row
    budget once ``max_rows`` is reached; later members stay ready, still
    in EDF order, for the next fire.

    With ``deficits`` (the caller's persistent tenant→credit map) a
    weighted-deficit tier sits ABOVE the deadline ordering: each fire
    grants every present tenant ``max_rows * w/Σw`` rows of credit
    (weights from ServingConfig.tenant_weights, ``default_weight`` for
    unlisted tenants), then repeatedly packs the EDF head of the
    highest-credit tenant, spending its credit per row. The tier is
    work-conserving — the device never idles for fairness; under
    contention a hot tenant's surplus items are the ones passed over
    (marked ``deferred``, shed as ``tenant_budget`` if they expire
    waiting). A tenant whose backlog fully drains in a fire resets to
    zero credit and leaves the map, bounding its cardinality to tenants
    with live backlog. ``deficits=None`` is the pure-EDF legacy path.
    """
    batch: List[ReadyItem] = []
    expired: List[ReadyItem] = []
    rest: List[ReadyItem] = []
    live: List[ReadyItem] = []
    for item in sorted(ready, key=ReadyItem.expiry):
        if item.deadline is not None and item.expiry() <= now:
            expired.append(item)
        else:
            live.append(item)
    if deficits is None:
        rows = 0
        for item in live:
            if rows < max_rows:
                batch.append(item)
                rows += item.rows()
            else:
                rest.append(item)
        return batch, expired, rest
    # --- tenant-weighted deficit tier (DRR) above EDF ---
    weights = weights or {}
    present: "dict[str, List[ReadyItem]]" = {}
    for item in live:
        present.setdefault(item.tenant, []).append(item)
    if present:
        total_w = sum(max(weights.get(t, default_weight), 1e-9)
                      for t in present)
        for t in present:
            share = max(weights.get(t, default_weight), 1e-9) / total_w
            # Credit carries over between fires (a starved tenant's
            # backlog catches up) but is capped so an idle-then-bursty
            # tenant cannot hoard the whole device.
            deficits[t] = min(deficits.get(t, 0.0) + max_rows * share,
                              2.0 * max_rows)
    rows = 0
    while rows < max_rows:
        cands = [t for t, items in present.items() if items]
        if not cands:
            break
        # Highest credit wins the slot; earliest deadline breaks ties.
        t = max(cands, key=lambda c: (deficits.get(c, 0.0),
                                      -present[c][0].expiry()))
        item = present[t].pop(0)
        batch.append(item)
        rows += item.rows()
        deficits[t] = deficits.get(t, 0.0) - item.rows()
    for t, items in list(present.items()):
        if items:
            for item in items:
                item.deferred = True
                rest.append(item)
        else:
            # Backlog fully served: classic DRR resets the credit, and
            # dropping the entry bounds the map to live-backlog tenants.
            deficits.pop(t, None)
    rest.sort(key=ReadyItem.expiry)
    return batch, expired, rest


def adapt_window(window_s: float, fill: float, *, lo: float, hi: float
                 ) -> float:
    """Pure AIMD window update: full batches stretch (backlog — linger to
    pack the next one fuller), partial batches shrink (idle — fire fast)."""
    if fill >= 1.0:
        return min(window_s * 2.0, hi)
    return max(window_s / 2.0, lo)


class ContinuousScheduler:
    """The three-stage data plane around one :class:`ServeWorker`.

    ``run()`` owns the dispatch loop in the calling thread (the serve
    worker thread), spawns ``INTAKE_THREADS`` intake threads and one
    completion thread, and tears all of them down on ``stop_event``:
    intake stops claiming first, in-hand ready jobs release back to
    pending (no attempt charged), the completion queue drains, and only
    then does run() return — the same graceful-drain contract
    ``step_batch`` honored.

    ``clock`` is injectable for window/EDF tests; spans keep their own
    ``time.perf_counter`` so traces stay real under a fake clock.
    """

    def __init__(self, worker, *, stop_event: Optional[threading.Event] = None,
                 poll_interval_s: float = 0.05, clock=time.perf_counter):
        self.worker = worker
        self.serving = worker.serving
        self.stop = stop_event if stop_event is not None else threading.Event()
        self.poll_interval_s = poll_interval_s
        self.clock = clock
        # _cond guards _ready, _window_s, and _stats — NOTHING blocking
        # runs under it (VMT116).
        self._cond = threading.Condition()
        self._ready: List[ReadyItem] = []
        self._window_s = self.serving.sched_window_min_s
        self._stats = {"batches": 0, "jobs": 0, "shed": 0, "released": 0,
                       "solo": 0}
        # Tenant-weighted fairness state (select_batch's deficit tier):
        # the persistent tenant→credit map, the configured weights, and
        # a per-tenant queue-wait EWMA for the sampler. All guarded by
        # _cond like the rest of the scheduler state.
        self._weights = dict(
            getattr(self.serving, "tenant_weights", None) or {})
        self._deficits: dict = {}
        self._tenant_wait_ms: dict = {}
        self._completions: stdlib_queue.Queue = stdlib_queue.Queue(
            maxsize=COMPLETION_DEPTH)
        # The intake's timed claim, one thread at a time: when the next one
        # is due (time.monotonic), poll_interval_s after the last claim any
        # intake thread began. Idle threads all wake at that moment, the
        # first moves it on and claims, the rest go back to waiting.
        # Guarded by _poll_lock, under which nothing blocks.
        self._poll_lock = threading.Lock()
        self._next_poll_at = 0.0
        # Generate apps only: sequences whose last token is dispatched and
        # whose tokens the host has not fetched yet, by id of the request.
        self._awaiting_tokens: dict = {}
        # Replica-pool mode: when the worker's engine is a ReplicaPool
        # (duck-typed on the checkout seam), batches PIN to one replica —
        # checkout here, dispatch on an executor thread (one in-flight
        # batch per replica slot), checkin in the dispatch task. The
        # dispatch loop keeps selecting the next batch while replicas
        # compute concurrently. Legacy single engines dispatch inline.
        self.pool = (worker.engine
                     if hasattr(worker.engine, "checkout") else None)
        self._executor: Optional[ThreadPoolExecutor] = None
        if self.pool is not None:
            slots = (len(self.pool.replicas)
                     * self.serving.pool_max_inflight_per_replica)
            self._executor = ThreadPoolExecutor(
                max_workers=max(1, slots),
                thread_name_prefix="sched-dispatch")

    # -------------------------------------------------------- intake stage
    def _intake_loop(self) -> None:
        """Claim when there may be work; prep on this thread; park ready
        items.

        When: straight after this thread's last job, unless that claim saw
        the queue empty behind it; when the queue signals that this process
        made a job deliverable (publish, nack, release); and, on one idle
        thread at a time, every ``poll_interval_s``. The timed claim is
        what finds a job another process wrote into the file or a claim
        whose visibility timeout ran out, and what sends the dead-letter
        notices. The claim itself stays the durable hand-over; the signal
        only chooses its moment.

        Backpressure: while the ready set is at ``sched_ready_depth`` this
        thread idles instead of claiming — ready jobs stay 'inflight' in
        the durable queue, so they keep counting against the HTTP door's
        AdmissionController depth (pending + inflight); the knob bounds
        claim run-ahead, it does not bypass admission.

        Runs under :func:`obs.crash_guard`: the exc tier proved the
        claim at the top of this loop sits OUTSIDE the intake
        try/except, so an injected ``queue.claim`` fault (or any remote
        transport error) would kill the thread silently. The guard
        records a ``thread_died`` bundle and flips ``/healthz`` instead.
        """
        with obs.crash_guard(threading.current_thread().name):
            self._intake_pump()

    def _await_work(self, seq: int) -> bool:
        """After a claim that left the queue empty: wait for the queue's
        signal or for this thread's turn at the timed claim, whichever is
        first. True when a signal ended the wait. ``seq`` is the queue's
        ``work_seq()`` from before that claim, so a publish since then is
        not slept through. ``stop`` is looked at every ``poll_interval_s``
        at the latest."""
        wait_for_work = getattr(self.worker.queue, "wait_for_work", None)
        if wait_for_work is None:
            # A queue that cannot signal (serve/remote.py: the jobs are on
            # another host): the timer alone, on every thread.
            self.stop.wait(self.poll_interval_s)
            return False
        while not self.stop.is_set():
            now = time.monotonic()
            with self._poll_lock:
                if now >= self._next_poll_at:
                    self._next_poll_at = now + self.poll_interval_s
                    return False
                due_in = self._next_poll_at - now
            signalled, seq = wait_for_work(seq, due_in)
            if signalled:
                return True
        return False

    def _intake_pump(self) -> None:
        work_seq = getattr(self.worker.queue, "work_seq", lambda: 0)
        signalled = False  # did a signal end this thread's last wait
        while not self.stop.is_set():
            with self._cond:
                backlog = len(self._ready)
            if backlog >= self.serving.sched_ready_depth:
                obs.INTAKE_BACKPRESSURE_POLLS.inc()
                self.stop.wait(self.poll_interval_s)
                continue
            seq = work_seq()
            with self._poll_lock:
                # Whatever prompted it, a claim looks at the whole queue:
                # the timed one is not due before poll_interval_s from now.
                self._next_poll_at = time.monotonic() + self.poll_interval_s
            job = self.worker._claim()
            if job is None:
                obs.INTAKE_EMPTY_POLLS.inc()
                signalled = self._await_work(seq)
                continue
            (obs.INTAKE_CLAIMS_SIGNALLED if signalled
             else obs.INTAKE_CLAIMS_UNSIGNALLED).inc()
            signalled = False
            self._park(job)
            if job.more is False:
                # The claim saw nothing deliverable behind this job, and the
                # number tells whether anything has been made so since: no
                # second claim only to find the queue empty.
                signalled = self._await_work(seq)

    def _park(self, job: Job) -> None:
        """Deadline check and prep of one claimed job, on the intake thread;
        the ready item is handed to the dispatch stage."""
        if self.worker._check_deadline(job):
            return  # expired on arrival: terminal push already sent
        enq_t = self.clock()
        deadline = self.worker._deadline_of(job)
        tenant = str(job.body.get("tenant") or "anon")
        if job.body.get("collect_attention"):
            # Per-request forward flag: step_one runs the whole
            # pipeline solo at dispatch, so no shared intake here.
            item = ReadyItem(job, None, None, None, deadline, enq_t,
                             solo=True, tenant=tenant)
        else:
            try:
                with obs.trace_scope(job.body.get("trace_id")), \
                        obs.span("worker.intake", job_id=job.id,
                                 task_id=job.body.get("task_id", "")):
                    qa_id, prepared, t0 = self.worker._intake(job)
            except Exception:
                self.worker._fail_job(job)
                return
            item = ReadyItem(job, qa_id, prepared, t0, deadline, enq_t,
                             tenant=tenant)
        item.ready_t = self.clock()
        with self._cond:
            self._ready.append(item)
            self._cond.notify()

    # ------------------------------------------------------ dispatch stage
    def _next_batch(self) -> Tuple[List[ReadyItem], List[ReadyItem]]:
        """Block until the window policy fires; returns (batch, expired).

        Both lists are selected under ``_cond`` but everything done WITH
        them (expiry pushes, device dispatch) happens after release.
        Returns two empty lists once ``stop`` is set.
        """
        max_rows = self.worker.engine.cfg.engine.max_batch_rows()
        with self._cond:
            while not self.stop.is_set():
                if not self._ready:
                    t = time.perf_counter()
                    self._cond.wait(self.poll_interval_s)
                    # In-memory inc, like the observe below (VMT116).
                    obs.DISPATCH_STARVED.inc(time.perf_counter() - t)
                    continue
                now = self.clock()
                fire, wait_s = fire_decision(
                    now,
                    rows=sum(i.rows() for i in self._ready),
                    oldest_enq_t=min(i.enq_t for i in self._ready),
                    nearest_expiry=min(i.expiry() for i in self._ready),
                    max_rows=max_rows,
                    window_s=self._window_s,
                    near_deadline_s=NEAR_DEADLINE_S,
                )
                if not fire:
                    self._cond.wait(min(wait_s, self.poll_interval_s))
                    continue
                # In-memory observe, like the gauge set below (VMT116).
                obs.SCHED_READY_JOBS.observe(len(self._ready))
                batch, expired, rest = select_batch(
                    self._ready, now, max_rows,
                    deficits=self._deficits, weights=self._weights)
                # Slice-assign keeps the one list object (and is the
                # truncation idiom VMT115 audits in this plane).
                self._ready[:] = rest
                # In-memory gauge set — non-blocking, fine under
                # _cond (VMT116 audits blocking calls only).
                for t, credit in self._deficits.items():
                    obs.TENANT_DEFICIT.set(credit, tenant=t)
                if batch:
                    fill = min(
                        sum(i.rows() for i in batch) / max_rows, 1.0)
                    self._window_s = adapt_window(
                        self._window_s, fill,
                        lo=self.serving.sched_window_min_s,
                        hi=self.serving.sched_window_max_s)
                return batch, expired
        return [], []

    def _checkout_for_dispatch(self):
        """Pool checkout that stays responsive to the drain signal: wait in
        poll-interval slices up to the configured checkout timeout."""
        deadline = self.clock() + self.serving.pool_checkout_timeout_s
        while not self.stop.is_set():
            remaining = deadline - self.clock()
            if remaining <= 0:
                break
            try:
                return self.pool.checkout(
                    timeout_s=min(self.poll_interval_s, remaining))
            except NoReadyReplica:
                continue
        raise NoReadyReplica("no ready replica before drain/timeout")

    def _dispatch(self, batch: List[ReadyItem]) -> None:
        """One fire: solos serve individually, the rest pack through
        ``run_many`` with results streaming to the completion stage.

        Pool mode pins the packed batch to ONE checked-out replica and
        runs it on the executor, so the dispatch loop can fire the next
        batch onto another replica while this one computes."""
        now = self.clock()
        for item in batch:
            self._observe_dispatched(item, now)
        with self._cond:
            # Per-tenant queue-wait EWMA for the sampler: the fairness
            # tier's observable effect is exactly this number staying
            # flat for light tenants while a hot tenant backlogs.
            for item in batch:
                wait_ms = max(now - item.enq_t, 0.0) * 1e3
                prev = self._tenant_wait_ms.get(item.tenant)
                self._tenant_wait_ms[item.tenant] = (
                    wait_ms if prev is None
                    else 0.8 * prev + 0.2 * wait_ms)
        packed = [i for i in batch if not i.solo]
        solos = [i for i in batch if i.solo]
        for item in solos:
            with self._cond:
                self._stats["solo"] += 1
                self._stats["jobs"] += 1
            self.worker.step_one(item.job)
        if not packed:
            return
        if self.pool is None:
            self._dispatch_packed(packed, None)
            return
        try:
            rep = self._checkout_for_dispatch()
        except NoReadyReplica:
            # Nothing can take the batch right now (swap-drain, breaker
            # storm, or shutdown): release every member for redelivery —
            # no attempt charged, and the delivery-count quarantine still
            # bounds jobs that land here forever.
            for item in packed:
                self.worker._failover_job(item.job, "none")
            return
        self._executor.submit(self._dispatch_packed, packed, rep)

    def _dispatch_packed(self, packed: List[ReadyItem], rep) -> None:
        """Forward one packed batch on one engine (a checked-out replica,
        or the worker's own engine in legacy mode) and stream results."""
        t_pack = time.perf_counter()
        engine = rep.engine if rep is not None else self.worker.engine
        reqs = [i.prepared for i in packed]
        plan = engine.chunk_plan([r.n_images for r in reqs])
        top_bucket = 0
        for idxs in plan:
            rows = sum(reqs[i].n_images for i in idxs)
            bucket = engine.cfg.engine.row_bucket_for(rows)
            top_bucket = max(top_bucket, bucket)
            obs.BATCH_FILL.observe(rows / bucket, bucket=str(bucket))
            obs.BATCHES_DISPATCHED.inc()
        with self._cond:
            self._stats["batches"] += len(plan)
            self._stats["jobs"] += len(packed)
        streamed = set()

        def _on_result(pos: int, result) -> None:
            streamed.add(pos)
            self._complete(packed[pos], result)

        rep_name = rep.name if rep is not None else ""
        t_fwd = time.perf_counter()
        for item in packed:
            obs.job_charge(item.job.body.get("trace_id", ""), "pack",
                           t_fwd - t_pack)
        rows_total = sum(r.n_images for r in reqs)

        def _charge_forward(wall_s, members) -> None:
            # Amortized device share per member (attrib double-entry: the
            # FULL wall lands on the busy ledger, only listed members are
            # billed — a mid-batch failure's unstreamed rows show as waste).
            obs.job_batch(
                wall_s,
                [(i.job.body.get("trace_id", ""), i.prepared.n_images)
                 for i in members],
                batch_rows=rows_total, bucket=top_bucket, replica=rep_name)

        # A batch of one serves one trace, so its spans join it (the
        # engine's child spans then sit in the request's own waterfall); a
        # shared batch stands alone under a fresh id, members in job_ids.
        own_trace = (packed[0].job.body.get("trace_id")
                     if len(packed) == 1 else None)
        try:
            with obs.trace_scope(own_trace), \
                    obs.span("worker.batch_forward", n_jobs=len(packed),
                             job_ids=[i.job.id for i in packed],
                             replica=rep_name):
                engine.run_many(reqs, on_result=_on_result)
            # Attribute the shared forward window into each member's own
            # trace (same contract as step_batch) so per-request
            # waterfalls stay contiguous under batching.
            dur_fwd = time.perf_counter() - t_fwd
            for item in packed:
                obs.default_tracer().record_span(
                    "worker.infer", t_fwd, dur_fwd,
                    trace_id=item.job.body.get("trace_id"),
                    job_id=item.job.id, task_id=item.prepared.spec.task_id,
                    batched=True, n_jobs=len(packed))
            _charge_forward(dur_fwd, packed)
            if rep is not None:
                self.pool.checkin(
                    rep, ok=True,
                    elapsed_ms=(time.perf_counter() - t_fwd) * 1e3)
        except Exception as e:  # noqa: BLE001 — split below
            _charge_forward(time.perf_counter() - t_fwd,
                            [i for pos, i in enumerate(packed)
                             if pos in streamed])
            if rep is not None:
                self.pool.checkin(rep, ok=False, error=e)
                rep.failovers += 1
            # Exactly-one-terminal: members that already streamed get
            # their terminal state from the completion stage; only the
            # rest terminate here. With a pool the REPLICA is the suspect
            # (release + redeliver; delivery_count bounds poison jobs) —
            # legacy mode keeps the nack/dead-letter path.
            for pos, item in enumerate(packed):
                if pos not in streamed:
                    if rep is not None:
                        self.worker._failover_job(item.job, rep.name)
                    else:
                        self.worker._fail_job(item.job)

    def _observe_dispatched(self, item: ReadyItem, now: float) -> None:
        """A ready item leaves the ready-queue for the device: claim to
        dispatch (``vmt_sched_wait_ms``) and its wait as a prepared job
        (``vmt_ready_wait_ms``, charged as ``ready_wait``: the intake is
        charged by the worker, so it is not counted twice)."""
        obs.SCHED_WAIT.observe(max(now - item.enq_t, 0.0) * 1e3)
        ready_wait = max(now - item.ready_t, 0.0)
        obs.READY_WAIT.observe(ready_wait * 1e3)
        obs.job_charge(item.job.body.get("trace_id", ""), "ready_wait",
                       ready_wait)

    def _complete(self, item: ReadyItem, result) -> None:
        """Hand a result to the completion stage, stamped with its put
        time. The blocking put IS the completion backpressure: a stalled
        persist/push stage stalls the dispatch thread, whose seconds
        blocked are counted, instead of piling unpersisted results
        without bound."""
        msg = (item, result, time.perf_counter())
        try:
            self._completions.put_nowait(msg)
        except stdlib_queue.Full:
            t = time.perf_counter()
            self._completions.put(msg)
            obs.DISPATCH_BLOCKED.inc(time.perf_counter() - t)

    # ---------------------------------------------------- completion stage
    def _completion_loop(self) -> None:
        """Persist + push off the dispatch thread, so the next batch's
        forward overlaps this batch's DB writes and websocket frames.

        Guarded like the intake loop: ``_fail_job`` in the except arm
        reaches the queue's nack (remote transport in split deploys), so
        even the recovery path can raise — the guard makes that death
        loud instead of stranding every future completion."""
        with obs.crash_guard(threading.current_thread().name):
            self._completion_pump()

    def _completion_pump(self) -> None:
        while True:
            msg = self._completions.get()
            if msg is None:
                return
            item, result, put_t = msg
            obs.COMPLETION_WAIT.observe(
                max(time.perf_counter() - put_t, 0.0) * 1e3)
            try:
                with obs.trace_scope(item.job.body.get("trace_id")):
                    self.worker._finish_job(item.job, item.qa_id,
                                            item.prepared, result, item.t0)
                self.worker.queue.ack(item.job.id)
                self.worker._untrack(item.job.id)
            except Exception:
                self.worker._fail_job(item.job)

    # ------------------------------------------------------ generate stage
    # How often a waiting job may be passed over by later ones that fit
    # before nothing behind it is admitted any more (no starvation of a
    # long prompt by a stream of short ones).
    GENERATE_MAX_PASSED_OVER = 8

    def _generate_loop(self) -> None:
        """The dispatch stage of an app that serves the ``generate`` task
        (engine/generate.py): continuous batching over a *running set* of
        sequences. Each iteration admits parked jobs while the sequence-
        state manager says yes (first fit in arrival order; a job that
        does not fit waits, never fails), runs at most one prefill chunk,
        then one decode step over every running sequence. A sequence
        whose last token is dispatched gives its slot and pages back at
        once; its job goes to the completion stage when the tokens have
        been fetched, as one terminal frame like every task's."""
        eng = self.pool.replicas[0].engine if self.pool is not None \
            else self.worker.engine
        running: List[ReadyItem] = []   # admitted, not yet done
        passed_over: dict = {}
        try:
            while not self.stop.is_set():
                with self._cond:
                    parked = list(self._ready)
                    if not parked and not running and eng.idle:
                        self._cond.wait(self.poll_interval_s)
                        continue
                self._generate_iteration(eng, parked, running, passed_over)
        finally:
            # Whatever is admitted goes back to the ready set, which run()
            # releases to the queue: a restart serves it from the start.
            for item in running:
                eng.release(item.prepared)
            eng.collect(drain=True)
            with self._cond:
                self._ready[:0] = running

    def _generate_iteration(self, eng, parked, running, passed_over) -> None:
        now = self.clock()
        with obs.span("sched.generate_iter", parked=len(parked),
                      running=len(running)) as sp:
            admitted, expired = [], []
            for item in parked:
                if item.deadline is not None and item.deadline.expired():
                    expired.append(item)
                    continue
                if eng.admit(item.prepared):
                    admitted.append(item)
                    passed_over.pop(item.job.id, None)
                    continue
                n = passed_over[item.job.id] = passed_over.get(
                    item.job.id, 0) + 1
                if n > self.GENERATE_MAX_PASSED_OVER:
                    break
            if admitted or expired:
                gone = {id(i) for i in admitted + expired}
                with self._cond:
                    self._ready[:] = [i for i in self._ready
                                      if id(i) not in gone]
                    self._stats["jobs"] += len(admitted)
                    self._stats["shed"] += len(expired)
            for item in expired:
                passed_over.pop(item.job.id, None)
                self.worker._expire_job(item.job)
            for item in admitted:
                self._observe_dispatched(item, now)
            running.extend(admitted)
            try:
                prefilling = [i for i in running
                              if i.prepared.seq.prefilling]
                if prefilling:
                    eng.prefill_next(prefilling[0].prepared)
                decoding = [i for i in running
                            if not i.prepared.seq.prefilling
                            and not i.prepared.seq.done]
                if decoding:
                    eng.decode([i.prepared for i in decoding])
                    obs.BATCHES_DISPATCHED.inc()
                    with self._cond:
                        self._stats["batches"] += 1
                for item in [i for i in running if i.prepared.seq.done]:
                    eng.release(item.prepared)
                    running.remove(item)
                    self._awaiting_tokens[id(item.prepared)] = item
                finished = eng.collect(
                    drain=not prefilling and not decoding)
            except Exception:
                # The step failed, and with it the state of every sequence
                # it touched: each job ends by the usual failure path.
                for item in running + list(self._awaiting_tokens.values()):
                    if item in running:
                        eng.release(item.prepared)
                    self.worker._fail_job(item.job)
                running.clear()
                self._awaiting_tokens.clear()
                eng.drop_pending()
                return
            sp.set(admitted=len(admitted), decoding=len(decoding))
        for req in finished:
            item = self._awaiting_tokens.pop(id(req))
            self._complete(item, req.result())

    # -------------------------------------------------------------- driver
    def run(self) -> None:
        intakes = [
            threading.Thread(target=self._intake_loop,
                             name=f"sched-intake-{i}", daemon=True)
            for i in range(INTAKE_THREADS)
        ]
        completion = threading.Thread(target=self._completion_loop,
                                      name="sched-completion", daemon=True)
        for t in intakes:
            t.start()
        completion.start()
        try:
            if getattr(self.worker.engine, "generates", False):
                self._generate_loop()
            while not self.stop.is_set():
                batch, expired = self._next_batch()
                for item in expired:
                    with self._cond:
                        self._stats["shed"] += 1
                    # An expiry while tenant-budget-deferred is the
                    # fairness tier's shed, not plain overload — keep
                    # the classes separate in vmt_shed_total{reason}.
                    self.worker._expire_job(
                        item.job,
                        reason=("tenant_budget" if item.deferred
                                else "deadline"))
                if batch:
                    self._dispatch(batch)
        finally:
            self.stop.set()
            # Drain order matters: intake stops claiming first, THEN the
            # remaining ready jobs release (a racing intake thread could
            # otherwise re-park a job after its release), then the
            # completion queue finishes every already-forwarded result.
            for t in intakes:
                t.join()
            if self._executor is not None:
                # In-flight replica batches finish (their results are
                # already streaming into the completion queue) before the
                # sentinel below — a shutdown must never orphan a batch
                # between forward and persist.
                self._executor.shutdown(wait=True)
            with self._cond:
                leftovers = list(self._ready)
                self._ready.clear()
                self._stats["released"] += len(leftovers)
            abandoned_by = (getattr(self.worker.engine, "replica_id", None)
                            or "scheduler")
            for item in leftovers:
                self.worker.queue.release(item.job.id)
                obs.record_event("job_abandoned", job_id=item.job.id,
                                 trace_id=item.job.body.get("trace_id"),
                                 replica=abandoned_by)
                frame = {
                    "terminal": "Server draining; job requeued for the "
                                "next worker.",
                    "requeued": True,
                    "abandoned_by": abandoned_by,
                    "question": item.job.body.get("question", ""),
                }
                log_to_terminal(
                    self.worker.hub, item.job.body.get("socket_id", ""),
                    frame)
                # Requeue, not a terminal: coalesced followers stay
                # attached and hear the notice; the next worker's
                # terminal fan-out settles them.
                self.worker._fan_to_followers(item.job.body, [frame],
                                              final=False)
                self.worker._untrack(item.job.id)
            self._completions.put(None)
            completion.join()

    # --------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Scheduler state for the time-series sampler. ``*_total`` keys
        get ``_per_s`` rates derived by the sampler."""
        with self._cond:
            vals = {
                "sched_ready": float(len(self._ready)),
                "sched_window_ms": self._window_s * 1e3,
                "sched_batches_total": float(self._stats["batches"]),
                "sched_jobs_total": float(self._stats["jobs"]),
                "sched_solo_total": float(self._stats["solo"]),
                "sched_shed_total": float(self._stats["shed"]),
                "sched_released_total": float(self._stats["released"]),
                "sched_completion_backlog":
                    float(self._completions.qsize()),
            }
            # Per-tenant queue-wait (EWMA over dispatched items) and live
            # deficit credit — cardinality bounded by tenants actually
            # seen / holding backlog.
            for t, v in self._tenant_wait_ms.items():
                vals[f"sched_tenant_wait_ms.{t}"] = float(v)
            for t, v in self._deficits.items():
                vals[f"sched_tenant_deficit.{t}"] = float(v)
            return vals
