"""Continuous-batching scheduler: window policy, EDF packing, drain, chaos.

The pure pieces (``fire_decision``/``select_batch``/``adapt_window``) test
with fabricated items and explicit clocks — no threads, no sleeps. The
integration tests run the real three-stage data plane over the ``stack``
fixture and assert the serving invariants the scheduler must preserve:
every job one terminal state, clean drain on stop, nothing lost.
"""

import queue as queue_mod
import threading
import time

import pytest

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.resilience import Deadline
from vilbert_multitask_tpu.serve.queue import make_job_message
from vilbert_multitask_tpu.serve.scheduler import (
    ContinuousScheduler,
    ReadyItem,
    adapt_window,
    fire_decision,
    select_batch,
)


class _Req:
    """Stands in for PreparedRequest: only n_images matters to packing."""

    def __init__(self, n_images=1):
        self.n_images = n_images


def _item(n_images=1, deadline=None, enq_t=0.0, solo=False):
    return ReadyItem(None, 1, None if solo else _Req(n_images), 0.0,
                     deadline, enq_t, solo=solo)


# ------------------------------------------------------------ window policy
def test_fire_when_bucket_full():
    fire, wait = fire_decision(
        100.0, rows=8, oldest_enq_t=100.0, nearest_expiry=float("inf"),
        max_rows=8, window_s=0.05, near_deadline_s=0.25)
    assert fire and wait == 0.0


def test_fire_when_window_elapsed():
    fire, wait = fire_decision(
        100.051, rows=1, oldest_enq_t=100.0, nearest_expiry=float("inf"),
        max_rows=8, window_s=0.05, near_deadline_s=0.25)
    assert fire


def test_fire_when_member_near_deadline():
    # 0.1 s of slack < 0.25 s near-deadline bar: the EDF front must not
    # wait out the rest of the window.
    fire, wait = fire_decision(
        100.0, rows=1, oldest_enq_t=99.99, nearest_expiry=100.1,
        max_rows=8, window_s=0.05, near_deadline_s=0.25)
    assert fire


def test_wait_is_bounded_by_window_and_deadline():
    # Neither condition met: wait until whichever comes first — the window
    # closing (0.04 s away) or the nearest deadline entering the
    # near-deadline band (10 - 0.25 s away).
    fire, wait = fire_decision(
        100.01, rows=1, oldest_enq_t=100.0, nearest_expiry=110.0,
        max_rows=8, window_s=0.05, near_deadline_s=0.25)
    assert not fire
    assert wait == pytest.approx(0.04)
    # ...and the deadline band bounds it when nearer than the window.
    fire, wait = fire_decision(
        100.01, rows=1, oldest_enq_t=100.0, nearest_expiry=100.27,
        max_rows=8, window_s=0.05, near_deadline_s=0.25)
    assert not fire
    assert wait == pytest.approx(0.01)


def test_adapt_window_aimd_bounds():
    assert adapt_window(0.01, 1.0, lo=0.002, hi=0.05) == 0.02  # full: x2
    assert adapt_window(0.04, 1.0, lo=0.002, hi=0.05) == 0.05  # capped
    assert adapt_window(0.01, 0.5, lo=0.002, hi=0.05) == 0.005  # partial: /2
    assert adapt_window(0.003, 0.1, lo=0.002, hi=0.05) == 0.002  # floored


# -------------------------------------------------------------- EDF packing
def test_select_batch_orders_by_deadline():
    loose = _item(deadline=Deadline(1000.0))
    tight = _item(deadline=Deadline(50.0))
    none = _item(deadline=None)  # budgetless packs last
    batch, expired, rest = select_batch([none, loose, tight],
                                        time.perf_counter(), max_rows=8)
    assert batch == [tight, loose, none]
    assert expired == [] and rest == []


def test_select_batch_sheds_expired_and_respects_row_budget():
    dead = _item(deadline=Deadline(0.001))
    live = [_item(n_images=4, deadline=Deadline(1000.0 + i))
            for i in range(3)]
    now = time.perf_counter() + 1.0  # dead's budget is long gone
    batch, expired, rest = select_batch([live[2], dead, live[0], live[1]],
                                        now, max_rows=8)
    assert expired == [dead]
    # Row budget stops charging at 8: two 4-row members pack, the third
    # stays ready for the next fire.
    assert batch == [live[0], live[1]]
    assert rest == [live[2]]


def test_solo_items_pack_into_the_fire_order():
    solo = _item(deadline=Deadline(10.0), solo=True)
    packed = _item(deadline=Deadline(1000.0))
    batch, expired, rest = select_batch([packed, solo],
                                        time.perf_counter(), max_rows=8)
    assert batch == [solo, packed]  # EDF puts the tight solo first


# ------------------------------------------------- dispatcher (fake clock)
def test_next_batch_fires_on_elapsed_window_with_injected_clock(stack):
    s, hub, q, store, worker = stack
    now = [100.0]
    sched = ContinuousScheduler(worker, clock=lambda: now[0])
    win0 = sched._window_s
    sched._ready.extend([_item(enq_t=100.0, deadline=None),
                         _item(enq_t=100.0, deadline=None)])
    now[0] = 100.0 + win0 + 1e-4  # oldest member waited out the window
    batch, expired = sched._next_batch()
    assert len(batch) == 2 and not expired
    # Partial fill (2 of 8 rows) shrinks the window, floored at the min.
    assert sched._window_s == s.sched_window_min_s


def test_next_batch_grows_window_after_full_bucket(stack):
    s, hub, q, store, worker = stack
    now = [100.0]
    sched = ContinuousScheduler(worker, clock=lambda: now[0])
    win0 = sched._window_s
    max_rows = worker.engine.cfg.engine.max_batch_rows()
    sched._ready.extend(_item(enq_t=100.0) for _ in range(max_rows))
    batch, expired = sched._next_batch()  # bucket full: fires at once
    assert len(batch) == max_rows
    assert sched._window_s == min(win0 * 2, s.sched_window_max_s)


# --------------------------------------------------------------- integration
def _start(worker, stop):
    t = threading.Thread(
        target=worker.run_forever,
        kwargs={"poll_interval_s": 0.01, "stop_event": stop}, daemon=True)
    t.start()
    return t


def _drain_frames(sub):
    frames = []
    while True:
        try:
            frames.append(sub.get_nowait())
        except queue_mod.Empty:
            return frames


def test_scheduler_serves_mixed_burst_end_to_end(stack):
    s, hub, q, store, worker = stack
    sub = hub.subscribe("sched-e2e")
    burst = [(1, ["img_a.jpg"]), (12, ["img_a.jpg", "img_b.jpg"]),
             (7, ["img_a.jpg", "img_b.jpg"])]
    n = 12
    batches_before = obs.BATCHES_DISPATCHED.value()
    for i in range(n):
        task_id, imgs = burst[i % len(burst)]
        q.publish(make_job_message(
            imgs, f"sched q {i}", task_id, "sched-e2e",
            deadline=Deadline(60.0).to_wire(), published_unix=time.time()))
    stop = threading.Event()
    t = _start(worker, stop)
    results = 0
    deadline_t = time.monotonic() + 120
    while results < n and time.monotonic() < deadline_t:
        try:
            frame = sub.get(timeout=30)
        except queue_mod.Empty:
            break
        if "result" in frame:
            results += 1
    stop.set()
    t.join(timeout=30)
    assert not t.is_alive()
    assert results == n
    assert q.counts() == {}  # every job acked, nothing pending/dead
    assert worker.inflight_count() == 0
    assert worker.scheduler is None  # run_forever cleaned up after itself
    # The burst actually went through batched dispatches, and fills were
    # sampled per chunk.
    assert obs.BATCHES_DISPATCHED.value() > batches_before
    assert obs.BATCH_FILL.all_samples()


def test_scheduler_drain_on_stop_releases_cleanly(stack):
    """SIGTERM contract: in-flight batches finish, ready jobs release back
    to pending (requeued notice, no attempt charged), nothing is lost."""
    s, hub, q, store, worker = stack
    sub = hub.subscribe("sched-drain")
    n = 8
    for i in range(n):
        q.publish(make_job_message(["img_a.jpg"], f"drain q {i}", 1,
                                   "sched-drain",
                                   deadline=Deadline(60.0).to_wire()))
    stop = threading.Event()
    t = _start(worker, stop)
    # Stop as soon as the first result lands: some jobs are mid-pipeline.
    deadline_t = time.monotonic() + 120
    while time.monotonic() < deadline_t:
        try:
            if "result" in sub.get(timeout=30):
                break
        except queue_mod.Empty:
            break
    stop.set()
    t.join(timeout=30)
    assert not t.is_alive()
    frames = _drain_frames(sub)
    done = 1 + sum(1 for f in frames if "result" in f)
    counts = q.counts()
    # Every job is exactly one of: completed (acked) or back in pending —
    # never stuck inflight, never dead-lettered by the drain.
    assert counts.get("inflight", 0) == 0
    assert counts.get("dead", 0) == 0
    assert done + counts.get("pending", 0) == n
    assert worker.inflight_count() == 0
    # Released ready jobs told their client (requeued, not lost) and
    # charged no delivery attempt (release, not nack).
    requeued = [f for f in frames if f.get("requeued")]
    if counts.get("pending", 0):
        assert requeued or done + len(requeued) <= n


def test_scheduler_chaos_exactly_one_terminal(stack):
    """The soak's --chaos invariant at unit scale: under injected intake
    errors and dispatch delays, every job still reaches EXACTLY one
    terminal state (result, dead-letter error, or deadline push)."""
    from vilbert_multitask_tpu.resilience import (
        FaultPlan,
        FaultRule,
        clear_plan,
        install_plan,
    )

    s, hub, q, store, worker = stack
    sub = hub.subscribe("sched-chaos")
    n = 10
    install_plan(FaultPlan(7, [
        FaultRule("worker.intake", "error", rate=0.3),
        FaultRule("engine.dispatch", "delay", rate=0.3, delay_s=0.02),
    ]))
    try:
        for i in range(n):
            q.publish(make_job_message(
                ["img_a.jpg"], f"chaos q {i}", 1, "sched-chaos",
                deadline=Deadline(60.0).to_wire()))
        stop = threading.Event()
        t = _start(worker, stop)
        terminals = {}
        dups = []
        deadline_t = time.monotonic() + 120
        while len(terminals) < n and time.monotonic() < deadline_t:
            try:
                frame = sub.get(timeout=30)
            except queue_mod.Empty:
                break
            if "result" in frame:
                state, qq = "result", frame["result"]["question"]
            elif frame.get("deadline_exceeded"):
                state, qq = "deadline", frame.get("question", "")
            elif "error" in frame:
                state, qq = "dead", frame.get("question", "")
            else:
                continue
            if qq in terminals:
                dups.append((qq, state))
            else:
                terminals[qq] = state
        stop.set()
        t.join(timeout=30)
        assert not t.is_alive()
    finally:
        clear_plan()
    assert len(terminals) == n, f"lost jobs: {sorted(terminals)}"
    assert not dups, f"duplicate terminal states: {dups}"
    assert q.counts().get("inflight", 0) == 0
    assert worker.inflight_count() == 0


# ------------------------------------------------- intake wake-up (ISSUE 26)
# A publish in this process wakes a waiting intake thread; the timer is the
# fallback, on one thread at a time. Where a pass has to prove the signal,
# the poll interval is 5 s and the assertion far inside it.
def _job(i=0):
    return make_job_message(["img_a.jpg"], f"wake q {i}", 1, "wake")


class _Intakes:
    """``n`` intake threads of a scheduler over the real queue and the real
    ``ServeWorker._claim``, with the prep stubbed out (no engine work)."""

    def __init__(self, worker, n, poll_interval_s):
        worker._intake = lambda job: (job.id, _Req(), 0.0)
        self.q, self.stop = worker.queue, threading.Event()
        self.sched = ContinuousScheduler(worker, stop_event=self.stop,
                                         poll_interval_s=poll_interval_s)
        self.empty0 = obs.INTAKE_EMPTY_POLLS.value()
        self.claims0 = self.claims()
        self.threads = [threading.Thread(target=self.sched._intake_pump,
                                         daemon=True) for _ in range(n)]
        for t in self.threads:
            t.start()

    @staticmethod
    def claims():
        return (obs.INTAKE_CLAIMS_SIGNALLED.value(),
                obs.INTAKE_CLAIMS_UNSIGNALLED.value())

    def new_claims(self):
        return tuple(b - a for a, b in zip(self.claims0, self.claims()))

    def empty_polls(self):
        return obs.INTAKE_EMPTY_POLLS.value() - self.empty0

    def all_waiting(self, timeout=10.0):
        """True once every thread has come back from its first, empty claim
        and waits on the queue."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            with self.q._wake:
                if self.q._waiting == len(self.threads):
                    return True
            time.sleep(0.005)
        return False

    def ready(self, n, timeout):
        """The ready set's job ids once it holds ``n`` (or at the timeout)."""
        with self.sched._cond:
            self.sched._cond.wait_for(
                lambda: len(self.sched._ready) >= n, timeout)
            return sorted(i.job.id for i in self.sched._ready)

    def close(self, timeout=10.0):
        """Stop, and spare the test the rest of a long poll interval: a
        signal a waiter takes it to the top of its loop."""
        self.stop.set()
        for _ in self.threads:
            self.q.publish(_job())
        for t in self.threads:
            t.join(timeout)
        return not any(t.is_alive() for t in self.threads)


def test_publish_wakes_a_waiting_intake(stack):
    s, hub, q, store, worker = stack
    intakes = _Intakes(worker, 4, poll_interval_s=5.0)
    try:
        ids = []
        for i in range(3):
            # One at a time: a thread still busy with a job claims the next
            # straight after it, with no need of a signal.
            assert intakes.all_waiting()
            ids.append(q.publish(_job(i)))
            assert intakes.ready(i + 1, timeout=2.0) == ids
        assert intakes.all_waiting()
        # Each came in on a thread the signal woke, none by the timer. The
        # empty claims are the threads' first: a claim that saw nothing
        # behind its job is not followed by one that finds the same.
        assert intakes.new_claims() == (3, 0)
        assert intakes.empty_polls() == 4
    finally:
        assert intakes.close()


def test_publish_between_empty_claim_and_wait_is_not_slept_through(stack):
    s, hub, q, store, worker = stack
    claim = worker._claim
    published = []

    def claim_then_publish():
        job = claim()
        if job is None and not published:
            published.append(q.publish(_job()))  # nobody waits yet
        return job

    worker._claim = claim_then_publish
    intakes = _Intakes(worker, 1, poll_interval_s=5.0)
    try:
        assert intakes.ready(1, timeout=2.0) == published
        assert intakes.new_claims() == (1, 0)
    finally:
        assert intakes.close()


def test_a_backlog_is_drained_without_signal_or_timer(stack):
    """Jobs that were there before anybody waited raise no signal: a claim
    says whether another is behind it, and the thread claims on."""
    s, hub, q, store, worker = stack
    ids = [q.publish(_job(i)) for i in range(6)]
    intakes = _Intakes(worker, 2, poll_interval_s=5.0)
    try:
        assert intakes.ready(6, timeout=2.0) == ids
        assert intakes.new_claims() == (0, 6)
    finally:
        assert intakes.close()


def test_claim_says_whether_another_job_is_behind(stack):
    q = stack[2]
    first, second, third = (q.publish(_job(i)) for i in range(3))
    assert q.claim(exclude=[second, third]).more is False
    q.release(first)
    assert [q.claim().more, q.claim().more, q.claim().more] == [
        True, True, False]
    assert q.claim() is None


def test_wait_for_work_returns_at_once_when_the_number_has_moved(stack):
    q = stack[2]
    seq = q.work_seq()
    assert q.claim() is None
    q.publish(_job())
    signalled, now = q.wait_for_work(seq, 5.0)
    assert signalled and now == seq + 1
    # The number is spent with the claim it prompted: nothing new, no signal.
    assert q.wait_for_work(now, 0.01) == (False, now)


@pytest.mark.parametrize("how", ["publish", "nack", "release"])
def test_whatever_makes_a_job_deliverable_wakes_a_waiter(stack, how):
    q = stack[2]
    if how != "publish":
        q.publish(_job())
        claimed = q.claim()
    seq = q.work_seq()
    woken = []
    t = threading.Thread(
        target=lambda: woken.append(q.wait_for_work(seq, 5.0)), daemon=True)
    t.start()
    while True:  # until it waits; the sequence number covers the gap anyway
        with q._wake:
            if q._waiting:
                break
        time.sleep(0.002)
    if how == "publish":
        q.publish(_job())
    elif how == "nack":
        assert q.nack(claimed.id) == "pending"
    else:
        q.release(claimed.id)
    t.join(2.0)
    assert not t.is_alive() and woken == [(True, seq + 1)]


def test_what_makes_nothing_deliverable_wakes_nobody(stack):
    s, hub, q, store, worker = stack
    q.max_delivery_attempts = 1
    q.publish(_job())
    job = q.claim()
    seq = q.work_seq()
    assert q.nack(job.id) == "dead"  # dead-lettered, not requeued
    q.release(job.id)                # no longer in flight: nothing to do
    q.ack(job.id)
    assert q.work_seq() == seq


def test_one_signal_wakes_one_waiter(stack):
    """Three wait, one job comes: one of them is told to claim. The others
    time out unsignalled, and the number they are handed is current, so
    their next wait does not end on the job that was not theirs."""
    q = stack[2]
    seq = q.work_seq()
    out = []
    threads = [threading.Thread(
        target=lambda: out.append(q.wait_for_work(seq, 0.5)), daemon=True)
        for _ in range(3)]
    for t in threads:
        t.start()
    while True:
        with q._wake:
            if q._waiting == 3:
                break
        time.sleep(0.002)
    q.publish(_job())
    for t in threads:
        t.join(5.0)
    assert sorted(out) == [(False, seq + 1)] * 2 + [(True, seq + 1)]
    assert q.wait_for_work(seq + 1, 0.01) == (False, seq + 1)


def test_stop_ends_every_waiter_within_the_poll_interval(stack):
    s, hub, q, store, worker = stack
    intakes = _Intakes(worker, 4, poll_interval_s=0.5)
    assert intakes.all_waiting()
    intakes.stop.set()  # and nothing else: no signal, no publish
    t0 = time.monotonic()
    for t in intakes.threads:
        t.join(5.0)
    assert not any(t.is_alive() for t in intakes.threads)
    # Generous for a loaded box, and still far from "never": each thread
    # looks at stop once a poll interval.
    assert time.monotonic() - t0 < 0.5 + 2.0
    assert intakes.ready(0, 0.0) == []


@pytest.mark.parametrize("how", ["second_queue_object", "visibility_timeout"])
def test_the_timed_claim_finds_what_raises_no_signal(stack, how):
    from vilbert_multitask_tpu.serve.queue import DurableQueue

    s, hub, q, store, worker = stack
    if how == "visibility_timeout":
        # A consumer that claimed and died: in flight, nobody will ack it.
        expected = q.publish(_job())
        assert q.claim().id == expected
        q.visibility_timeout_s = 0.3
    intakes = _Intakes(worker, 4, poll_interval_s=0.1)
    try:
        assert intakes.all_waiting()
        seq = q.work_seq()
        if how == "second_queue_object":
            # What another process does: its own object on the same file.
            expected = DurableQueue(s.queue_db_path).publish(_job())
        assert intakes.ready(1, timeout=10.0) == [expected]
        assert q.work_seq() == seq  # no signal was raised for it
        assert intakes.new_claims() == (0, 1)
    finally:
        assert intakes.close()


def test_idle_intakes_poll_like_one_thread_and_count_every_claim(stack):
    s, hub, q, store, worker = stack
    n, poll, jobs = 4, 0.05, 5
    intakes = _Intakes(worker, n, poll_interval_s=poll)
    try:
        assert intakes.all_waiting()
        t0 = time.monotonic()
        ids = []
        for i in range(jobs):
            time.sleep(0.2)
            ids.append(q.publish(_job(i)))
        assert intakes.ready(jobs, timeout=5.0) == ids
        elapsed = time.monotonic() - t0
        empty, claims = intakes.empty_polls(), intakes.new_claims()
    finally:
        assert intakes.close()
    # One timed claim a poll interval whatever the number of threads (four
    # sleepers of their own made n of them), plus the n first claims and at
    # most one lost race a job. A loaded box polls late, never more often.
    assert n < empty <= elapsed / poll + n + jobs + 1
    assert empty < 0.6 * n * elapsed / poll
    assert sum(claims) == jobs
