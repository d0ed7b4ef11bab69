"""Where a store call gets its sqlite connection (obs/sqlitestore.py).

The five stores keep their connections and lend one for each ``with
self._conn() as c:`` block. What has to hold: a connection is opened once
and then reused by whichever thread calls next (a handler thread of the
HTTP server lives for one request, so "per thread" would not be reuse at
all); two blocks open at once never share one; a failed block leaves
nothing behind for the next borrower; a kept connection never serves a
stale snapshot; and each store's durability PRAGMAs are what they were.
And since a kept connection re-takes the file's write lock within
microseconds: writers queue for their turn (the two ``flock``ed sidecars),
so that two processes writing back to back hand the file to each other.
"""

import dataclasses
import fcntl
import os
import sqlite3
import subprocess
import sys
import threading
import time
from typing import Callable

import pytest

from vilbert_multitask_tpu.obs import sqlitestore
from vilbert_multitask_tpu.obs.attrib import JobCost
from vilbert_multitask_tpu.obs.fleet import FleetSpine
from vilbert_multitask_tpu.obs.identity import mint_identity
from vilbert_multitask_tpu.obs.instruments import Registry
from vilbert_multitask_tpu.obs.trace import Tracer
from vilbert_multitask_tpu.obs.tracestore import TraceStore
from vilbert_multitask_tpu.serve.db import ResultStore
from vilbert_multitask_tpu.serve.queue import DurableQueue
from vilbert_multitask_tpu.serve.resultcache import ResultCache


@dataclasses.dataclass
class Kind:
    """One store: how to build it on a path, one committed write that the
    number ``i`` tells apart, and a read that counts those writes."""
    label: str
    synchronous: int  # PRAGMA synchronous: 1 NORMAL, 2 FULL
    make: Callable
    write: Callable
    read: Callable


def _trace_write(store, i):
    store.offer(JobCost(trace_id=f"t{i}-{store.ident}", task="vqa",
                        verdict="error", stages={"forward": 1.0}))
    store.flush()


KINDS = [
    Kind("queue", 1, DurableQueue,
         lambda s, i: s.publish({"i": i}),
         lambda s: s.counts().get("pending", 0)),
    Kind("results", 2, ResultStore,
         lambda s, i: s.create_question(1, f"q{i}", [], "sock"),
         lambda s: len(s.recent(limit=10_000))),
    Kind("cache", 1, lambda path: ResultCache(path, fingerprint="fp"),
         lambda s, i: s.admit(f"key{i}-{id(s)}", socket_id="sock"),
         lambda s: int(s.stats()["cache_leading_rows"])),
    # A flush upserts this identity's heartbeat row: the payload carries
    # how many writes it has made, and the read sums that over the peers.
    Kind("fleet", 1,
         lambda path: FleetSpine(path, mint_identity("test"),
                                 registry=Registry(), tracer=Tracer()),
         lambda s, i: s.flush({"n": i + 1}),
         lambda s: sum(p["payload"].get("n", 0)
                       for p in s.peers(include_stale=True))),
    Kind("traces", 1,
         lambda path: TraceStore(path, mint_identity("test").ident),
         _trace_write,
         lambda s: len(s.list(limit=10_000))),
]


@pytest.fixture(params=KINDS, ids=lambda k: k.label)
def kind(request):
    return request.param


@pytest.fixture()
def store(kind, tmp_path):
    s = kind.make(str(tmp_path / f"{kind.label}.sqlite3"))
    yield s
    s.close()


def watch(store):
    """Record every connection ``store._conn()`` lends from here on (the
    list keeps them alive, so two entries are one connection exactly when
    they are the same object)."""
    lent, lend = [], store._conn

    def _conn():
        conn = lend()
        lent.append(conn)
        return conn

    store._conn = _conn
    return lent


def distinct(lent):
    return len({id(c) for c in lent})


def test_label_and_pragmas_are_each_stores_own(kind, store):
    assert store.label == kind.label
    for _ in range(2):  # the connection the constructor opened, reused
        with store._conn() as c:
            assert c.execute("PRAGMA synchronous").fetchone()[0] \
                == kind.synchronous
            assert c.execute("PRAGMA journal_mode").fetchone()[0] == "wal"
    with store._conn() as c, store._conn() as fresh:  # and a new one
        assert fresh is not c
        assert fresh.execute("PRAGMA synchronous").fetchone()[0] \
            == kind.synchronous
        assert fresh.execute("PRAGMA journal_mode").fetchone()[0] == "wal"


def test_calls_on_one_thread_use_the_one_connection(kind, store):
    lent = watch(store)
    for i in range(12):
        kind.write(store, i)
        assert kind.read(store) == i + 1
    assert len(lent) >= 24 and distinct(lent) == 1
    # ...and it is the one the constructor opened: none was opened since.
    assert len(store._idle) == 1 and store._idle[0] is lent[0]


def test_threads_that_live_for_one_call_share_one_connection(kind, store):
    """The handler-thread case: eight threads, one call each, one after
    the other. A connection per thread would open eight."""
    lent = watch(store)
    for i in range(8):
        t = threading.Thread(target=kind.write, args=(store, i))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    assert kind.read(store) == 8
    assert distinct(lent) == 1


def test_two_blocks_at_once_hold_different_connections(store):
    inside, leave = threading.Barrier(3), threading.Event()
    held = []

    def block():
        with store._conn() as c:
            held.append(c)
            inside.wait(timeout=30)
            leave.wait(timeout=30)

    threads = [threading.Thread(target=block) for _ in range(2)]
    for t in threads:
        t.start()
    inside.wait(timeout=30)  # both are inside their blocks now
    assert len(held) == 2 and held[0] is not held[1]
    assert store._idle == []
    leave.set()
    for t in threads:
        t.join(timeout=30)
    assert sorted(map(id, store._idle)) == sorted(map(id, held))


def test_a_block_that_raises_rolls_back_and_leaves_nothing_behind(store):
    # ``user_version`` is a number in the file's header that every store
    # leaves at 0 and a transaction writes and rolls back like any row.
    with pytest.raises(RuntimeError, match="mid-block"):
        with store._conn() as failed:
            failed.execute("BEGIN IMMEDIATE")
            failed.execute("PRAGMA user_version = 7")
            assert failed.in_transaction
            raise RuntimeError("mid-block")
    with store._conn() as c:
        assert c is failed  # rolled back clean, so it was kept
        assert not c.in_transaction
        assert c.execute("PRAGMA user_version").fetchone()[0] == 0
        c.execute("BEGIN IMMEDIATE")
        c.execute("PRAGMA user_version = 8")
    with store._conn() as c:
        assert c.execute("PRAGMA user_version").fetchone()[0] == 8


def test_a_block_sqlite_failed_loses_its_connection(store):
    with pytest.raises(sqlite3.OperationalError):
        with store._conn() as failed:
            failed.execute("INSERT INTO no_such_table VALUES (1)")
    assert store._idle == []
    with pytest.raises(sqlite3.ProgrammingError):  # closed, not pooled
        failed.execute("SELECT 1")
    with store._conn() as c:
        assert c is not failed and not c.in_transaction


def test_commit_through_one_object_is_read_at_once_through_another(
        kind, tmp_path):
    """No stale snapshot: the reader's kept connection has already looked
    at the file before each write lands."""
    path = str(tmp_path / "shared.sqlite3")
    writer, reader = kind.make(path), kind.make(path)
    try:
        for i in range(5):
            assert kind.read(reader) == i
            kind.write(writer, i)
            assert kind.read(reader) == i + 1
            assert kind.read(writer) == i + 1
    finally:
        writer.close()
        reader.close()


def _queue_reads(q):
    job = q.claim()
    q.nack(job.id)
    q.counts(), q.oldest_pending_age_s(), q.inflight_claims()
    q.dead_jobs(), q.pop_dead_letters()


def _results_reads(r):
    qa = r.create_question(1, "again", [], "sock", queue_job_id=7)
    assert r.create_question(1, "again", [], "sock", queue_job_id=7) == qa
    r.get_task(1), r.list_tasks(), r.get_question(qa), r.recent()


def _cache_reads(c):
    c.admit("again", socket_id="a")
    assert c.admit("again", socket_id="b")[0] == "attach"
    c.peek_followers("again"), c.stats()
    c.complete("again", {"answer": 1})
    assert c.admit("again", socket_id="c")[0] == "hit"


def _fleet_reads(f):
    f.peers(include_stale=True), f.health(), f.render_prometheus()
    f.timeseries(), f.chrome_trace(), f.snapshot()


def _traces_reads(t):
    (row,) = t.list(limit=1)
    t.get(row["trace_id"]), t.get("no-such-trace")


READS = {"queue": _queue_reads, "results": _results_reads,
         "cache": _cache_reads, "fleet": _fleet_reads,
         "traces": _traces_reads}


def test_no_call_leaves_a_statement_open_on_its_kept_connection(kind, store):
    """An unfinished cursor on an idle connection would pin a WAL snapshot:
    stale reads for its next borrower and a checkpoint that never
    completes. Every method of every store, then a checkpoint that must
    find no reader in its way."""
    for i in range(3):
        kind.write(store, i)
    READS[kind.label](store)
    assert len(store._idle) == 1
    raw = sqlite3.connect(store.path)
    try:
        raw.execute("PRAGMA user_version = 1")  # something in the WAL
        busy, _, _ = raw.execute("PRAGMA wal_checkpoint(TRUNCATE)").fetchone()
    finally:
        raw.close()
    assert busy == 0


def test_close_closes_every_idle_connection_and_a_later_call_works(
        kind, store):
    kind.write(store, 0)
    with store._conn() as a, store._conn() as b:
        pass
    idle = list(store._idle)
    assert len(idle) == 2 and {id(a), id(b)} == set(map(id, idle))
    store.close()
    assert store._idle == []
    for conn in idle:
        with pytest.raises(sqlite3.ProgrammingError):
            conn.execute("SELECT 1")
    assert kind.read(store) == 1  # opens anew
    kind.write(store, 1)
    assert kind.read(store) == 2
    assert len(store._idle) == 1 and store._idle[0] not in idle


def test_the_two_counters_add_up_to_the_calls_made(kind, store):
    # A label of the test's own: the registry is the process's, and a
    # sampler thread some other test left running must not count here.
    store.label = f"{kind.label}.counted"
    opened = lambda: sqlitestore._OPENED.value(store=store.label)
    reused = lambda: sqlitestore._REUSED.value(store=store.label)
    lent = watch(store)
    for i in range(5):
        kind.write(store, i)
        kind.read(store)
    assert (opened(), reused()) == (0, len(lent))
    with store._conn(), store._conn(), store._conn():
        pass  # one idle, two more to open
    assert (opened(), reused()) == (2, len(lent) - 2)
    store.close()
    kind.read(store)
    assert opened() == 3 and opened() + reused() == len(lent)


def test_idle_connections_beyond_the_cap_are_closed_on_return(
        store, monkeypatch):
    monkeypatch.setattr(sqlitestore, "MAX_IDLE", 2)
    with store._conn() as a, store._conn() as b, store._conn() as c:
        pass
    # Blocks unwind innermost first: c and b came home, a found no room.
    assert store._idle == [c, b]
    with pytest.raises(sqlite3.ProgrammingError):
        a.execute("SELECT 1")


def test_a_read_never_queues_and_a_write_gives_its_turn_back(kind, store):
    store.close()  # the constructor wrote the schema
    assert store._gate is None
    assert kind.read(store) == 0
    assert store._gate is None  # a reader never opens the sidecars
    kind.write(store, 0)
    line, turn = store._gate
    assert (line.name, turn.name) == (store.path + "-line",
                                      store.path + "-turn")
    (conn,) = store._idle
    assert not conn._has_turn and not store._write_lock.locked()
    store.close()
    assert store._gate is None and line.closed and turn.closed
    kind.write(store, 1)  # ... and a later write opens them anew
    assert kind.read(store) == 2


@pytest.mark.parametrize("failure", [RuntimeError, sqlite3.OperationalError],
                         ids=["block_raised", "sqlite_failed"])
def test_a_failed_write_block_gives_its_turn_back(store, failure):
    """Kept (rolled back clean) or dropped (sqlite failed it), the
    connection must not leave with the turn: the next writer would wait
    for ever, ``flock`` has no timeout."""
    with pytest.raises(failure):
        with store._conn() as failed:
            failed.execute("BEGIN IMMEDIATE")
            assert failed._has_turn
            if failure is RuntimeError:
                raise RuntimeError("mid-block")
            failed.execute("INSERT INTO no_such_table VALUES (1)")
    assert not failed._has_turn and not store._write_lock.locked()
    done = threading.Event()

    def write():
        with store._conn() as c:
            c.execute("BEGIN IMMEDIATE")
            c.execute("PRAGMA user_version = 3")
        done.set()

    t = threading.Thread(target=write, daemon=True)
    t.start()
    assert done.wait(timeout=30)
    t.join(timeout=30)


def test_a_writer_waits_for_its_turn_and_a_reader_does_not(kind, store):
    kind.write(store, 0)
    order, wrote = [], threading.Event()

    def write():
        kind.write(store, 1)
        order.append("second writer")
        wrote.set()

    with store._conn() as c:
        c.execute("BEGIN IMMEDIATE")
        t = threading.Thread(target=write, daemon=True)
        t.start()
        assert kind.read(store) == 1  # reads pass: WAL, and no queue
        assert not wrote.wait(timeout=0.3)  # the writer is in line
        order.append("first writer")
    assert wrote.wait(timeout=30)
    t.join(timeout=30)
    assert order == ["first writer", "second writer"]
    assert kind.read(store) == 2


# A second process, for the test below: one publish when told to.
_WAITER_SRC = r"""
import sys
from vilbert_multitask_tpu.serve.queue import DurableQueue

q = DurableQueue(sys.argv[1])
print("READY", flush=True)
sys.stdin.readline()
q.publish({"who": "b"})
"""


@pytest.mark.parametrize("waiter", ["thread", "process"])
def test_a_waiting_writer_gets_the_turn_before_the_one_that_comes_back(
        waiter, tmp_path):
    """What a connection per call used to give by accident (a millisecond
    between two writes of one process, in which a peer asleep in sqlite's
    busy handler could wake) the queue in front of the write lock gives on
    purpose. ``a`` writes, and while it does ``b`` arrives; ``a`` then
    writes twenty more times back to back on its kept connection. ``b``'s
    row is the second, whoever the scheduler favours: ``a`` finds the line
    taken. (Without the queue ``b`` sleeps its first millisecond while
    ``a`` is through most of the twenty.)"""
    db = str(tmp_path / "queue.sqlite3")
    q = DurableQueue(db)
    if waiter == "thread":
        peer = DurableQueue(db)
        t = threading.Thread(target=peer.publish, args=({"who": "b"},))
        arrive, finish = t.start, lambda: t.join(timeout=60)
    else:
        proc = subprocess.Popen(
            [sys.executable, "-c", _WAITER_SRC, db],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert proc.stdout.readline().strip() == "READY"
        arrive = lambda: (proc.stdin.write("go\n"), proc.stdin.flush())
        finish = lambda: proc.wait(timeout=60)
    line = os.open(db + "-line", os.O_RDWR)
    try:
        with q._conn() as c:
            c.execute("BEGIN IMMEDIATE")
            arrive()
            for _ in range(60_000):  # until b stands in line
                try:
                    fcntl.flock(line, fcntl.LOCK_EX | fcntl.LOCK_NB)
                except BlockingIOError:
                    break
                fcntl.flock(line, fcntl.LOCK_UN)
                time.sleep(0.001)
            else:
                pytest.fail("the second writer never stood in line")
            c.execute("INSERT INTO jobs (queue, body, created_at) "
                      "VALUES ('q', '{\"who\": \"a\"}', 0)")
        for _ in range(20):
            q.publish({"who": "a"})
        finish()
        with q._conn() as c:
            won = [body[9] for (body,) in c.execute(
                "SELECT body FROM jobs ORDER BY id")]  # {"who": "a"}
    finally:
        os.close(line)
        q.close()
    assert won == ["a", "b"] + ["a"] * 20


def test_many_threads_at_once_never_share_a_connection(tmp_path):
    """More threads than cores on one queue, the interpreter switching
    threads as often as it can: a connection lent twice at once, a job
    claimed twice or a lost publish would each show."""
    q = DurableQueue(str(tmp_path / "q.sqlite3"))
    threads_n, jobs_each = 24, 20
    guard, in_use, clashes, claimed = threading.Lock(), set(), [], []
    lend, take_back = q._conn, q._take_back

    def _conn():
        conn = lend()
        with guard:
            if id(conn) in in_use:
                clashes.append(conn)
            in_use.add(id(conn))
        return conn

    def _take_back(conn):
        with guard:
            in_use.discard(id(conn))
        return take_back(conn)

    q._conn, q._take_back = _conn, _take_back

    def work(n):
        for i in range(jobs_each):
            q.publish({"thread": n, "i": i})
        while (job := q.claim()) is not None:
            with guard:
                claimed.append(job.id)
            q.ack(job.id)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(n,))
                   for n in range(threads_n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert clashes == [] and in_use == set()
    assert len(claimed) == len(set(claimed)) == threads_n * jobs_each
    assert q.counts() == {}
    assert 1 <= len(q._idle) <= threads_n
    q.close()


def test_serveapp_stop_closes_the_stores(tiny_framework_cfg, engine,
                                         tmp_path):
    from vilbert_multitask_tpu.serve.app import ServeApp

    cfg = dataclasses.replace(
        tiny_framework_cfg,
        serving=dataclasses.replace(
            tiny_framework_cfg.serving,
            queue_db_path=str(tmp_path / "q.sqlite3"),
            results_db_path=str(tmp_path / "r.sqlite3"),
            media_root=str(tmp_path / "media")))
    app = ServeApp(cfg, engine=engine)
    stores = (app.queue, app.store, app.cache, app.fleet, app.tracestore)
    assert sorted(s.label for s in stores) == [
        "cache", "fleet", "queue", "results", "traces"]
    assert all(len(s._idle) == 1 for s in stores)
    app.stop()
    assert all(s._idle == [] for s in stores)
    assert app.queue.counts() == {}  # a call after close() opens anew
