"""Where a store call gets its sqlite connection: one kept per file, lent.

The five sqlite stores (``serve/queue.py``, ``serve/db.py``,
``serve/resultcache.py``, ``obs/fleet.py``, ``obs/tracestore.py``) run every
method as ``with self._conn() as c:``. Opening a connection costs more than
the statement it carries (connect + PRAGMAs 1.45 ms, close 0.30 ms, the
statement 0.05-1.0 ms on the chip host; PERF.md, PR 26), and a connection
kept per *thread* does not help where it matters: the HTTP server gives
every request a thread of its own, so a handler thread would open its
connection once and use it once. The kept connections therefore belong to
the store object and outlive the threads: a call borrows the one returned
last, or opens one when none is idle, and leaving the ``with`` block commits
(or rolls back on an exception) exactly as ``sqlite3.Connection.__exit__``
does and hands the connection back. One thread uses a connection at a time,
any thread in turn.

A kept connection also takes away what used to hand the file's write lock
from one process to the next: sqlite does not queue for that lock, a
connection that finds it taken sleeps (1, 2, 5, 10 ... 100 ms) and looks
again, and a process that no longer spends a millisecond opening its next
connection has the lock back within microseconds, every time. So write
transactions queue before they start, at exclusive ``flock``s on the
sidecars ``<file>-line`` and ``<file>-turn``, where the kernel wakes the
waiter the moment the holder lets go: ``_LentConnection`` asks its store for
the turn in front of the first statement of a block that can write and gives
it up after the block's commit or rollback. Reads never touch it.

Lives under ``obs/`` because that is the one package both ``serve/`` and
``obs/`` may import (pyproject ``[tool.vmtlint.layers]``).
"""

from __future__ import annotations

import fcntl
import os
import sqlite3
import threading
from typing import List, Optional

from vilbert_multitask_tpu.obs.instruments import REGISTRY

# Idle connections a store keeps; one more coming back is closed instead.
# A handler thread borrows its connection before it stands in line to write,
# so a store can have as many out as there are callers: under
# base.saturated's 64 the most that were idle at once were 50 on ``cache``,
# 20 on ``queue``, 2, 2 and 1 on the others (PERF.md section 6, PR 31).
MAX_IDLE = 64

_OPENED = REGISTRY.counter(
    "vmt_store_conn_opened_total",
    "Store calls that opened a new sqlite connection (none was idle).",
    labelnames=("store",))
_REUSED = REGISTRY.counter(
    "vmt_store_conn_reused_total",
    "Store calls that borrowed a kept sqlite connection.",
    labelnames=("store",))


# First words of the statements that only read: everything else a block
# starts with (BEGIN IMMEDIATE, INSERT, UPDATE, DELETE, DDL) can take the
# file's write lock, and queues at the gate first.
_READS = ("SELECT", "PRAGMA")


class _LentConnection(sqlite3.Connection):
    """What ``_conn()`` hands out. ``__exit__`` is the base class's commit
    or rollback, then the way home; ``home`` is set only while lent, so an
    idle connection holds no reference to its store."""

    home: Optional["SqliteStore"] = None
    _has_turn = False  # this block holds its store's turn to write

    def _before(self, writes: bool) -> None:
        if writes and not self._has_turn and self.home is not None:
            self.home._wait_for_turn()
            self._has_turn = True

    def execute(self, sql, *args):
        self._before(not sql.lstrip().upper().startswith(_READS))
        return super().execute(sql, *args)

    def executemany(self, sql, *args):
        self._before(True)
        return super().executemany(sql, *args)

    def executescript(self, script):
        self._before(True)
        return super().executescript(script)

    def __exit__(self, exc_type, exc, tb):
        home, self.home = self.home, None
        clean = False
        try:
            super().__exit__(exc_type, exc, tb)
            # Only a connection that is out of its transaction, and whose
            # block sqlite did not fail, is worth keeping: anything else
            # could hand the next caller a half-open transaction.
            clean = not (self.in_transaction
                         or isinstance(exc, sqlite3.Error))
        finally:
            if self._has_turn:  # after the commit: the next writer's
                self._has_turn = False
                home._give_up_turn()
            if not (clean and home is not None and home._take_back(self)):
                self.close()
        return False


class SqliteStore:
    """Base of the stores: the file's path and its idle connections."""

    label = ""  # the counters' ``store``: queue, results, cache, fleet, traces
    # True leaves a connection at sqlite's default ``synchronous`` (FULL:
    # every commit syncs the WAL), which is what the audit rows of
    # serve/db.py are written under; the others say NORMAL.
    full_sync = False

    def __init__(self, path: str):
        self.path = path
        self._idle: List[sqlite3.Connection] = []
        self._idle_lock = threading.Lock()
        # The queue in front of the file's write lock (module docstring):
        # this store's writers one at a time, and that one against every
        # other store object and process through the two sidecars.
        self._write_lock = threading.Lock()
        self._gate = None  # (line, turn), opened at the first write
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)

    def _conn(self) -> sqlite3.Connection:
        """A connection for one ``with`` block: the idle one returned last
        (its page and statement caches are the warmest), else a new one."""
        with self._idle_lock:
            conn = self._idle.pop() if self._idle else None
        if conn is None:
            conn = sqlite3.connect(self.path, timeout=30.0,
                                   check_same_thread=False,
                                   factory=_LentConnection)
            conn.execute("PRAGMA journal_mode=WAL")
            if not self.full_sync:
                conn.execute("PRAGMA synchronous=NORMAL")
            _OPENED.inc(store=self.label)
        else:
            _REUSED.inc(store=self.label)
        conn.home = self
        return conn

    def _wait_for_turn(self) -> None:
        """Two locks, because one is not a queue: whoever lets go of a lone
        ``flock`` and asks again has it back before the waiter it woke has
        run. So the turn is asked for only from the one place in line, and
        that place is given up only with the turn in hand: the writer that
        comes straight back finds the line taken by whoever waited, and
        sleeps in the kernel like everybody else."""
        self._write_lock.acquire()  # every wait here is without the GIL
        in_turn = False
        try:
            if self._gate is None:
                self._gate = tuple(open(self.path + suffix, "ab")
                                   for suffix in ("-line", "-turn"))
            line, turn = self._gate
            fcntl.flock(line, fcntl.LOCK_EX)
            fcntl.flock(turn, fcntl.LOCK_EX)
            fcntl.flock(line, fcntl.LOCK_UN)
            in_turn = True
        finally:
            if not in_turn:
                self._write_lock.release()

    def _give_up_turn(self) -> None:
        fcntl.flock(self._gate[1], fcntl.LOCK_UN)
        self._write_lock.release()

    def _take_back(self, conn: sqlite3.Connection) -> bool:
        with self._idle_lock:
            if len(self._idle) < MAX_IDLE:
                self._idle.append(conn)
                return True
        return False

    def close(self) -> None:
        """Close the idle connections (closing a file's last connection
        checkpoints its WAL) and the sidecars. A call made afterwards opens
        anew."""
        with self._idle_lock:
            idle, self._idle = self._idle, []
        for conn in idle:
            conn.close()
        with self._write_lock:  # behind a writer that is in its block
            gate, self._gate = self._gate, None
        for sidecar in gate or ():
            sidecar.close()
