"""The clients of the server, in a process of their own.

    python benchmark/harness/loadgen.py        (commands on stdin, as JSON)

Started by ``run.py`` before it touches JAX; imports neither JAX nor the
program. It is what a browser is to the server: HTTP ``POST /`` submits and
websockets that receive every frame for their socket id. Times are
``CLOCK_MONOTONIC`` (``time.monotonic``), the same clock in every process of
the machine.

Commands, one JSON object per line; each is answered by one line:

  {"cmd": "connect", "http_port": .., "ws_port": .., "sockets": n}
  {"cmd": "run", "schedule": path, "out": path, "t0": monotonic seconds,
   "threads": n, "grace_s": s}
  {"cmd": "quit"}

``run`` reads a schedule (``harness/arrivals.py``), sends it and writes one
stamp line per request to ``out``. A request's ``body`` is POSTed as it is,
with the ``socket_id`` of its session's websocket added; the frame whose
``result[key_field]`` equals the request's ``key`` is its answer, whatever
else either holds. In an open-loop schedule request ``r`` is
sent at ``t0 + r.due`` (sleep, then spin the last millisecond) whatever
became of the others; in a closed-loop one each client sends its next
request when the last is answered, from ``t0 - warm_seconds`` (the callers'
first sends ``STAGGER_S`` apart) until ``t0 + seconds``. Then it waits up to
``grace_s`` for frames still due.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time

SPIN_S = 0.001
# Closed-loop callers begin this far apart: a batch job's workers do not
# open their first connections in one millisecond, and the server's listen
# queue (the standard library's 5) drops what a burst of 64 overfills.
STAGGER_S = 0.005


class Clients:
    def __init__(self, http_port: int, ws_port: int, sockets: int):
        from websockets.sync.client import connect

        self.http_port = http_port
        self.lock = threading.Lock()
        # (key_field, key) → stamp dict of an open request
        self.waiting: dict = {}
        self.key_fields: tuple = ()
        self.socket_ids = [f"bench-{k}" for k in range(sockets)]
        self.sockets = []
        self.readers = []
        for sid in self.socket_ids:
            ws = connect(f"ws://127.0.0.1:{ws_port}/chat/", max_size=None)
            ws.send(sid)
            self.sockets.append(ws)
            t = threading.Thread(target=self._read, args=(ws,), daemon=True,
                                 name=f"ws-{sid}")
            t.start()
            self.readers.append(t)

    def _read(self, ws) -> None:
        from websockets.exceptions import ConnectionClosed

        while True:
            try:
                raw = ws.recv()
            except ConnectionClosed:
                return
            now = time.monotonic()
            frame = json.loads(raw)
            result = frame.get("result")
            if result is None:
                continue
            stamp = None
            with self.lock:
                for field in self.key_fields:
                    value = result.get(field)
                    if isinstance(value, (str, int)):
                        stamp = self.waiting.get((field, value))
                    if stamp is not None:
                        break
            if stamp is None:
                continue
            if "recv" in stamp:
                stamp["extra_frames"] = stamp.get("extra_frames", 0) + 1
                continue
            stamp["result"] = result
            stamp["recv"] = now
            stamp["answered"].set()

    def send(self, request: dict, due) -> dict:
        """Submit one request; returns its stamp (filled in as it goes)."""
        stamp = {"i": request["i"], "due": due,
                 "answered": threading.Event()}
        with self.lock:
            self.waiting[(request["key_field"], request["key"])] = stamp
        body = json.dumps(dict(
            request["body"],
            socket_id=self.socket_ids[request["session"]
                                      % len(self.socket_ids)]))
        stamp["send"] = time.monotonic()
        try:
            conn = http.client.HTTPConnection("127.0.0.1", self.http_port,
                                              timeout=60)
            try:
                conn.request("POST", "/", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                reply = json.loads(resp.read())
            finally:
                conn.close()
            stamp["status"] = resp.status
            stamp["cache"] = reply.get("cache")
        except (OSError, ValueError, http.client.HTTPException) as e:
            stamp["status"] = 0
            stamp["error"] = repr(e)
        stamp["http_done"] = time.monotonic()
        return stamp

    def close(self) -> None:
        for ws in self.sockets:
            ws.close()
        for t in self.readers:
            t.join(timeout=10)


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        if left > SPIN_S:
            time.sleep(left - SPIN_S)


def run_open(clients: Clients, requests: list, t0: float,
             threads: int) -> list:
    stamps = [None] * len(requests)
    cursor = iter(range(len(requests)))
    cursor_lock = threading.Lock()

    def sender():
        while True:
            with cursor_lock:
                k = next(cursor, None)
            if k is None:
                return
            due = t0 + requests[k]["due"]
            _sleep_until(due)
            stamps[k] = clients.send(requests[k], due)

    pool = [threading.Thread(target=sender, daemon=True, name=f"send-{n}")
            for n in range(threads)]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    return stamps


def run_closed(clients: Clients, requests: list, start: float,
               stop: float) -> list:
    by_client: dict = {}
    for r in requests:
        by_client.setdefault(r["client"], []).append(r)
    stamps: list = []
    stamps_lock = threading.Lock()

    def caller(mine: list, first: float):
        _sleep_until(first)
        for r in mine:
            if time.monotonic() >= stop:
                return
            stamp = clients.send(r, None)
            with stamps_lock:
                stamps.append(stamp)
            if stamp["status"] != 200:
                time.sleep(0.05)  # a refused submit: do not spin on it
                continue
            # The next request waits for this one's frame (a frame that
            # never comes frees the client after a minute).
            stamp["answered"].wait(timeout=60)

    pool = [threading.Thread(target=caller,
                             args=(mine, start + k * STAGGER_S),
                             daemon=True, name=f"client-{c}")
            for k, (c, mine) in enumerate(sorted(by_client.items()))]
    for t in pool:
        t.start()
    for t in pool:
        t.join()
    return stamps


def run(clients: Clients, cmd: dict) -> dict:
    with open(cmd["schedule"], encoding="utf-8") as f:
        sched = json.load(f)
    t0 = cmd["t0"]
    requests = sched["requests"]
    clients.key_fields = tuple(sorted({r["key_field"] for r in requests}))
    if sched["arrivals"] == "open":
        stamps = run_open(clients, requests, t0, cmd["threads"])
    else:
        stamps = run_closed(clients, requests, t0 - sched["warm_seconds"],
                            t0 + sched["seconds"])
    deadline = time.monotonic() + cmd["grace_s"]
    for stamp in stamps:
        if stamp.get("status") == 200:
            stamp["answered"].wait(timeout=max(0.0,
                                               deadline - time.monotonic()))
    with clients.lock:
        clients.waiting.clear()
    with open(cmd["out"], "w", encoding="utf-8") as f:
        for stamp in stamps:
            stamp.pop("answered")
            f.write(json.dumps(stamp) + "\n")
    return {"ok": True, "sent": len(stamps)}


def main() -> int:
    clients = None
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "connect":
            clients = Clients(cmd["http_port"], cmd["ws_port"],
                              cmd["sockets"])
            reply = {"ok": True}
        elif cmd["cmd"] == "run":
            reply = run(clients, cmd)
        elif cmd["cmd"] == "quit":
            break
        else:
            reply = {"ok": False, "error": f"unknown cmd {cmd['cmd']!r}"}
        print(json.dumps(reply), flush=True)
    if clients is not None:
        clients.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
