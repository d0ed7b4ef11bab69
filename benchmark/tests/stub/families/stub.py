"""The ``stub`` family: the smallest thing that answers, and the worked
example of ``benchmark/families/__init__.py``. Its "model" is two matrices,
``y = tanh(x @ w1) @ w2``; its server a thread that takes the POST, runs the
jitted forward and pushes one frame on the session's websocket. It is made
of new files only (this directory), is never entered in ``BENCHMARK.json``,
and is run on the CPU alone:

    python benchmark/run.py --manifest benchmark/tests/stub/manifest.json \\
        --workload stub.trickle --seed 5 --seconds 4 --trace 1 --rehearsal

Nothing under ``benchmark/harness``, ``benchmark/reduce`` or ``run.py``
knows of it (``tests/test_family_seam.py``).
"""

from __future__ import annotations

import http.server
import itertools
import json
import threading
import time

import numpy as np

from benchmark.harness import arrivals


def _shapes(model: dict) -> dict:
    return {"w1": {"kernel": (model["d_in"], model["d_hidden"])},
            "w2": {"kernel": (model["d_hidden"], model["d_out"])}}


def check_traffic(config: dict, traffic: dict) -> None:
    if not all(int(kind["rows"]) >= 1 for kind in traffic["deck"]):
        raise ValueError("a stub request holds one vector or more")


def assets(config: dict, traffic: dict, cache_dir: str) -> tuple:
    """Nothing on disk: the requests' vectors are drawn with the schedule."""
    t = time.monotonic()
    return ({"d_in": config["model"]["d_in"]},
            {"stub_assets_s": time.monotonic() - t})


def schedule(traffic: dict, seed: int, seconds: float, assets: dict) -> dict:
    rng = arrivals.rng_for(seed, 1)
    made = itertools.count()

    def request(rows: int) -> dict:
        name = f"stub-{next(made)}"
        x = rng.standard_normal((rows, assets["d_in"])).round(4).tolist()
        return {"body": {"id": name, "x": x}, "key": name, "key_field": "id",
                "rows": rows, "kind": (rows,), "x": x}

    def sessions(n: int) -> list:
        kinds = arrivals.deal(traffic["deck"], n)
        return [[request(int(kinds[pos]["rows"]))
                 for _ in range(int(traffic["questions_per_session"]))]
                for pos in rng.permutation(n)]

    return arrivals.schedule(traffic, rng, seconds, sessions)


def weights(config: dict, seed: int) -> tuple:
    if config["precision"] != "float32":
        raise SystemExit("the stub family makes float32 weights")
    from benchmark.harness import weights as tree_weights  # imports JAX

    shapes = _shapes(config["model"])
    return tree_weights.make(shapes, seed), tree_weights.count(shapes)


class _Websockets:
    """One websocket a socket id: the first message names it."""

    def __init__(self):
        from websockets.sync.server import serve

        self.sockets: dict = {}
        self.lock = threading.Lock()
        self.server = serve(self._handle, "127.0.0.1", 0)
        self.bound_port = self.server.socket.getsockname()[1]
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True, name="stub-ws")
        self.thread.start()

    def _handle(self, ws) -> None:
        from websockets.exceptions import ConnectionClosed

        try:
            with self.lock:
                self.sockets[ws.recv()] = ws
            for _ in ws:
                pass
        except ConnectionClosed:
            pass

    def push(self, socket_id: str, frame: dict) -> None:
        with self.lock:
            self.sockets[socket_id].send(json.dumps(frame))

    def stop(self) -> None:
        self.server.shutdown()
        self.thread.join(timeout=10)


class StubApp:
    """What the generic code asks of an application: ``http_port``,
    ``ws.bound_port`` and ``stop()``."""

    def __init__(self, params: dict, row_counts: list):
        import jax
        import jax.numpy as jnp

        from vilbert_multitask_tpu import obs

        self.ws = _Websockets()
        self.answered: list = []   # (monotonic time, rows) of each answer
        self.counter = obs.REGISTRY.counter(
            "stub_answers_total", "Frames the stub family's server pushed.")
        forward = jax.jit(lambda p, x: jnp.matmul(
            jnp.tanh(jnp.matmul(x, p["w1"]["kernel"], precision="highest")),
            p["w2"]["kernel"], precision="highest"))
        for rows in row_counts:  # every shape the traffic sends, before it
            forward(params, np.zeros((rows, params["w1"]["kernel"].shape[0]),
                                     np.float32)).block_until_ready()
        app = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                body = json.loads(self.rfile.read(
                    int(self.headers["Content-Length"])))
                with obs.span("stub.forward"):
                    y = np.asarray(forward(
                        params, np.asarray(body["x"], np.float32)))
                app.ws.push(body["socket_id"],
                            {"result": {"id": body["id"], "y": y.tolist()}})
                app.answered.append((time.monotonic(), len(body["x"])))
                app.counter.inc()
                reply = b'{"ok": true}'
                self.send_response(200)
                self.send_header("Content-Length", str(len(reply)))
                self.end_headers()
                self.wfile.write(reply)

            def log_message(self, *args):
                pass

        self.http = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.http.daemon_threads = True
        self.http_port = self.http.server_address[1]
        self.thread = threading.Thread(target=self.http.serve_forever,
                                       daemon=True, name="stub-http")
        self.thread.start()

    def stop(self) -> None:
        self.http.shutdown()
        self.http.server_close()
        self.thread.join(timeout=10)
        self.ws.stop()


def boot(config: dict, traffic: dict, params, assets: dict, state_dir: str,
         rehearsal: bool) -> tuple:
    t = time.monotonic()
    app = StubApp(params, sorted({int(k["rows"]) for k in traffic["deck"]}))
    return app, {"stub_boot_s": time.monotonic() - t}


def units_since(app, since: float) -> float:
    return float(sum(rows for at, rows in list(app.answered) if at >= since))


def flops_per_unit(config: dict) -> int:
    m = config["model"]
    return 2 * m["d_in"] * m["d_hidden"] + 2 * m["d_hidden"] * m["d_out"]


def unwritten_bytes(app, config: dict) -> tuple:
    return 0, {}


def sample(requests: list, stamps: dict, seed: int, limit: int) -> list:
    """Answered requests drawn from the seed, the longest kind first."""
    answered = [r for r in requests if r["i"] in stamps]
    arrivals.rng_for(seed, 2).shuffle(answered)
    return sorted(answered, key=lambda r: -r["rows"])[:limit]


def _round(a: np.ndarray, lower) -> np.ndarray:
    if lower is None:
        return a
    if lower != "bf16":
        raise SystemExit(f"the stub's control is bf16, not {lower!r}")
    import ml_dtypes

    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def run_reference(config: dict, params, picked: list, assets: dict,
                  lower=None) -> list:
    """Plain numpy in float64 (``lower``: every operand of both products
    rounded to bfloat16, the precision below the configuration's float32)."""
    w1 = _round(np.asarray(params["w1"]["kernel"], np.float64), lower)
    w2 = _round(np.asarray(params["w2"]["kernel"], np.float64), lower)
    return [_round(np.tanh(_round(np.asarray(r["x"], np.float64), lower)
                           @ w1), lower) @ w2 for r in picked]


def frame_of(request: dict, output) -> dict:
    return {"id": request["key"], "y": np.asarray(output).tolist()}


def compare(picked: list, stamps: dict, outputs: list) -> dict:
    """``y_err_max``: the largest difference between a served number and the
    reference's, over the spread of the reference's numbers."""
    diffs, unanswered = [], 0
    for request, ref in zip(picked, outputs):
        result = stamps[request["i"]].get("result") or {}
        served = np.asarray(result.get("y", []), np.float64)
        if result.get("id") != request["key"] or served.shape != ref.shape:
            unanswered += 1
            continue
        diffs.append(np.abs(served - ref).max())
    scale = float(np.concatenate([o.ravel() for o in outputs]).std()) \
        if outputs else 1.0
    return {"y_err_max": max(diffs) / scale if diffs else float("inf"),
            "unanswered": unanswered, "compared": len(picked),
            "reference_spread": scale}
