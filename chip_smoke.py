"""Chip smoke: the quickest proof that the system still starts on the chip.

    python chip_smoke.py                  # on the TPU (through the chip tool)
    python chip_smoke.py --cpu-rehearsal  # tiny model, CPU, explicit

One process, no children. Boots the serving stack through its normal entry
points (``ServeApp(...)``, ``app.warm()``, ``app.start()``) at the full
width of the model this repo is named for — default ``FrameworkConfig()``:
ViLBERT 768x12 text / 1024x6 visual / 6 bridges / 9 heads, 37+1 tokens, 101
regions, bf16 compute, every row bucket, Pallas kernels and fused heads on —
with random weights from a seed, answers real requests over HTTP + a
websocket, and checks that nothing on the way hid the device:

  device   the default backend is a TPU the peak tables know, else exit 1
           naming the platform found (this script sets no jax_platforms)
  boot     features from a seed, fresh state dir, ServeApp at full width
  warm     every bucket compiles (or deserializes); every replica ready
  serve    one request per task family alone (buckets 1/2/4/8/10 fire),
           then mixed bursts until the scheduler packs a 16/32-row chunk;
           every submit answers ``"cache": "miss"`` and ends in exactly one
           ``{"result": ...}`` frame with finite scores of the right shape
  nohide   Pallas on, zero compiles inside serving, zero swallowed AOT
           cache failures
  kernel   the Mosaic-compiled kernel (``interpret=False``, bf16) against
           the XLA reference at the three serving geometries

Every phase has its own deadline, so a hang is a named failure. Exit 0 and
one last stdout line ``{"ok": true, "device": {...}}`` only if every phase
passed; otherwise non-zero, the failing phase named, and no result line.

``--cpu-rehearsal`` runs the same phases on the CPU with the tiny model and
the kernels in the Pallas interpreter. It is what tier-1 exercises; it is
never chosen automatically and its result says ``"platform": "cpu"``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import http.client
import importlib.metadata
import json
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback

# (task_id, question, n_images): one request per served task family, sent
# one at a time so each dispatches alone and every image bucket fires.
FAMILY_PLAN = [
    (1, "what is the man holding", 1),            # VQA
    (15, "is the bowl to the right of the mug", 1),  # GQA
    (4, "which object can you eat", 1),           # Visual7W
    (11, "the woman in the red coat", 1),         # RefCOCO
    (16, "q: is it a person? a: no q: is it red? a: yes", 1),  # GuessWhat
    (13, "two dogs are playing in the snow", 1),  # SNLI-VE
    (12, "both images contain exactly two wolves", 2),  # NLVR2
    (7, "a dog catching a frisbee", 4),           # retrieval, bucket 4
    (7, "a red car parked outside", 8),           # retrieval, bucket 8
    (7, "people waiting for a train", 10),        # retrieval, bucket 10
]
N_IMAGES = max(n for _, _, n in FAMILY_PLAN)
# A burst is concurrent submits cycling through these (task_id, n_images).
# Rows, not requests, fill a chunk: whatever becomes ready while one forward
# is in flight packs into the next, and two 10-candidate retrievals there
# already need a throughput bucket. How many co-arrive depends on the
# machine, so bursts grow until a 16/32-row chunk has dispatched.
BURST_MIX = ((7, 10), (7, 10), (1, 1), (7, 8), (12, 2), (7, 10))
BURST_SIZES = (12, 24, 48, 96)
# The three attention geometries serving runs (Nq, Nk): text→image and
# image→text co-attention, visual self-attention; 8 heads of 128.
KERNEL_GEOMETRIES = ((38, 101), (101, 38), (101, 101))


class SmokeError(Exception):
    """A check the smoke makes did not hold."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeError(message)


def say(message: str) -> None:
    print(message, flush=True)


@contextlib.contextmanager
def phase(name: str, deadline_s: float):
    """Run one named phase under its own deadline. An exception fails the
    run with the phase named; a phase still running at its deadline is
    reported and the process exits on the spot — a hang in native code
    never returns to the interpreter, so nothing gentler can end it."""
    say(f"== {name}: start (deadline {deadline_s:.0f}s)")

    def expire():
        say(f"FAILED {name}: still running after {deadline_s:.0f}s (hang)")
        os._exit(1)

    timer = threading.Timer(deadline_s, expire)
    timer.daemon = True
    timer.start()
    t0 = time.monotonic()
    try:
        yield
    except Exception as e:
        traceback.print_exc()
        say(f"FAILED {name}: {type(e).__name__}: {e}")
        raise SystemExit(1) from e
    finally:
        timer.cancel()
    say(f"== {name}: ok in {time.monotonic() - t0:.1f}s")


def _entries(path: str) -> int:
    """Files under a cache directory (0 when it does not exist yet)."""
    return sum(len(files) for _, _, files in os.walk(path))


def counter_total(name: str) -> float:
    """Sum over every label set of one registered ``vmt_*`` counter."""
    from vilbert_multitask_tpu import obs

    (counter,) = [i for i in obs.REGISTRY.instruments() if i.name == name]
    return sum(counter.collect().values())


# ------------------------------------------------------------------ device
def check_device(rehearsal: bool) -> dict:
    import jax
    import jaxlib

    from vilbert_multitask_tpu.config import require_tpu
    from vilbert_multitask_tpu.engine.flops import peak_flops_for

    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    else:
        require_tpu("chip_smoke.py")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"devices: {device['count']}")
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu}  python {sys.version.split()[0]}")
    if not rehearsal:
        check(peak_flops_for(dev.device_kind) is not None,
              f"engine/flops.py has no peak entry for {dev.device_kind!r}")
    return device


# -------------------------------------------------------------------- boot
def build_config(rehearsal: bool, state_dir: str):
    from vilbert_multitask_tpu.config import (
        EngineConfig,
        FrameworkConfig,
        ServingConfig,
    )

    cfg = FrameworkConfig()  # the full-width defaults
    if rehearsal:
        # Same topology, buckets and kernels-on path at CPU size; the
        # kernels run in the Pallas interpreter because this says so.
        cfg = dataclasses.replace(
            cfg, model=cfg.model.tiny(pallas_interpret=True),
            engine=EngineConfig(max_text_len=12, max_regions=9,
                                num_features=8, compute_dtype="float32"))
    serving = dataclasses.replace(
        ServingConfig(),
        queue_db_path=os.path.join(state_dir, "queue.sqlite3"),
        results_db_path=os.path.join(state_dir, "results.sqlite3"),
        media_root=os.path.join(state_dir, "media"),
        http_port=0, ws_port=0)
    return dataclasses.replace(cfg, serving=serving)


def write_features(cfg, root: str) -> str:
    """N_IMAGES reference-schema ``.npy`` files from a seed, at the region
    count and width the engine is configured for (100 x 2048 by default)."""
    import numpy as np

    from vilbert_multitask_tpu.features.pipeline import synthetic_regions
    from vilbert_multitask_tpu.features.store import save_reference_npy

    out = os.path.join(root, "features")
    os.makedirs(out)
    rng = np.random.default_rng(0)
    for i in range(N_IMAGES):
        region = synthetic_regions(cfg.model.v_feature_size,
                                   n_boxes=cfg.engine.num_features, rng=rng)
        save_reference_npy(os.path.join(out, f"img_{i}.npy"), region,
                           f"img_{i}")
    return out


# ------------------------------------------------------------------- serve
class Client:
    """The browser's two connections: one websocket that receives every
    frame for a socket id, and HTTP ``POST /`` submits."""

    def __init__(self, http_port: int, ws_port: int, socket_id: str):
        from websockets.sync.client import connect

        self.socket_id = socket_id
        self._ws = connect(f"ws://127.0.0.1:{ws_port}/chat/")
        self._ws.send(socket_id)
        self._http_port = http_port
        self._cond = threading.Condition()
        self._results: dict = {}  # question → [result payload, ...]
        self._other_terminals: list = []  # error / dead-letter frames
        self._reader = threading.Thread(target=self._read, daemon=True,
                                        name="chip-smoke-ws")
        self._reader.start()

    def _read(self) -> None:
        from websockets.exceptions import ConnectionClosed

        while True:
            try:
                frame = json.loads(self._ws.recv())
            except ConnectionClosed:
                return
            with self._cond:
                if "result" in frame:
                    q = frame["result"].get("question", "")
                    self._results.setdefault(q, []).append(frame["result"])
                elif "error" in frame:
                    self._other_terminals.append(frame)
                self._cond.notify_all()

    def submit(self, task_id: int, question: str, n_images: int) -> None:
        body = json.dumps({
            "task_id": task_id, "socket_id": self.socket_id,
            "question": question,
            "image_list": [f"img_{k}.jpg" for k in range(n_images)]})
        conn = http.client.HTTPConnection("127.0.0.1", self._http_port,
                                          timeout=60)
        try:
            conn.request("POST", "/", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            reply = json.loads(resp.read())
        finally:
            conn.close()
        check(resp.status == 200, f"POST / → {resp.status} {reply}")
        # A hit or a coalesced submit would be answered without the device.
        check(reply.get("cache") == "miss",
              f"submit {question!r} was not a cache miss: {reply}")

    def submit_concurrently(self, requests: dict) -> None:
        """One thread per ``question → (task_id, n_images)`` submit, all
        started together; the first failed submit is re-raised."""
        errors: list = []

        def one(question, task_id, n_images):
            try:
                self.submit(task_id, question, n_images)
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=one, args=(q, t, n))
                   for q, (t, n) in requests.items()]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        if errors:
            raise errors[0]

    def wait_for(self, questions, timeout_s: float) -> None:
        """Block until every question has a result frame (or fail)."""
        deadline = time.monotonic() + timeout_s
        with self._cond:
            while True:
                check(not self._other_terminals,
                      f"error frame: {self._other_terminals[:1]}")
                missing = [q for q in questions if q not in self._results]
                if not missing:
                    return
                left = deadline - time.monotonic()
                check(left > 0, f"{len(missing)} of {len(questions)} "
                      f"requests got no result frame in {timeout_s:.0f}s, "
                      f"e.g. {missing[0]!r}")
                self._cond.wait(timeout=left)

    def results(self) -> dict:
        with self._cond:
            return {q: list(v) for q, v in self._results.items()}

    def close(self) -> None:
        self._ws.close()
        self._reader.join(timeout=10)


def _scores(payload: dict) -> list:
    """Every score and confidence a result payload carries."""
    out = []
    for key in ("answers", "boxes", "ranking"):
        for item in payload.get(key) or ():
            out.extend(item[k] for k in ("confidence", "score") if k in item)
    return out


def check_result(task_id: int, n_images: int, payload: dict) -> None:
    """Finite values of the expected shape, by the task's decode family."""
    from vilbert_multitask_tpu.config import TASK_REGISTRY

    spec = TASK_REGISTRY[task_id]
    want = {"labels": ("answers", spec.top_k), "binary": ("answers", 2),
            "trinary": ("answers", 3), "grounding": ("boxes", spec.top_k),
            "ranking": ("ranking", n_images)}[spec.decode]
    got = payload.get(want[0]) or ()
    check(payload.get("task_id") == task_id and len(got) == want[1],
          f"{spec.name}: expected {want[1]} {want[0]}, got {payload}")
    scores = _scores(payload)
    check(bool(scores) and all(math.isfinite(s) for s in scores),
          f"{spec.name}: non-finite scores in {payload}")


def serve_requests(app, cfg) -> dict:
    from vilbert_multitask_tpu import obs

    def dispatched() -> set:
        return {int(key[0]) for key, n in
                obs.BATCH_FILL.series_counts().items() if n}

    before = dispatched()

    def fired() -> set:
        """Row buckets the scheduler dispatched since this phase began."""
        return dispatched() - before

    client = Client(app.http_port, app.ws.bound_port, "chip-smoke")
    sent: dict = {}  # question → (task_id, n_images)
    try:
        for task_id, question, n in FAMILY_PLAN:
            client.submit(task_id, question, n)
            client.wait_for([question], timeout_s=120)
            sent[question] = (task_id, n)
        image_buckets = set(cfg.engine.image_buckets)
        check(image_buckets <= fired(),
              f"image buckets {sorted(image_buckets)} expected, the "
              f"scheduler dispatched {sorted(fired())}")
        # Bursts: concurrent distinct submits, grown until the scheduler
        # packs a throughput-sized chunk.
        big = set(cfg.engine.throughput_buckets)
        for round_i, size in enumerate(BURST_SIZES):
            burst = {f"burst {round_i} item {i} what is this":
                     BURST_MIX[i % len(BURST_MIX)] for i in range(size)}
            client.submit_concurrently(burst)
            client.wait_for(list(burst), timeout_s=180)
            sent.update(burst)
            if big & fired():
                break
        check(bool(big & fired()),
              f"no {sorted(big)}-row chunk dispatched after bursts of "
              f"{BURST_SIZES}; buckets seen {sorted(fired())}")
        # Exactly one result frame each: give a duplicate time to show up.
        time.sleep(1.0)
        results = client.results()
    finally:
        client.close()
    for question, (task_id, n) in sent.items():
        frames = results.get(question, [])
        check(len(frames) == 1,
              f"{question!r}: {len(frames)} result frames, expected 1")
        check_result(task_id, n, frames[0])
    check(set(results) == set(sent),
          f"result frames for unknown requests: {set(results) - set(sent)}")
    return {"requests": len(sent), "row_buckets_dispatched": sorted(fired())}


# ------------------------------------------------------------------ kernel
def check_kernel(rehearsal: bool) -> dict:
    """The co-attention kernel against the XLA reference, bf16, at the
    three geometries serving uses — compiled by Mosaic on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from vilbert_multitask_tpu.ops.attention import (
        mask_to_bias,
        multi_head_attention,
    )
    from vilbert_multitask_tpu.ops.coattention import flash_cross_attention

    heads, depth, batch = 8, 128, 2
    worst = {}
    for nq, nk in KERNEL_GEOMETRIES:
        keys = jax.random.split(jax.random.PRNGKey(nq * 1000 + nk), 3)
        q = jax.random.normal(keys[0], (batch, nq, heads, depth),
                              jnp.bfloat16)
        k = jax.random.normal(keys[1], (batch, nk, heads, depth),
                              jnp.bfloat16)
        v = jax.random.normal(keys[2], (batch, nk, heads, depth),
                              jnp.bfloat16)
        mask = np.ones((batch, nk), np.int32)
        mask[:, nk - 5:] = 0  # a masked tail, like padded regions/tokens
        bias = mask_to_bias(jnp.asarray(mask))
        out = flash_cross_attention(q, k, v, bias, interpret=rehearsal)
        ref, _ = multi_head_attention(q, k, v, bias, dtype=jnp.float32)
        out = np.asarray(out.astype(jnp.float32))
        check(out.shape == (batch, nq, heads, depth)
              and bool(np.isfinite(out).all()),
              f"kernel {nq}x{nk}: bad shape or non-finite output")
        err = float(np.max(np.abs(out - np.asarray(ref, np.float32))))
        # bf16-class tolerance (the bound the in-tree TPU test used).
        check(err <= 2e-2, f"kernel {nq}x{nk}: max |err| {err:.4g} > 2e-2")
        worst[f"{nq}x{nk}"] = round(err, 5)
    return worst


# -------------------------------------------------------------------- main
def run(rehearsal: bool) -> dict:
    # Phase deadlines sum to 1150 s, inside the 1200 s the chip check
    # allows (seen on a v5e, cold: device 19, boot 59, warm 74, serve 5).
    with phase("device", 90):
        device = check_device(rehearsal)

    state_dir = tempfile.mkdtemp(prefix="chip_smoke_state_")
    app = None
    try:
        with phase("boot", 240):
            from vilbert_multitask_tpu import native
            from vilbert_multitask_tpu.engine import cachedir
            from vilbert_multitask_tpu.serve.app import ServeApp

            say(f"native.available(): {native.available()}")
            cfg = build_config(rehearsal, state_dir)
            cache_dir = cachedir.enable_compilation_cache()
            cache_before = _entries(cache_dir)
            say(f"compile cache: {cache_dir} ({cache_before} entries; "
                f"{cachedir.CACHE_DIR_ENV} "
                f"{'set' if os.environ.get(cachedir.CACHE_DIR_ENV) else 'unset'})")
            features = write_features(cfg, state_dir)
            app = ServeApp(cfg, feature_root=features)
            aot_dir = app.cfg.engine.aot_cache_dir
            aot_before = _entries(aot_dir)
            say(f"AOT executable cache: {aot_dir} ({aot_before} entries)")

        def compiles() -> float:
            return counter_total("vmt_engine_compiles_total")

        with phase("warm", 480):
            app.warm()
            states = {r.name: r.state for r in app.engine.replicas}
            check(all(s == "ready" for s in states.values()),
                  f"replicas not ready after warm: {states}")
            app.start()

        with phase("serve", 180):
            compiles_before = compiles()
            served = serve_requests(app, cfg)
            compiled_in_serving = compiles() - compiles_before

        with phase("nohide", 10):
            check(app.boot_info.get("pallas") is True,
                  f"Pallas kernels are not on: {app.boot_info}")
            check(compiled_in_serving == 0,
                  f"{compiled_in_serving:.0f} compiles inside serving "
                  f"(vmt_engine_compiles_total moved)")
            # store_failed / load_failed / exec_fallback, all swallowed.
            swallowed = counter_total("vmt_aot_cache_failures_total")
            check(swallowed == 0,
                  f"{swallowed:.0f} swallowed AOT cache failures "
                  f"(vmt_aot_cache_failures_total moved)")

        with phase("kernel", 120):
            kernel_err = check_kernel(rehearsal)

        boot = app.boot_info
        report = {
            "program_family": boot["program_family"],
            "replicas": boot["replicas"],
            "boot": {"engine_init_s": boot["engine_init_s"],
                     "warmup_s": boot["warmup_s"],
                     **boot.get("boot_phases", {})},
            "total_compiles": compiles(),
            "compile_cache": {"dir": cache_dir, "entries_before": cache_before,
                              "entries_after": _entries(cache_dir)},
            "aot_cache": {"dir": aot_dir, "entries_before": aot_before,
                          "entries_after": _entries(aot_dir)},
            "served": served,
            "kernel_max_abs_err": kernel_err,
        }
    finally:
        if app is not None:
            with phase("stop", 30):
                app.stop()
        shutil.rmtree(state_dir, ignore_errors=True)
    say("report: " + json.dumps(report))
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny model on the CPU, kernels in the Pallas "
                        "interpreter — a rehearsal of the phases, not a "
                        "chip check; never chosen automatically")
    args = p.parse_args(argv)
    device = run(args.cpu_rehearsal)
    result = {"ok": True, "device": device}
    if args.cpu_rehearsal:
        result["rehearsal"] = True
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
