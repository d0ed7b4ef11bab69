"""The ``generate`` task on the served path (ISSUE 28, test f): a
``ServeApp`` built around the tiny hybrid decoder answers ``POST /`` through
the durable queue, the continuous scheduler's running set, the generate
engine and ``_finish_job`` with exactly one terminal result frame a request,
equal to the reference's full forward; what cannot be served is a 400 at the
door; nothing compiles once warm. CPU, float32; no timing is a measurement.
"""

import dataclasses
import http.client
import json
import time

import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as reference
from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    GENERATE_TASK_ID,
    FrameworkConfig,
    GenerateConfig,
    OlmoHybridConfig,
    ServingConfig,
)

MODEL = OlmoHybridConfig().tiny()
LOGIT_IDS = [0, 7, 511]
NEW = 6
ATOL = 2e-4   # float32 both sides: see tests/test_olmo_hybrid.py


def post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", "/", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def counted(name: str) -> dict:
    """A counter's value by label tuple."""
    return {key: v for inst in obs.REGISTRY.instruments()
            if inst.name == name for key, v in inst.collect().items()}


def compiles() -> float:
    return sum(counted("vmt_engine_compiles_total").values())


@pytest.fixture(scope="module")
def app(tmp_path_factory):
    pytest.importorskip("websockets")
    from vilbert_multitask_tpu.serve.app import ServeApp

    root = tmp_path_factory.mktemp("generate_app")
    cfg = FrameworkConfig(
        generate=GenerateConfig(
            model=MODEL, param_dtype="float32", prefill_buckets=(64, 128),
            decode_buckets=(2, 4), slots=4, kv_pages=32, page_size=16,
            decode_attention_pages=4),
        serving=dataclasses.replace(
            ServingConfig(), queue_db_path=str(root / "q.sqlite3"),
            results_db_path=str(root / "r.sqlite3"),
            media_root=str(root / "media"), http_port=0, ws_port=0))
    cfg = dataclasses.replace(cfg, engine=dataclasses.replace(
        cfg.engine, aot_cache_dir=str(root / "aot")))
    app = ServeApp(cfg)
    app.warm()
    app.start()
    yield app
    app.stop()


@pytest.fixture(scope="module")
def answered(app):
    """Five requests of different length sent at once (more than the four
    slots: one waits for a slot), every frame their socket received."""
    from websockets.sync.client import connect

    rng = np.random.default_rng(5)
    prompts = {f"doc-{n}": rng.integers(0, MODEL.vocab_size, n).tolist()
               for n in (150, 70, 33, 200, 9)}
    before = compiles()
    chunks_before = counted("vmt_prefill_attention_chunks_total")
    frames = []
    with connect(f"ws://127.0.0.1:{app.ws.bound_port}/chat/") as ws:
        ws.send("sockGen")
        time.sleep(0.2)
        for name, prompt in prompts.items():
            status, reply = post(app.http_port, {
                "task_id": GENERATE_TASK_ID, "socket_id": "sockGen",
                "question": name, "prompt_ids": prompt,
                "max_new_tokens": NEW, "logit_ids": LOGIT_IDS})
            assert status == 200, reply
        deadline = time.monotonic() + 120
        results = {}
        while len(results) < len(prompts) and time.monotonic() < deadline:
            try:
                frame = json.loads(ws.recv(timeout=5))
            except TimeoutError:
                continue
            frames.append(frame)
            if "result" in frame:
                results.setdefault(frame["result"]["question"],
                                   []).append(frame["result"])
        assert len(results) == len(prompts), (
            f"only {sorted(results)} of {sorted(prompts)} answered in 120 s")
        time.sleep(0.5)   # a second result frame would arrive about now
        try:
            while True:
                frames.append(json.loads(ws.recv(timeout=0.2)))
        except TimeoutError:
            pass
    chunks = counted("vmt_prefill_attention_chunks_total")
    return {"prompts": prompts, "frames": frames,
            "compiled": compiles() - before,
            "attention_chunks": {key: v - chunks_before.get(key, 0)
                                 for key, v in chunks.items()
                                 if v != chunks_before.get(key, 0)}}


def test_one_terminal_result_frame_a_request(answered):
    results = [f["result"] for f in answered["frames"] if "result" in f]
    assert sorted(r["question"] for r in results) == sorted(
        answered["prompts"])
    for r in results:
        assert r["task_name"] == "Generate"
        assert len(r["tokens"]) == len(r["token_logits"]) == NEW
        assert [len(row) for row in r["logits"]] == [len(LOGIT_IDS)] * NEW


def test_frames_equal_the_reference_full_forward(app, answered):
    params = app.engine.replicas[0].engine.params
    model = dataclasses.asdict(MODEL)
    for frame in answered["frames"]:
        r = frame.get("result")
        if r is None:
            continue
        prompt = answered["prompts"][r["question"]]
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + NEW)
        ref = np.asarray(reference.forward(params, model,
                                           prompt + r["tokens"], rows=rows))
        assert (ref.argmax(-1) == np.asarray(r["tokens"])).all()
        assert np.abs(ref.max(-1) - np.asarray(r["token_logits"])).max() < ATOL
        assert np.abs(ref[:, LOGIT_IDS] - np.asarray(r["logits"])).max() < ATOL


def test_nothing_compiles_once_warm_and_nothing_leaks(app, answered):
    assert answered["compiled"] == 0
    eng = app.engine.replicas[0].engine
    assert not eng.seqstate.live() and eng.seqstate.bytes_in_use == 0
    assert app.queue.counts().get("inflight", 0) == 0


@pytest.mark.parametrize("body,says", [
    ({"prompt_ids": [1] * 600, "max_new_tokens": 8}, "exceed the context"),
    ({"prompt_ids": [1, 2], "max_new_tokens": 8,
      "logit_ids": list(range(17))}, "at most 16"),
    ({"prompt_ids": [1, 512], "max_new_tokens": 8}, "lie in"),
    ({"prompt_ids": [], "max_new_tokens": 8}, "at least one token"),
    ({"prompt_ids": [1, 2], "max_new_tokens": 0}, "at least 1"),
    ({"prompt_ids": "1 2 3", "max_new_tokens": 4}, "list of whole numbers"),
])
def test_what_cannot_be_served_is_a_400(app, body, says):
    status, reply = post(app.http_port, dict(
        body, task_id=GENERATE_TASK_ID, socket_id="sockGen"))
    assert status == 400 and says in reply["error"]


def test_one_app_one_model(app):
    status, reply = post(app.http_port, {
        "task_id": 1, "socket_id": "s", "question": "what is this",
        "image_list": ["a.jpg"]})
    assert status == 400 and "not served" in reply["error"]


def test_spans_and_instruments_are_exposed(app, answered):
    names = {s.name for s in obs.default_tracer().spans()}
    assert {"engine.prefill", "engine.decode_step", "seqstate.admit",
            "sched.generate_iter"} <= names
    conn = http.client.HTTPConnection("127.0.0.1", app.http_port, timeout=30)
    try:
        conn.request("GET", "/metrics?format=prometheus")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    for name in ("vmt_prefill_tokens_total", "vmt_decode_tokens_total",
                 "vmt_seq_admitted_total", "vmt_seq_released_total",
                 "vmt_seq_slots_in_use", "vmt_kv_pages_in_use",
                 "vmt_seqstate_bytes_in_use", "vmt_decode_batch_fill"):
        assert name in text, name


def test_prefill_attention_counters_move_and_are_exposed(app, answered):
    """ISSUE 33's counters on the served path. Prompts of 150, 70, 33, 200
    and 9 tokens in buckets of 64 and 128 are 128 + 22, 70, 33, 128 + 72
    and 9: seven chunks, every one through the ``jax.numpy`` attention
    (this app's model has its kernels off), which walks no kernel page."""
    assert answered["attention_chunks"] == {("xla",): 7}
    conn = http.client.HTTPConnection("127.0.0.1", app.http_port, timeout=30)
    try:
        conn.request("GET", "/metrics?format=prometheus")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    assert 'vmt_prefill_attention_chunks_total{path="xla"' in text
    assert "# TYPE vmt_prefill_attention_pages_total counter" in text


def test_a_vilbert_door_refuses_the_generate_task(tmp_path):
    """The other side of one app, one model: an ``ApiServer`` that was given
    no generate configuration answers task 20 with a 400 and queues
    nothing."""
    from vilbert_multitask_tpu.serve.db import ResultStore
    from vilbert_multitask_tpu.serve.http_api import ApiServer
    from vilbert_multitask_tpu.serve.push import PushHub
    from vilbert_multitask_tpu.serve.queue import DurableQueue

    queue = DurableQueue(str(tmp_path / "q.sqlite3"))
    api = ApiServer(queue, ResultStore(str(tmp_path / "r.sqlite3")),
                    PushHub())
    code, reply = api.submit_job({
        "task_id": GENERATE_TASK_ID, "socket_id": "s",
        "prompt_ids": [1, 2], "max_new_tokens": 4})
    assert code == 400 and "not served" in reply["error"]
    assert queue.counts().get("pending", 0) == 0
