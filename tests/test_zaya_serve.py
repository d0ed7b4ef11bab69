"""The ``generate`` task served by the fourth decoder: a ``ServeApp`` whose
``generate.model`` is the tiny zaya configuration answers ``POST /``
through the same door, queue, ``_generate_loop``, ``GenerateEngine`` and
``SequenceState`` as the other three, its frames equal the reference's full
forward, nothing compiles once warm, and the CCA and skip counters say what
ran. CPU, float32; no timing is a measurement."""

import dataclasses
import http.client
import json
import time

import numpy as np
import pytest

from benchmark.reference import zaya as reference
from tests.test_generate_serve import compiles, post
from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    GENERATE_TASK_ID,
    FrameworkConfig,
    GenerateConfig,
    ServingConfig,
    ZayaConfig,
)
from vilbert_multitask_tpu.engine.generate import GenerateEngine

MODEL = ZayaConfig().tiny()
LOGIT_IDS = [0, 7, 511]
NEW = 4
ATOL = 2e-4   # float32 both sides: see tests/test_zaya.py
PROMPTS = (90, 33, 9, 64, 17)


def framework_config(root) -> FrameworkConfig:
    cfg = FrameworkConfig(
        generate=GenerateConfig(
            model=MODEL, param_dtype="float32", prefill_buckets=(64,),
            decode_buckets=(4,), slots=4, kv_pages=32, page_size=16,
            decode_attention_pages=4),
        serving=dataclasses.replace(
            ServingConfig(), queue_db_path=str(root / "q.sqlite3"),
            results_db_path=str(root / "r.sqlite3"),
            media_root=str(root / "media"), http_port=0, ws_port=0))
    return dataclasses.replace(cfg, engine=dataclasses.replace(
        cfg.engine, aot_cache_dir=str(root / "aot")))


def counted():
    return {name: sum(obs.REGISTRY.counter(
        name, labelnames=("program",)).collect().values())
        for name in ("vmt_cca_rows_total", "vmt_cca_calls_total",
                     "vmt_moe_skipped_total", "vmt_moe_pairs_total")}


@pytest.fixture(scope="module")
def app(tmp_path_factory):
    pytest.importorskip("websockets")
    from vilbert_multitask_tpu.serve.app import ServeApp

    app = ServeApp(framework_config(tmp_path_factory.mktemp("zaya_app")))
    app.warm()
    app.start()
    yield app
    app.stop()


@pytest.fixture(scope="module")
def answered(app):
    """Five prompts sent at once (more than the four slots): two chunks
    long, one whole bucket, shorter than a page."""
    from websockets.sync.client import connect

    rng = np.random.default_rng(11)
    prompts = {f"doc-{n}": rng.integers(0, MODEL.vocab_size, n).tolist()
               for n in PROMPTS}
    before, counts_before = compiles(), counted()
    results = {}
    with connect(f"ws://127.0.0.1:{app.ws.bound_port}/chat/") as ws:
        ws.send("sockZaya")
        time.sleep(0.2)
        for name, prompt in prompts.items():
            status, reply = post(app.http_port, {
                "task_id": GENERATE_TASK_ID, "socket_id": "sockZaya",
                "question": name, "prompt_ids": prompt,
                "max_new_tokens": NEW, "logit_ids": LOGIT_IDS})
            assert status == 200, reply
        deadline = time.monotonic() + 120
        while len(results) < len(prompts) and time.monotonic() < deadline:
            try:
                frame = json.loads(ws.recv(timeout=5))
            except TimeoutError:
                continue
            if "result" in frame:
                assert frame["result"]["question"] not in results
                results[frame["result"]["question"]] = frame["result"]
    assert sorted(results) == sorted(prompts)
    counts = {k: v - counts_before[k] for k, v in counted().items()}
    return {"prompts": prompts, "results": results, "counts": counts,
            "compiled": compiles() - before}


def test_the_app_is_the_same_engine_class_with_the_fourth_module(app):
    engine = app.engine.replicas[0].engine
    assert type(engine) is GenerateEngine
    assert engine.model_lib.__name__.endswith("models.zaya")
    assert set(engine.seqstate.arrays) == {"cca_conv", "cca_prev", "k", "v",
                                           "token"}
    assert engine.seqstate.arrays["k"].shape[0] == MODEL.num_hidden_layers


def test_frames_equal_the_reference_full_forward(app, answered):
    params = app.engine.replicas[0].engine.params
    model = dict(dataclasses.asdict(MODEL), rope_parameters=MODEL.rope)
    for name, r in answered["results"].items():
        prompt = answered["prompts"][name]
        assert r["task_name"] == "Generate" and len(r["tokens"]) == NEW
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


def test_the_counters_say_what_ran(app, answered):
    """Every real row went through CCA's preparation in both layers; every
    real token-layer was either computed by an expert or skipped."""
    c = answered["counts"]
    rows = sum(PROMPTS) + len(PROMPTS) * (NEW - 1)
    assert c["vmt_cca_rows_total"] == 2 * rows
    assert c["vmt_moe_skipped_total"] + c["vmt_moe_pairs_total"] == 2 * rows
    assert c["vmt_moe_skipped_total"] > 0
    conn = http.client.HTTPConnection("127.0.0.1", app.http_port, timeout=30)
    try:
        conn.request("GET", "/metrics?format=prometheus")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    for name in ("vmt_cca_rows_total", "vmt_cca_calls_total",
                 "vmt_moe_skipped_total", "vmt_seqstate_bytes_in_use"):
        assert name in text, name
