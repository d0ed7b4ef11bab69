"""The ``generate`` task served by the second decoder (ISSUE 32): a
``ServeApp`` whose ``generate.model`` is the tiny laguna configuration
answers ``POST /`` through the same door, queue, ``_generate_loop``,
``GenerateEngine`` and ``SequenceState`` as the hybrid decoder
(``tests/test_generate_serve.py``), its frames equal the reference's full
forward, nothing compiles once warm, and the expert layer's counters and the
ring gauge are exposed. CPU, float32; no timing is a measurement.
"""

import dataclasses
import http.client
import json
import time

import numpy as np
import pytest

from benchmark.reference import laguna as reference
from tests.test_generate_serve import compiles, post
from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    GENERATE_TASK_ID,
    FrameworkConfig,
    GenerateConfig,
    LagunaConfig,
    ServingConfig,
)
from vilbert_multitask_tpu.engine.generate import (
    GenerateEngine,
    generate_fingerprint,
)

MODEL = LagunaConfig().tiny()
LOGIT_IDS = [0, 7, 383]
NEW = 5
ATOL = 2e-4   # float32 both sides: see tests/test_laguna.py


def framework_config(root) -> FrameworkConfig:
    cfg = FrameworkConfig(
        generate=GenerateConfig(
            model=MODEL, param_dtype="float32", prefill_buckets=(32, 64),
            decode_buckets=(2, 4), slots=4, kv_pages=32, page_size=16,
            decode_attention_pages=4),
        serving=dataclasses.replace(
            ServingConfig(), queue_db_path=str(root / "q.sqlite3"),
            results_db_path=str(root / "r.sqlite3"),
            media_root=str(root / "media"), http_port=0, ws_port=0))
    return dataclasses.replace(cfg, engine=dataclasses.replace(
        cfg.engine, aot_cache_dir=str(root / "aot")))


@pytest.fixture(scope="module")
def app(tmp_path_factory):
    pytest.importorskip("websockets")
    from vilbert_multitask_tpu.serve.app import ServeApp

    app = ServeApp(framework_config(tmp_path_factory.mktemp("laguna_app")))
    app.warm()
    app.start()
    yield app
    app.stop()


@pytest.fixture(scope="module")
def answered(app):
    """Five prompts sent at once (more than the four slots): shorter than
    the window, several windows long, ending mid-page."""
    from websockets.sync.client import connect

    rng = np.random.default_rng(9)
    prompts = {f"doc-{n}": rng.integers(0, MODEL.vocab_size, n).tolist()
               for n in (120, 70, 33, 150, 9)}
    before = compiles()
    results = {}
    with connect(f"ws://127.0.0.1:{app.ws.bound_port}/chat/") as ws:
        ws.send("sockLaguna")
        time.sleep(0.2)
        for name, prompt in prompts.items():
            status, reply = post(app.http_port, {
                "task_id": GENERATE_TASK_ID, "socket_id": "sockLaguna",
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
    return {"prompts": prompts, "results": results,
            "compiled": compiles() - before}


def test_the_app_is_the_same_engine_class_with_the_other_module(app):
    engine = app.engine.replicas[0].engine
    assert type(engine) is GenerateEngine
    assert engine.model_lib.__name__.endswith("models.laguna")
    assert set(engine.seqstate.arrays) == {"ring_k", "ring_v", "k", "v",
                                           "token"}


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


def test_an_id_beyond_the_held_vocabulary_is_a_400(app):
    status, reply = post(app.http_port, {
        "task_id": GENERATE_TASK_ID, "socket_id": "sockLaguna",
        "prompt_ids": [1, MODEL.vocab_size], "max_new_tokens": 4})
    assert status == 400 and "lie in" in reply["error"]


def test_expert_counters_and_ring_gauge_are_exposed(app, answered):
    """Pairs routed are 4 a real token and sparse layer; about half of them
    are computed here (8 of 16 experts held), never more; the fullest held
    expert is at least the mean."""
    def delta(name):
        inst = obs.REGISTRY.counter(name, labelnames=("program",))
        return {k[0]: v for k, v in inst.collect().items()}

    routed, here = delta("vmt_moe_pairs_routed_total"), delta(
        "vmt_moe_pairs_total")
    calls, touched = delta("vmt_moe_calls_total"), delta(
        "vmt_moe_experts_touched_total")
    prompt_tokens = sum(len(p) for p in answered["prompts"].values())
    sparse = len(MODEL.sparse_layers)
    assert routed["prefill"] >= prompt_tokens * 4 * sparse
    for program in ("prefill", "decode"):
        assert 0.25 < here[program] / routed[program] < 0.75
        assert 0 < touched[program] <= calls[program] * MODEL.held[1]
    load = obs.REGISTRY.histogram("vmt_moe_expert_load_max_over_mean",
                                  labelnames=("program",))
    samples = load.window_samples(600, program="prefill")
    assert samples and min(samples) >= 1.0
    conn = http.client.HTTPConnection("127.0.0.1", app.http_port, timeout=30)
    try:
        conn.request("GET", "/metrics?format=prometheus")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    for name in ("vmt_moe_calls_total", "vmt_moe_pairs_total",
                 "vmt_moe_pairs_routed_total",
                 "vmt_moe_experts_touched_total",
                 "vmt_moe_expert_load_max_over_mean",
                 "vmt_seq_ring_bytes_in_use", "vmt_seqstate_bytes_in_use"):
        assert name in text, name


def test_the_aot_fingerprint_names_the_model(tmp_path):
    """One model's executables are never read for the other's."""
    from vilbert_multitask_tpu.config import OlmoHybridConfig

    laguna = framework_config(tmp_path)
    olmo = dataclasses.replace(laguna, generate=dataclasses.replace(
        laguna.generate, model=OlmoHybridConfig().tiny()))
    assert generate_fingerprint(laguna)["generate"]["model"][
        "model_type"] == "laguna"
    assert generate_fingerprint(olmo)["generate"]["model"][
        "model_type"] == "olmo_hybrid"
    assert generate_fingerprint(laguna) != generate_fingerprint(olmo)
