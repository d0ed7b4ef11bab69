"""The ``generate`` task served by the third decoder (ISSUE 34): a
``ServeApp`` whose ``generate.model`` is the tiny phi4flash configuration
answers ``POST /`` through the same door, queue, ``_generate_loop``,
``GenerateEngine`` and ``SequenceState`` as the other two
(``tests/test_generate_serve.py``, ``tests/test_laguna_serve.py``), its
frames equal the reference's full forward, nothing compiles once warm, and
the split's counters are exposed. CPU, float32; no timing is a measurement.
"""

import dataclasses
import http.client
import json
import time

import numpy as np
import pytest

from benchmark.reference import phi4flash as reference
from tests.test_generate_serve import compiles, post
from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.config import (
    GENERATE_TASK_ID,
    FrameworkConfig,
    GenerateConfig,
    Phi4FlashConfig,
    ServingConfig,
)
from vilbert_multitask_tpu.engine.generate import (
    GenerateEngine,
    generate_fingerprint,
)

MODEL = Phi4FlashConfig().tiny()
LOGIT_IDS = [0, 7, 383]
NEW = 5
ATOL = 3e-4   # float32 both sides: see tests/test_phi4flash.py
PROMPTS = (120, 70, 33, 150, 9)


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


def rows_counted():
    return {(name, k[0]): v for name in (
        "vmt_self_decoder_rows_total", "vmt_cross_decoder_rows_total",
        "vmt_ssm_scan_tokens_total")
        for k, v in obs.REGISTRY.counter(
            name, labelnames=("program",)).collect().items()}


@pytest.fixture(scope="module")
def app(tmp_path_factory):
    pytest.importorskip("websockets")
    from vilbert_multitask_tpu.serve.app import ServeApp

    app = ServeApp(framework_config(tmp_path_factory.mktemp("phi4flash_app")))
    app.warm()
    app.start()
    yield app
    app.stop()


@pytest.fixture(scope="module")
def answered(app):
    """Five prompts sent at once (more than the four slots): shorter than
    the window, several windows and chunks long, ending mid-page."""
    from websockets.sync.client import connect

    rng = np.random.default_rng(9)
    prompts = {f"doc-{n}": rng.integers(0, MODEL.vocab_size, n).tolist()
               for n in PROMPTS}
    before, rows_before = compiles(), rows_counted()
    results = {}
    with connect(f"ws://127.0.0.1:{app.ws.bound_port}/chat/") as ws:
        ws.send("sockPhi")
        time.sleep(0.2)
        for name, prompt in prompts.items():
            status, reply = post(app.http_port, {
                "task_id": GENERATE_TASK_ID, "socket_id": "sockPhi",
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
    rows = {k: v - rows_before.get(k, 0.0)
            for k, v in rows_counted().items()}
    return {"prompts": prompts, "results": results, "rows": rows,
            "compiled": compiles() - before}


def test_the_app_is_the_same_engine_class_with_the_third_module(app):
    engine = app.engine.replicas[0].engine
    assert type(engine) is GenerateEngine
    assert engine.model_lib.__name__.endswith("models.phi4flash")
    assert set(engine.seqstate.arrays) == {"ssm", "conv", "ring_k", "ring_v",
                                           "k", "v", "token"}
    assert engine.seqstate.arrays["k"].shape[0] == 1     # one paged layer


def test_frames_equal_the_reference_full_forward(app, answered):
    params = app.engine.replicas[0].engine.params
    model = dataclasses.asdict(MODEL)
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


def test_the_splits_counters_say_what_ran(app, answered):
    """Every prompt token ran the self-decoder, one row a prompt the
    cross-decoder; every decoded token both; three Mamba layers a row."""
    rows = answered["rows"]
    tokens, decoded = sum(PROMPTS), len(PROMPTS) * (NEW - 1)
    assert rows[("vmt_self_decoder_rows_total", "prefill")] == tokens
    assert rows[("vmt_cross_decoder_rows_total", "prefill")] == len(PROMPTS)
    assert rows[("vmt_self_decoder_rows_total", "decode")] == decoded
    assert rows[("vmt_cross_decoder_rows_total", "decode")] == decoded
    assert rows[("vmt_ssm_scan_tokens_total", "prefill")] == 3 * tokens
    assert rows[("vmt_ssm_scan_tokens_total", "decode")] == 3 * decoded
    conn = http.client.HTTPConnection("127.0.0.1", app.http_port, timeout=30)
    try:
        conn.request("GET", "/metrics?format=prometheus")
        text = conn.getresponse().read().decode()
    finally:
        conn.close()
    for name in ("vmt_self_decoder_rows_total",
                 "vmt_cross_decoder_rows_total", "vmt_ssm_scan_tokens_total",
                 "vmt_shared_kv_page_reads_total",
                 "vmt_seq_ring_bytes_in_use", "vmt_seqstate_bytes_in_use"):
        assert name in text, name


def test_the_aot_fingerprint_names_the_model(tmp_path):
    from vilbert_multitask_tpu.config import LagunaConfig

    phi = framework_config(tmp_path)
    laguna = dataclasses.replace(phi, generate=dataclasses.replace(
        phi.generate, model=LagunaConfig().tiny()))
    assert generate_fingerprint(phi)["generate"]["model"][
        "model_type"] == "phi4flash"
    assert generate_fingerprint(phi) != generate_fingerprint(laguna)
