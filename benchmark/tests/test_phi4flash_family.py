"""The ``phi4flash`` family and its cell ``phi4flash.reasoning`` (ISSUE
34): the rehearsal ends ``correct`` with every countable metric and its
float8 control does not; the traffic fits the configuration as the issue
reckoned it; the configuration holds every published size, nothing cut;
what the family counts is what the shapes say; the sample takes every kind
of the deck; the scan's reader takes its tokens from the counters and
returns None where there is nothing. CPU; no timing here is a measurement.
Run with the other benchmark tests (``-p no:xdist``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.spec import BENCH_DIR, ROOT, Spec
from benchmark.reduce import readers
from benchmark.reduce.kinds import trace_selective_scan_roofline as scan_kind

CELL = "phi4flash.reasoning"


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--rehearsal", *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_rehearsal_ends_correct_with_every_countable_metric():
    result, stderr = run("--seed", "2147484001", "--seconds", "20",
                         "--trace", "1")
    assert result["rehearsal"] is True and result["correct"] is True, \
        stderr[-2000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    for name in ("decode_batch_fill", "prefill_tokens_per_s",
                 "decode_tokens_per_s", "kv_pages_in_use_share",
                 "seq_admit_refused_in_window", "engine_prefill_p50_ms",
                 "engine_decode_step_p50_ms", "compiles_in_window.reasoning",
                 "engine_init_s.reasoning", "warmup_s.reasoning",
                 "cross_decoder_row_share"):
        assert result["metrics"][name]["value"] is not None, name
    # 8 generated tokens a prompt of 24-300: one row in ten or so went
    # through the stack's second half; 100 would be a prefill that skips
    # nothing.
    assert 3 < result["metrics"]["cross_decoder_row_share"]["value"] < 30
    # What a CPU run can count it reports; a device time it never does.
    assert not any("mfu" in n or "idle" in n or "device" in n
                   or "roofline" in n for n in result["metrics"])


def test_the_control_in_the_next_precision_down_is_not_correct():
    result, _ = run("--seed", "77", "--seconds", "20", "--trace", "0",
                    "--control", "fp8")
    assert result["correct"] is False
    assert result["compared"]["unanswered"]["value"] == 0


def test_the_traffic_fits_the_configuration_as_reckoned():
    spec = Spec(CELL)
    spec.family.check_traffic(spec.config, spec.traffic)
    traffic, engine = spec.traffic, spec.config["engine"]
    deck = {int(k["prompt_tokens"]): int(k["count"]) for k in traffic["deck"]}
    assert deck == {256: 16, 512: 28, 1024: 26, 2048: 16, 4096: 8, 8192: 2}
    assert sum(deck.values()) == traffic["clients"] == 96 <= engine["slots"]
    assert sum(n * c for n, c in deck.items()) == 126976
    assert (traffic["arrivals"], traffic["jitter"], traffic["max_new_tokens"],
            traffic["logit_ids"], traffic["warm_seconds"]) == (
        "closed", 0.125, 256, 16, 8)
    page = engine["page_size"]
    pages = sum(c * -(-(n + traffic["max_new_tokens"]) // page)
                for n, c in deck.items())
    assert (pages, engine["kv_pages"]) == (592, 640)
    assert engine["kv_pages"] % engine["decode_attention_pages"] == 0
    assert max(engine["decode_buckets"]) == engine["slots"]


def test_the_configuration_holds_every_published_size():
    spec = Spec(CELL)
    c = spec.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Phi-4-mini-flash-reasoning"]
        assert c["source"] == row["source_url"]
        assert {k: c.get(k) for k in row["config"]} == row["config"]
    assert c["reduced"] == []
    model = spec.family.model_of(c)
    assert (model["num_hidden_layers"], model["hidden_size"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["intermediate_size"], model["sliding_window"],
            model["mb_per_layer"], model["vocab_size"],
            model["tie_word_embeddings"], model["layer_norm_eps"]) == (
        32, 2560, 40, 20, 10240, 512, 2, 200064, True, 1e-5)
    for key in ("deployment", "assumed", "weights", "engine"):
        assert c[key]
    assert all(isinstance(v, str) and len(v) > 40
               for v in c["assumed"].values())
    # The program takes the same keys, and nothing else of the file.
    from vilbert_multitask_tpu.config import Phi4FlashConfig

    served = Phi4FlashConfig(**model)
    assert (served.d_inner, served.dt_rank, served.head_dim) == (5120, 160,
                                                                 64)


def test_what_the_family_counts_is_what_the_shapes_say():
    import importlib

    spec = Spec(CELL)
    model = spec.family.model_of(spec.config)
    shapes = importlib.import_module(
        "benchmark.reference.phi4flash").param_shapes(model)
    assert len(shapes["layers"]) == 32 and "lm_head" not in shapes
    assert shapes["embed"] == (200064, 2560)
    assert shapes["layers"][0]["in_proj"] == (2560, 10240)
    assert shapes["layers"][0]["x_proj"] == (5120, 160 + 32)
    assert shapes["layers"][17]["wqkv"] == (2560, 80 * 64)
    assert shapes["layers"][18]["gmu_in"] == (2560, 5120)
    assert shapes["layers"][19]["wq"] == (2560, 2560)
    H, I, Ci = 2560, 10240, 5120
    mlp = H * 2 * I + I * H
    mamba = H * 2 * Ci + Ci * 192 + 160 * Ci + Ci * H + mlp
    attention = H * 80 * 64 + H * H + mlp
    gmu = 2 * H * Ci + mlp
    cross = 2 * H * H + mlp
    self_decoder = 9 * mamba + 9 * attention
    whole = self_decoder + 7 * gmu + 7 * cross + 200064 * H
    assert spec.family.flops_per_unit(spec.config) == 2 * whole
    assert 7.6e9 < 2 * whole < 7.8e9
    assert spec.family._matrices(shapes) == (self_decoder, whole)
    assert 0.50 < self_decoder / whole < 0.52


def test_the_sample_takes_every_kind_then_the_longest():
    spec = Spec(CELL)
    requests = [{"i": i, "kind": (kind,), "deck_count": 1}
                for i, kind in enumerate(
                    [256] * 30 + [512] * 30 + [1024] * 10 + [2048] * 5
                    + [4096] * 3 + [8192] * 1)]
    stamps = {r["i"]: {"result": {"tokens": [1, 2]}} for r in requests}
    picked = spec.family.sample(requests, stamps, seed=5, limit=24)
    kinds = [r["kind"][0] for r in picked]
    assert len(picked) == 24
    assert {k: kinds.count(k) for k in set(kinds)} == {
        8192: 1, 4096: 3, 2048: 5, 1024: 10, 512: 3, 256: 2}
    assert all(r["served_tokens"] == [1, 2] for r in picked)
    again = spec.family.sample(requests, stamps, seed=5, limit=24)
    assert [r["i"] for r in again] == [r["i"] for r in picked]


def _ctx(ops, tokens=8 * 900.0, chunks=8.0):
    return {"trace": {"ops": ops, "modules": [], "busy_s": 1.0,
                      "window_s": 2.0},
            "counters": {"before": {}, "after": {
                "vmt_prefill_tokens_total": tokens,
                "vmt_prefill_attention_chunks_total": chunks}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


EVENT = ("%selective_scan.3 = (f32[1024,40,128]{2,1,0}, f32[16,40,128]"
         "{2,1,0}) custom-call(f32[65536] %bc, f32[1024,40,128] %c, "
         "f32[1024,40,128] %dt, f32[16,40,128] %a, f32[40,128] %d, "
         "f32[16,40,128] %h)")
READER = {"kind": "trace_selective_scan_roofline",
          "params": {"op_contains": "selective_scan"}}


def test_scan_roofline_takes_its_tokens_from_the_counters_not_the_bucket():
    flops, moved = scan_kind.selective_scan_cost(900, 5120, 16)
    assert flops == 9 * 5120 * 16 * 900
    assert moved == 900 * (3 * 5120 + 32) * 4 + 2 * 16 * 5120 * 4
    least = max(flops / 197e12, moved / 819e9)
    assert least == moved / 819e9       # the vector unit's work: bytes lead
    ops = [(EVENT, 0.1 * i, 0.0004) for i in range(9)] + [
        ("%fusion.1 = f32[8] fusion()", 0.0, 0.5)]
    got = readers.read(READER, _ctx(ops))
    assert got == pytest.approx(100 * 9 * least / 0.0036)
    assert got < 100
    # the bucket's 1024 rows would have read higher
    padded = scan_kind.selective_scan_cost(1024, 5120, 16)
    assert padded[1] / 819e9 > 1.1 * least
    share = {"kind": "trace_op_share",
             "params": {"op_contains": "selective_scan"}}
    assert readers.read(share, _ctx(ops)) == pytest.approx(100 * 0.0036)


def test_readers_that_find_nothing_return_none():
    no_events = _ctx([("%fusion.1 = f32[8] fusion()", 0.0, 0.5)])
    assert readers.read(READER, no_events) is None
    for name in ("ssm_device_share", "shared_kv_read_device_share"):
        with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
            assert readers.read(json.load(f), no_events) is None
    no_counters = _ctx([(EVENT, 0.0, 0.0004)])
    no_counters["counters"]["after"] = {}
    assert readers.read(READER, no_counters) is None
    assert readers.read(READER, {"counters": no_counters["counters"]}) \
        is None                                          # a rehearsal
    with open(os.path.join(BENCH_DIR, "metrics",
                           "cross_decoder_row_share.json")) as f:
        share = json.load(f)
    assert readers.read(share, no_counters) is None      # the parent
    rows = _ctx([])
    rows["counters"]["after"] = {"vmt_cross_decoder_rows_total": 170.0,
                                 "vmt_self_decoder_rows_total": 1000.0}
    assert readers.read(share, rows) == pytest.approx(17.0)
