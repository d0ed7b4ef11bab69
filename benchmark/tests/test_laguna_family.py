"""The ``laguna`` family and its cell ``laguna.mixedqueue`` (ISSUE 32): the
rehearsal ends ``correct`` with every countable metric and its float8
control does not; the traffic fits the configuration as the issue reckoned
it; the configuration holds every published width; what the family counts
is what the shapes say; the two reader kinds read what they say they read
and return None where there is nothing. CPU; no timing here is a
measurement. Run with the other benchmark tests (``-p no:xdist``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.spec import BENCH_DIR, ROOT, Spec
from benchmark.reduce import readers
from benchmark.reduce.kinds import trace_moe_roofline

CELL = "laguna.mixedqueue"


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
                 "engine_decode_step_p50_ms", "compiles_in_window.mixedqueue",
                 "engine_init_s.mixedqueue", "warmup_s.mixedqueue",
                 "moe_expert_load_max_over_mean"):
        assert result["metrics"][name]["value"] is not None, name
    assert result["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1.0
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
    assert deck == {512: 18, 1024: 12, 2048: 8, 4096: 5, 8192: 3, 16384: 1,
                    32768: 1}
    assert sum(deck.values()) == traffic["clients"] == 48 <= engine["slots"]
    assert sum(n * c for n, c in deck.items()) == 132096
    assert (traffic["arrivals"], traffic["jitter"], traffic["max_new_tokens"],
            traffic["logit_ids"]) == ("closed", 0.125, 256, 16)
    page = engine["page_size"]
    pages = sum(c * -(-(n + traffic["max_new_tokens"]) // page)
                for n, c in deck.items())
    assert (pages, engine["kv_pages"]) == (564, 576)
    assert engine["kv_pages"] % engine["decode_attention_pages"] == 0


def test_the_configuration_holds_every_published_width():
    spec = Spec(CELL)
    c = spec.config
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "Laguna-S-2.1"]
        assert c["source"] == row["source_url"]
        differing = sorted(k for k, v in row["config"].items()
                           if c.get(k) != v)
        assert differing == sorted(c["reduced"])
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                              "vocab_size": 100352}
    model = spec.family.model_of(c)
    assert (model["num_experts"], model["experts_held"]) == (256, [0, 128])
    assert model["layer_types"] == ["full_attention"] + [
        "sliding_attention"] * 3 + ["full_attention"]
    assert model["num_attention_heads_per_layer"] == [48, 72, 72, 72, 48]
    assert model["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (model["hidden_size"], model["head_dim"],
            model["num_key_value_heads"], model["intermediate_size"],
            model["moe_intermediate_size"], model["num_experts_per_tok"],
            model["sliding_window"], model["moe_routed_scaling_factor"]) == (
        3072, 128, 8, 12288, 1024, 10, 512, 2.5)
    for key in ("deployment", "assumed", "weights", "engine"):
        assert c[key]


def test_what_the_family_counts_is_what_the_shapes_say():
    import importlib

    spec = Spec(CELL)
    model = spec.family.model_of(spec.config)
    shapes = importlib.import_module(
        "benchmark.reference.laguna").param_shapes(model)
    assert shapes["layers"][1]["experts_gate_up"] == (128, 3072, 2048)
    assert shapes["layers"][4]["wq"] == (3072, 48 * 128)
    assert shapes["lm_head"] == (3072, 50176)
    H, W = 3072, 1024
    attention = 2 * (H * (48 + 16) * 128 + 48 * 128 * H + H * 48) + 3 * (
        H * (72 + 16) * 128 + 72 * 128 * H + H * 72)
    used = attention + 3 * H * 12288 + 4 * (3 * H * W + H * 256
                                            + 5 * 3 * H * W)
    assert spec.family.flops_per_unit(spec.config) == 2 * used


def _ctx(ops, calls=8.0, pairs=8 * 240.0, touched=8 * 110.0):
    return {"trace": {"ops": ops, "modules": [], "busy_s": 1.0,
                      "window_s": 2.0},
            "counters": {"before": {}, "after": {
                "vmt_moe_calls_total": calls, "vmt_moe_pairs_total": pairs,
                "vmt_moe_experts_touched_total": touched}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


EVENT = ("%moe_experts.3 = f32[2688,3072]{1,0} custom-call(s32[168] %a, "
         "s32[1] %b, bf16[2688,3072] %rows, bf16[128,3072,2048] %gu, "
         "bf16[128,1024,3072] %down)")


def test_moe_roofline_takes_its_work_from_the_counters_not_the_buffers():
    flops, moved = trace_moe_roofline.moe_cost(240, 110, 3072, 1024)
    assert flops == 6 * 3072 * 1024 * 240
    assert moved == 110 * 3 * 3072 * 1024 * 2 + 240 * 3072 * 4
    least = max(flops / 197e12, moved / 819e9)
    ops = [(EVENT, 0.1 * i, 0.004) for i in range(5)] + [
        ("%fusion.1 = f32[8] fusion()", 0.0, 0.5)]
    reader = {"kind": "trace_moe_roofline",
              "params": {"op_contains": "moe_experts"}}
    got = readers.read(reader, _ctx(ops))
    assert got == pytest.approx(100 * 5 * least / 0.020)
    assert got < 100
    # the buffers' 2688 rows and 128 experts would have read far higher
    worst = trace_moe_roofline.moe_cost(2688, 128, 3072, 1024)
    assert max(worst[0] / 197e12, worst[1] / 819e9) > 1.15 * least
    share = {"kind": "trace_op_share",
             "params": {"op_contains": "moe_experts"}}
    assert readers.read(share, _ctx(ops)) == pytest.approx(100 * 0.020 / 1.0)


def test_readers_that_find_nothing_return_none():
    roofline = {"kind": "trace_moe_roofline",
                "params": {"op_contains": "moe_experts"}}
    share = {"kind": "trace_op_share",
             "params": {"op_contains": "moe_experts"}}
    no_events = _ctx([("%fusion.1 = f32[8] fusion()", 0.0, 0.5)])
    assert readers.read(roofline, no_events) is None
    assert readers.read(share, no_events) is None
    no_counters = _ctx([(EVENT, 0.0, 0.004)])
    no_counters["counters"]["after"] = {}
    assert readers.read(roofline, no_counters) is None   # the parent
    assert readers.read(roofline, {"counters": no_counters["counters"]}) \
        is None                                          # a rehearsal
    load = {"kind": "histogram_mean", "params": {
        "instrument": "vmt_moe_expert_load_max_over_mean"}}
    assert readers.read(load, {"histograms": {}}) is None
