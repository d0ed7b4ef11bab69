"""The ``zaya`` family and its cell ``zaya.longctx``: the rehearsal ends
``correct`` with every countable metric and its float8 control does not;
the traffic fits the configuration as reckoned, and its lengths and order
are one for every seed; the configuration holds
every published width; what the family counts is what the shapes say; the
new reader kind reads what it says it reads and returns None where there
is nothing. CPU; no timing here is a measurement. Run with the other
benchmark tests (``-p no:xdist``)."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.spec import BENCH_DIR, ROOT, Spec
from benchmark.reduce import readers
from benchmark.reduce.kinds import trace_cca_prep_roofline

CELL = "zaya.longctx"


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--rehearsal", *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


def test_rehearsal_ends_correct_with_every_countable_metric():
    result, out = run("--seed", "2147484001", "--seconds", "20", "--trace",
                      "1")
    assert result["rehearsal"] is True and result["correct"] is True, \
        out[-2000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    for name in ("decode_batch_fill", "prefill_tokens_per_s",
                 "decode_tokens_per_s", "kv_pages_in_use_share",
                 "seq_admit_refused_in_window", "engine_prefill_p50_ms",
                 "engine_decode_step_p50_ms", "compiles_in_window.longctx",
                 "engine_init_s.longctx", "warmup_s.longctx",
                 "moe_expert_load_max_over_mean", "moe_skip_share"):
        assert result["metrics"][name]["value"] is not None, name
    assert 0 < result["metrics"]["moe_skip_share"]["value"] < 50
    # the router's choices in float32 and in bfloat16 operands are printed
    assert '"top1_flip_share"' in out
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
    assert deck == {8192: 4, 16384: 4, 32768: 4, 65536: 2, 98304: 1,
                    126976: 1}
    assert sum(deck.values()) == traffic["clients"] == 16 <= engine["slots"]
    assert sum(n * c for n, c in deck.items()) == 585728 == 16 * 36608
    assert (traffic["arrivals"], traffic["jitter"], traffic["max_new_tokens"],
            traffic["logit_ids"]) == ("closed", 0.125, 128, 16)
    page = engine["page_size"]
    pages = sum(c * -(-(n + traffic["max_new_tokens"]) // page)
                for n, c in deck.items())
    assert (pages, engine["kv_pages"]) == (2304, 2432)
    assert engine["kv_pages"] % engine["decode_attention_pages"] == 0


def test_every_seed_sends_the_same_lengths_in_the_same_order():
    """The family's schedule: the callers' lists of kinds and lengths are
    one for every seed (a seed-drawn order and seed-drawn lengths spread
    ``rows_per_s`` past what a cell may); the ids are the seed's. Every
    length lies up to an eighth under its deck entry, and no two of a pass
    are equal."""
    spec = Spec(CELL)
    assets = {"vocab_size": spec.config["vocab_size"]}
    a, b = (spec.family.schedule(spec.traffic, seed, 51, assets)
            for seed in (5, 2147484001))
    shape = [[(r["client"], r["kind"], len(r["body"]["prompt_ids"]))
              for r in s["requests"]] for s in (a, b)]
    assert shape[0] == shape[1]
    assert a["requests"][0]["body"]["prompt_ids"] != \
        b["requests"][0]["body"]["prompt_ids"]
    for r in a["requests"]:
        nominal, length = r["kind"][0], len(r["body"]["prompt_ids"])
        assert nominal - nominal // 8 < length <= nominal
    first = [len(r["body"]["prompt_ids"]) for r in a["requests"][:16]]
    assert len(set(first)) == 16
    assert sorted(r["kind"][0] for r in a["requests"][:16]) == sorted(
        int(k["prompt_tokens"]) for k in spec.traffic["deck"]
        for _ in range(int(k["count"])))


def test_the_configuration_holds_every_published_width():
    spec = Spec(CELL)
    c = spec.config
    catalog = os.environ.get("MODEL_CATALOG", "")
    if catalog and os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            (row,) = [r for r in map(json.loads, f)
                      if r["name"] == "ZAYA1-8B"]
        assert c["source"] == row["source_url"]
        differing = sorted(k for k, v in row["config"].items()
                           if c.get(k) != v)
        assert differing == sorted(c["reduced"])
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["published"] == {"num_hidden_layers": 40}
    model = spec.family.model_of(c)
    assert model["layer_types"] == ["hybrid"] * 10
    assert (model["hidden_size"], model["head_dim"],
            model["num_attention_heads"], model["num_key_value_heads"],
            model["num_experts"], model["num_experts_per_tok"],
            model["moe_intermediate_size"], model["router_hidden_size"],
            model["vocab_size"], model["cca_time0"], model["cca_time1"]) == (
        2048, 128, 8, 2, 16, 1, 2048, 256, 262272, 2, 2)
    for key in ("deployment", "assumed", "weights", "engine"):
        assert c[key]


def test_what_the_family_counts_is_what_the_shapes_say():
    import importlib

    spec = Spec(CELL)
    model = spec.family.model_of(spec.config)
    shapes = importlib.import_module(
        "benchmark.reference.zaya").param_shapes(model)
    layer = shapes["layers"][0]
    assert layer["experts_gate_up"] == (16, 2048, 4096)
    assert layer["wqk"] == (2048, 1280) and layer["conv1"] == (2, 10, 128,
                                                                 128)
    assert layer["router_out"] == (256, 17)
    assert shapes["embed"] == (262272, 2048)
    H = 2048
    attention = H * 1280 + H * 256 + 1024 * H + 2 * 10 * 128 * 128
    router = H * 256 + 2 * 256 * 256 + 256 * 17
    assert spec.family.flops_per_unit(spec.config) == 2 * 10 * (
        attention + router + 3 * H * 2048)


def _ctx(ops, calls=20.0, rows=20 * 1900.0):
    return {"trace": {"ops": ops, "modules": [], "busy_s": 1.0,
                      "window_s": 2.0},
            "counters": {"before": {}, "after": {
                "vmt_cca_calls_total": calls, "vmt_cca_rows_total": rows}},
            "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


EVENT = ("%cca_prep.3 = (bf16[2048,1024]{1,0}, bf16[2048,256]{1,0}) "
         "custom-call(f32[2056,1280]{1,0} %window, f32[2,1,1280]{2,1,0} "
         "%taps, bf16[2,10,128,128]{3,2,1,0} %w1, f32[2]{0} %tau, "
         "f32[2048,128]{1,0} %cos, f32[2048,128]{1,0} %sin)")


def test_cca_roofline_takes_its_work_from_the_counters_not_the_buffers():
    flops, moved = trace_cca_prep_roofline.cca_prep_cost(1900, 10, 128, 2, 2)
    assert flops == 4 * 10 * 128 * 128 * 1900
    assert moved == 1900 * 1280 * 6 + 2 * 10 * 128 * 128 * 2
    least = max(flops / 197e12, moved / 819e9)
    ops = [(EVENT, 0.1 * i, 0.0002) for i in range(5)] + [
        ("%fusion.1 = f32[8] fusion()", 0.0, 0.5)]
    reader = {"kind": "trace_cca_prep_roofline",
              "params": {"op_contains": "cca_prep"}}
    got = readers.read(reader, _ctx(ops))
    assert got == pytest.approx(100 * 5 * least / 0.001)
    # the bucket's 2048 rows would have read higher
    worst = trace_cca_prep_roofline.cca_prep_cost(2048, 10, 128, 2, 2)
    assert max(worst[0] / 197e12, worst[1] / 819e9) > 1.05 * least
    share = {"kind": "trace_op_share", "params": {"op_contains": "cca_prep"}}
    assert readers.read(share, _ctx(ops)) == pytest.approx(100 * 0.001)


def test_readers_that_find_nothing_return_none():
    roofline = {"kind": "trace_cca_prep_roofline",
                "params": {"op_contains": "cca_prep"}}
    no_events = _ctx([("%fusion.1 = f32[8] fusion()", 0.0, 0.5)])
    assert readers.read(roofline, no_events) is None
    no_counters = _ctx([(EVENT, 0.0, 0.0002)])
    no_counters["counters"]["after"] = {}
    assert readers.read(roofline, no_counters) is None   # the parent
    assert readers.read(roofline, {"counters": no_counters["counters"]}) \
        is None                                          # a rehearsal
    skip = {"kind": "counter_ratio", "params": {
        "numerator": ["vmt_moe_skipped_total"],
        "denominator": ["vmt_moe_skipped_total", "vmt_moe_pairs_total"],
        "scale": 100.0}}
    assert readers.read(skip, {"counters": {"before": {}, "after": {
        "vmt_moe_pairs_total": 10.0}}}) is None           # the parent
    assert readers.read(skip, {"counters": {"before": {}, "after": {
        "vmt_moe_pairs_total": 90.0, "vmt_moe_skipped_total": 10.0}}}) \
        == pytest.approx(10.0)
