"""The ``olmo_hybrid`` family and its cell ``olmo.longdocs`` (ISSUE 28, test
g): the rehearsal ends ``correct``, its float8 control does not, the traffic
fits the configuration, every seed sends the deck's kinds in the deck's
shares, and what the family counts is what the program's shapes say. CPU;
no timing here is a measurement. Run with the other benchmark tests
(``-p no:xdist``: they share ``benchmark/.cache/state``)."""

import collections
import dataclasses
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import arrivals
from benchmark.harness.spec import BENCH_DIR, ROOT, Spec
from benchmark.reduce import readers

CELL = "olmo.longdocs"


def run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELL, "--rehearsal", *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def test_rehearsal_ends_correct_with_every_countable_metric():
    result, stderr = run("--seed", "2147484001", "--seconds", "6",
                         "--trace", "1")
    assert result["rehearsal"] is True and result["correct"] is True, \
        stderr[-2000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    for name in ("logit_err_rms", "logit_err_max", "argmax_gap_max",
                 "unanswered", "compiles_in_window"):
        assert f"compared {name}" in stderr
    # What a CPU run can count it reports; a device time it never does.
    for name in ("decode_batch_fill", "prefill_tokens_per_s",
                 "decode_tokens_per_s", "kv_pages_in_use_share",
                 "seq_admit_refused_in_window", "engine_prefill_p50_ms",
                 "engine_decode_step_p50_ms", "compiles_in_window.longdocs",
                 "engine_init_s.longdocs", "warmup_s.longdocs"):
        assert result["metrics"][name]["value"] is not None, name
    assert not any("mfu" in n or "idle" in n or "device" in n
                   or "roofline" in n for n in result["metrics"])


def test_the_control_in_the_next_precision_down_is_not_correct():
    result, _ = run("--seed", "77", "--seconds", "6", "--trace", "0",
                    "--control", "fp8")
    assert result["correct"] is False
    assert result["compared"]["unanswered"]["value"] == 0
    worst = result["compared"]["logit_err_max"]
    assert worst["value"] > 3 * worst["limit"]


def test_the_traffic_fits_the_configuration():
    spec = Spec(CELL)
    spec.family.check_traffic(spec.config, spec.traffic)
    deck = spec.traffic["deck"]
    tokens = sum(int(k["prompt_tokens"]) * int(k["count"]) for k in deck)
    callers = sum(int(k["count"]) for k in deck)
    assert callers == spec.traffic["clients"] == 24
    resident = tokens + callers * spec.traffic["max_new_tokens"]
    engine = spec.config["engine"]
    pool = engine["kv_pages"] * engine["page_size"]
    assert pool == spec.config["max_position_embeddings"]
    assert (resident, round(100 * resident / pool)) == (60416, 92)
    with pytest.raises(ValueError, match="past the context"):
        spec.family.check_traffic(spec.config, dict(
            spec.traffic, deck=[{"prompt_tokens": 65536, "count": 1}]))


@pytest.mark.parametrize("seed", [1, 2147484001, 2 ** 31 + 5])
def test_every_seed_sends_the_decks_kinds_in_the_decks_shares(seed):
    spec = Spec(CELL)
    sched = spec.family.schedule(spec.traffic, seed, 51.0,
                                 {"vocab_size": 1000})
    requests = sched["requests"]
    kinds = collections.Counter(r["kind"][0] for r in requests)
    passes = len(requests) // 24
    assert len(requests) == passes * 24
    assert kinds == {int(k["prompt_tokens"]): passes * int(k["count"])
                     for k in spec.traffic["deck"]}
    assert arrivals.composition([dict(r, due=0.0) for r in requests]) == \
        sorted((n,) for n, c in kinds.items() for _ in range(c))
    for r in requests:
        nominal, n = r["kind"][0], len(r["body"]["prompt_ids"])
        assert nominal * 0.875 < n <= nominal
        assert r["rows"] == r["body"]["max_new_tokens"] == 128
        assert len(set(r["body"]["logit_ids"])) == 16
        assert r["body"]["question"] == r["key"]
    # Every pass of 24 consecutive sessions (one a caller) is one deck.
    for start in range(0, len(requests), 24):
        assert collections.Counter(
            r["kind"][0] for r in requests[start:start + 24]) == {
                int(k["prompt_tokens"]): int(k["count"])
                for k in spec.traffic["deck"]}
        assert sorted(r["client"] for r in requests[start:start + 24]) \
            == list(range(24))
    # No two prompts of a deck's worth of consecutive requests are equal.
    lengths = [len(r["body"]["prompt_ids"]) for r in requests[:240]]
    assert all(len(set(lengths[i:i + 24])) > 20 for i in range(0, 216))
    other = spec.family.schedule(spec.traffic, seed + 1, 51.0,
                                 {"vocab_size": 1000})["requests"]
    assert [r["kind"] for r in other] != [r["kind"] for r in requests]


def test_the_family_counts_what_the_programs_shapes_say():
    from vilbert_multitask_tpu.config import OlmoHybridConfig
    from vilbert_multitask_tpu.models import olmo_hybrid

    spec = Spec(CELL)
    model = spec.family.model_of(spec.config)
    model.pop("model_type"), model.pop("rope_parameters")
    cfg = OlmoHybridConfig(**model)
    shapes = olmo_hybrid.param_shapes(cfg)
    reference = spec.family._reference(spec.config)
    assert shapes == reference.param_shapes(spec.family.model_of(spec.config))
    # 12 linear layers of 215.3 M and 4 full ones of 185.8 M in matrices.
    flops = spec.family.flops_per_unit(spec.config)
    assert flops == 2 * (12 * (88473600 + 2 * 115200 + 126812160)
                         + 4 * (58982400 + 126812160))
    assert round(flops / 1e9, 2) == 6.66
    # The catalog's keys sit at the file's top level, under their own names.
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Olmo-Hybrid-7B"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert spec.config[key] == 16
        elif key == "layer_types":
            assert spec.config[key] == value[:16]
        else:
            assert spec.config[key] == value, key
    assert spec.config["source"] == row["source_url"]
    assert spec.config["reduced"] == ["num_hidden_layers"]
    assert spec.config["published"] == {"num_hidden_layers": 32}
    assert dataclasses.asdict(cfg)["layer_types"] == tuple(
        OlmoHybridConfig().layer_types[:16])


def test_the_rooflines_cost_is_the_recurrences_own():
    kind = readers.find_kind("trace_gated_delta_roofline")
    from benchmark.reduce.kinds import trace_gated_delta_roofline as mod

    flops, moved = mod.gated_delta_cost(2048, 30, 96, 192)
    assert flops == 6 * 96 * 192 * 30 * 2048
    assert moved == 2048 * 30 * ((96 + 96 + 192 + 192) * 2 + 8) \
        + 2 * 30 * 96 * 192 * 4
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    name = ("%gated_delta_scan.3 = (f32[30,32,64,192]{3,2,1,0:T(8,128)}, "
            "f32[30,96,192]{2,1,0:T(8,128)}) custom-call(f32[30,32,64,192]"
            "{3,2,1,0} %u, f32[30,32,64,96]{3,2,1,0} %w)")
    least = max(flops / 197e12, moved / 819e9)
    ctx = {"trace": {"ops": [(name, 0.0, 4 * least), ("%copy.1", 0, 1.0)]},
           "peaks": peaks}
    assert kind(ctx, op_contains="gated_delta_scan") == pytest.approx(25.0)
    # A program without the kernel (the parent): nothing to read, no raise.
    assert kind({"trace": {"ops": [("%copy.1", 0, 1.0)]}, "peaks": peaks},
                op_contains="gated_delta_scan") is None
    assert kind({}, op_contains="gated_delta_scan") is None
    scaled = readers.find_kind("histogram_mean_scaled")
    assert scaled({"histograms": {"h": {(): [0.5, 0.7]}}}, instrument="h",
                  scale=100.0) == pytest.approx(60.0)
    assert scaled({"histograms": {}}, instrument="h", scale=100.0) is None
