"""Each cell end to end at the tiny size, on the CPU, behind the explicit
rehearsal flag: the phases of a run, never a measurement. Then the two ways
a run has to fail: the control (the reference in float8 put in the program's
place) and an answer altered where it is produced."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.spec import BENCH_DIR, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    CELLS = [w["name"] for w in json.load(f)["workloads"]]
ONE_PER_TRAFFIC = ["base.interactive", "base.saturated"]


def run(script, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, script), *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_every_phase_and_says_it_is_one(cell, trace):
    result, stderr = run("run.py", "--workload", cell, "--seed", "2147484001",
                         "--seconds", "5", "--trace", trace, "--rehearsal")
    assert result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, stderr[-2000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert "busy_s" not in result["device"]  # no device time off the chip
    assert not any("mfu" in name or "idle" in name or "device" in name
                   for name in result["metrics"])
    assert "compared score_err_rms" in stderr


def test_without_the_flag_and_without_a_chip_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("cell", ONE_PER_TRAFFIC)
def test_the_control_in_the_next_precision_down_is_not_correct(cell):
    result, _ = run("run.py", "--workload", cell, "--seed", "77",
                    "--seconds", "5", "--trace", "0", "--rehearsal",
                    "--control", "fp8")
    assert result["correct"] is False
    assert result["compared"]["unanswered"]["value"] == 0
    rms = result["compared"]["score_err_rms"]
    assert rms["value"] > 3 * rms["limit"]


@pytest.mark.parametrize("cell", ONE_PER_TRAFFIC)
def test_an_answer_altered_where_it_is_produced_is_not_correct(cell):
    result, _ = run(os.path.join("tests", "broken_run.py"), "answer_altered",
                    "--workload", cell, "--seed", "78", "--seconds", "5",
                    "--trace", "0")
    assert result["correct"] is False
