"""The seam between what is true of every cell and what is one
architecture's: ``run.py``, ``harness/`` and ``reduce/`` name nothing of
ViLBERT, and a family made of new files only (``stub/``, with a manifest of
its own, never entered in ``BENCHMARK.json``) runs through the same
``run.py`` to ``correct: true``, its control to ``correct: false``, with a
reader kind that was brought as a file."""

import glob
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness.spec import BENCH_DIR, ROOT

STUB = os.path.join(BENCH_DIR, "tests", "stub", "manifest.json")
GENERIC = sorted(
    [os.path.join(BENCH_DIR, "run.py")]
    + glob.glob(os.path.join(BENCH_DIR, "harness", "**", "*.py"),
                recursive=True)
    + glob.glob(os.path.join(BENCH_DIR, "reduce", "**", "*.py"),
                recursive=True))
# Keys, tasks, heads and classes of one family, and the name of another.
FAMILY_WORDS = ("v_feature_size", "max_regions", "task_id", "image_list",
                '"question"', "ViLBertConfig", "input_cache", "row_buckets",
                "gallery", "stub.py", "families.vilbert", "families/vilbert")


@pytest.mark.parametrize("path", GENERIC,
                         ids=lambda p: os.path.relpath(p, BENCH_DIR))
def test_the_generic_files_name_nothing_of_a_family(path):
    with open(path) as f:
        text = f.read()
    assert [w for w in FAMILY_WORDS if w in text] == []


def test_the_generic_files_are_the_ones_the_readme_says():
    names = {os.path.relpath(p, BENCH_DIR) for p in GENERIC}
    assert {"run.py", "harness/spec.py", "harness/loadgen.py",
            "harness/arrivals.py", "harness/weights.py", "reduce/readers.py",
            "reduce/trace.py", "reduce/flops.py"} <= names


def run_stub(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--manifest",
         STUB, "--workload", "stub.trickle", "--seconds", "4", "--rehearsal",
         *args],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("seed", ["5", "2147487103"])
def test_a_family_of_new_files_only_runs_to_correct(seed):
    result, stderr = run_stub("--seed", seed, "--trace", "1")
    assert result["correct"] is True, stderr[-2000:]
    assert result["rehearsal"] is True
    assert result["attempted"] == 32 and result["failed"] == 0
    assert set(result["compared"]) == {"y_err_max", "unanswered",
                                       "compiles_in_window"}
    assert "compared y_err_max" in stderr
    # A kind found under reduce/kinds/, a span and a phase of the family's.
    assert result["metrics"]["answers_per_s"]["value"] == pytest.approx(
        8.0, abs=1.0)
    assert result["metrics"]["stub_forward_p50_ms"]["value"] > 0
    assert result["metrics"]["stub_boot_s"]["value"] > 0
    assert "stub_boot_s" in result["setup_phases"]


def test_its_end_to_end_line_and_its_control():
    result, _ = run_stub("--seed", "6", "--trace", "0")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"latency_p50_ms", "setup_s"}
    result, _ = run_stub("--seed", "6", "--trace", "0", "--control", "bf16")
    assert result["correct"] is False
    err = result["compared"]["y_err_max"]
    assert err["value"] > 10 * err["limit"]
    assert result["compared"]["unanswered"]["value"] == 0


@pytest.mark.parametrize("manifest, cell", [
    (os.path.join(ROOT, "BENCHMARK.json"), "base.interactive"),
    (STUB, "stub.trickle")])
def test_finding_a_family_does_not_touch_jax(manifest, cell):
    """``run.py`` starts the generator child before JAX is imported; the
    family is found before that, so importing one may not import JAX."""
    code = ("import sys; from benchmark.harness.spec import Spec; "
            f"Spec({cell!r}, {manifest!r}); "
            "sys.exit('jax' in sys.modules)")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
