"""chip_smoke.py and the other chip entry points refuse to run off the chip
unless told to: without a TPU they exit non-zero naming the platform they
found, print no result, and never fall back to the CPU. The smoke's
``--cpu-rehearsal`` — the explicit way to run its phases here — must pass."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, timeout, **env):
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, capture_output=True, text=True,
        timeout=timeout, env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


@pytest.mark.parametrize("argv", [
    ["chip_smoke.py"],
    ["-m", "vilbert_multitask_tpu.serve.app"],
], ids=["chip_smoke", "serve.app"])
def test_entry_points_refuse_the_cpu(argv):
    r = _run(argv, timeout=120)
    assert r.returncode != 0, r.stdout[-2000:]
    assert "'cpu'" in r.stdout + r.stderr  # the platform it found, named
    assert '"ok"' not in r.stdout and '"metric"' not in r.stdout


def _rehearse(n_devices: int) -> dict:
    r = _run(["chip_smoke.py", "--cpu-rehearsal"], timeout=600,
             XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    assert r.returncode == 0, (r.stdout[-3000:], r.stderr[-3000:])
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": n_devices}}
    report = json.loads(next(
        ln for ln in r.stdout.splitlines()
        if ln.startswith("report: "))[len("report: "):])
    # Every image bucket alone, and at least one throughput-sized chunk.
    buckets = set(report["served"]["row_buckets_dispatched"])
    assert {1, 2, 4, 8, 10} <= buckets and buckets & {16, 32}
    assert report["served"]["requests"] >= 10
    return report


def test_cpu_rehearsal_passes():
    assert _rehearse(1)["program_family"] == "rows"


def test_cpu_rehearsal_passes_on_a_mesh():
    """More than one visible device: ServeApp builds the dp mesh by itself
    and serves the ``batched`` family — the path a four-chip host takes."""
    assert _rehearse(8)["program_family"] == "batched"
