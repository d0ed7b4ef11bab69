"""Where the harness finds things: everything is looked up by the name that
``BENCHMARK.json`` gives it, so a new cell, configuration, traffic mix or
per-layer metric is new files plus one new entry."""

from __future__ import annotations

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Spec:
    """One cell's view of ``BENCHMARK.json`` and the files it names."""

    def __init__(self, workload: str):
        self.manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                             f"has {sorted(cells)}")
        self.cell = cells[workload]
        (entry,) = [c for c in self.manifest["configs"]
                    if c["name"] == self.cell["config"]]
        self.config = _load(os.path.join(ROOT, entry["file"]))
        self.traffic = _load(os.path.join(
            BENCH_DIR, "traffic", self.cell["traffic"] + ".json"))
        self.limits = _load(os.path.join(
            BENCH_DIR, "limits", self.cell["name"] + ".json"))

    def _reported_here(self, metric: dict) -> bool:
        return self.cell["name"] in metric.get(
            "workloads", [self.cell["name"]])

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"]
                if self._reported_here(m)]

    def per_layer(self) -> list:
        """(manifest entry, reader file) for each metric of this cell."""
        return [(m, _load(reader_file(m["name"])))
                for m in self.manifest["per_layer"] if self._reported_here(m)]


def reader_file(metric: str) -> str:
    """``metrics/<name>.json``; a quantity split by what its cells report
    (``forward_mfu.interactive``, ``forward_mfu.saturated``) may share one
    reader, ``metrics/forward_mfu.json``."""
    for name in (metric, metric.rpartition(".")[0]):
        path = os.path.join(BENCH_DIR, "metrics", name + ".json")
        if name and os.path.exists(path):
            return path
    raise SystemExit(f"no reader file benchmark/metrics/{metric}.json")


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = _load(os.path.join(BENCH_DIR, "peaks.json"))
    for entry in table["chips"]:
        if entry["device_kind"] == device_kind:
            return entry
    raise SystemExit(f"benchmark/peaks.json has no entry for device kind "
                     f"{device_kind!r}")
