"""Where the harness finds things: everything is looked up by the name that
``BENCHMARK.json`` gives it, so a new cell, configuration, traffic mix,
per-layer metric, reader kind or model family is new files plus new
entries. What belongs to one architecture is the *family*'s, a module the
configuration's file names (``benchmark/families/__init__.py``)."""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_DIR = os.path.join(BENCH_DIR, ".cache")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _module(directory: str, *names: str):
    """The module ``<directory>/<names...>`` (a file or a package), imported
    by its dotted path from the root of the checkout, or None."""
    path = os.path.join(directory, *names)
    if not (os.path.exists(path + ".py")
            or os.path.exists(os.path.join(path, "__init__.py"))):
        return None
    dotted = os.path.relpath(path, ROOT).replace(os.sep, ".")
    return importlib.import_module(dotted)


class Spec:
    """One cell's view of a manifest and the files it names. The manifest
    is ``BENCHMARK.json``; a builder may name another (``run.py
    --manifest``), whose own directories (its ``paths``, relative to it)
    are then searched before ``benchmark/``."""

    def __init__(self, workload: str, manifest: str = MANIFEST):
        self.manifest = _load(manifest)
        base = os.path.dirname(os.path.abspath(manifest))
        self.dirs = [os.path.normpath(os.path.join(base, p))
                     for p in self.manifest["paths"]]
        if BENCH_DIR not in self.dirs:
            self.dirs.append(BENCH_DIR)
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; the manifest "
                             f"has {sorted(cells)}")
        self.cell = cells[workload]
        (entry,) = [c for c in self.manifest["configs"]
                    if c["name"] == self.cell["config"]]
        self.config = _load(os.path.join(base, entry["file"]))
        self.traffic = _load(self.find("traffic",
                                       self.cell["traffic"] + ".json"))
        self.limits = _load(self.find("limits",
                                      self.cell["name"] + ".json"))
        self.family = self.family_of(self.config)

    def find(self, *names: str) -> str:
        """The first ``<directory>/<names...>`` that exists."""
        for d in self.dirs:
            path = os.path.join(d, *names)
            if os.path.exists(path):
                return path
        raise SystemExit(f"no file {os.path.join(*names)} under "
                         f"{[os.path.relpath(d, ROOT) for d in self.dirs]}")

    def family_of(self, config: dict):
        """The module ``families/<family>`` the configuration names; a
        configuration that names none is an error, never a default."""
        name = config.get("family")
        if not name:
            raise SystemExit(f"configuration {config.get('name')!r} names "
                             f"no \"family\" (benchmark/families/)")
        for d in self.dirs:
            module = _module(d, "families", name)
            if module is not None:
                return module
        raise SystemExit(f"no family {name!r}: no families/{name}.py or "
                         f"families/{name}/ under the benchmark")

    def rehearsal_sizes(self) -> dict:
        """``tests/tiny.<family>.json``: the sizes of a CPU rehearsal."""
        return _load(self.find("tests",
                               f"tiny.{self.config['family']}.json"))

    def _reported_here(self, metric: dict) -> bool:
        return self.cell["name"] in metric.get(
            "workloads", [self.cell["name"]])

    def end_to_end(self) -> list:
        return [m for m in self.manifest["end_to_end"]
                if self._reported_here(m)]

    def per_layer(self) -> list:
        """(manifest entry, reader file) for each metric of this cell."""
        return [(m, _load(reader_file(m["name"], self.dirs)))
                for m in self.manifest["per_layer"] if self._reported_here(m)]


def reader_file(metric: str, dirs=(BENCH_DIR,)) -> str:
    """``metrics/<name>.json``; a quantity split by what its cells report
    (``forward_mfu.interactive``, ``forward_mfu.saturated``) may share one
    reader, ``metrics/forward_mfu.json``."""
    for name in (metric, metric.rpartition(".")[0]):
        for d in dirs:
            path = os.path.join(d, "metrics", name + ".json")
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
