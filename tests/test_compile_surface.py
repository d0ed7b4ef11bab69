"""Compile-surface manifest suite: discovery, determinism, drift
detection, the CLI gates (``surface --check``, ``--prune-baseline
--check``), the rename/delete-aware ``--changed`` subset — and the
runtime↔manifest contract: a TINY engine booted on CPU must never
compile a key the committed COMPILE_SURFACE.json doesn't enumerate."""

import ast
import json
import os
import subprocess
import textwrap

import pytest

from vilbert_multitask_tpu.analysis import surface as surf
from vilbert_multitask_tpu.analysis.cli import (
    _changed_subset,
    _parse_name_status,
    main as cli_main,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(REPO, surf.MANIFEST_NAME)


def _library_sources():
    out = {}
    lib = os.path.join(REPO, "vilbert_multitask_tpu")
    for dirpath, dirnames, filenames in os.walk(lib):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            full = os.path.join(dirpath, fn)
            rel = os.path.relpath(full, REPO).replace(os.sep, "/")
            with open(full, "r", encoding="utf-8") as f:
                out[rel] = f.read()
    return out


@pytest.fixture(scope="module")
def fresh_surface():
    return surf.build_surface(surf.load_project(_library_sources()))


# ---------------------------------------------------------------- manifest
def test_surface_enumerates_full_key_universe(fresh_surface):
    dims = fresh_surface["dimensions"]
    families = [p["family"] for p in dims["program_families"]]
    assert families == ["batched", "rows"]
    assert dims["buckets"]["values"] == [1, 2, 4, 8, 10, 16, 32]
    assert dims["param_dtypes"]["values"] == ["float32", "bfloat16",
                                              "int8"]
    assert dims["fused_modes"]["values"] == [True, False]
    assert dims["collect_attention"]["values"] == [False, True]
    assert [t["id"] for t in dims["topologies"]] == ["dp-1.tp1.sp1"]
    # 2 families × 7 buckets × 3 dtypes × 2 fused × 1 topo × 2 attn
    assert fresh_surface["record_count"] == 168
    assert len(fresh_surface["records"]) == 168
    keys = [r["key"] for r in fresh_surface["records"]]
    assert len(set(keys)) == 168  # unique and total


def test_surface_static_origins_are_bounded(fresh_surface):
    """Every value reaching a compile-key parameter must be bounded
    (bucketized / knob / literal) — an unbounded origin here is the
    compile-cache blowup VMT124 exists to catch."""
    progs = fresh_surface["dimensions"]["program_families"]
    total = 0
    for prog in progs:
        for entries in prog["static_origins"].values():
            for e in entries:
                total += 1
                assert e["bounded"], e
    assert total > 0  # the analysis actually found dispatch sites


def test_surface_witnesses_anchor_in_real_files(fresh_surface):
    dims = fresh_surface["dimensions"]
    seen = 0
    for dim in ("buckets", "param_dtypes", "fused_modes",
                "collect_attention"):
        for w in dims[dim]["witnesses"]:
            seen += 1
            assert os.path.exists(os.path.join(REPO, w["path"])), w
            assert w["line"] >= 1
    assert seen >= 6


def test_surface_build_is_deterministic():
    sources = _library_sources()
    a = surf.render_surface(surf.build_surface(surf.load_project(sources)))
    b = surf.render_surface(surf.build_surface(surf.load_project(sources)))
    assert a == b


def test_committed_manifest_matches_tree(fresh_surface):
    """The acceptance gate: COMPILE_SURFACE.json is committed and clean
    against the tree it describes."""
    assert os.path.exists(MANIFEST), (
        "COMPILE_SURFACE.json not committed — run `python -m "
        "vilbert_multitask_tpu.analysis surface`")
    with open(MANIFEST, "r", encoding="utf-8") as f:
        committed = json.load(f)
    assert surf.diff_surface(committed, fresh_surface) == []


def test_diff_surface_reports_dimension_drift(fresh_surface):
    mutated = json.loads(json.dumps(fresh_surface))
    mutated["dimensions"]["buckets"]["values"].append(64)
    msgs = surf.diff_surface(mutated, fresh_surface)
    assert any("buckets" in m for m in msgs)
    missing = surf.diff_surface(None, fresh_surface)
    assert missing and "missing" in missing[0]


def test_discover_programs_on_fixture_idiom():
    src = textwrap.dedent('''
        import jax
        from functools import partial

        class Eng:
            def _build(self, bucket, flag):
                key = ("demo", bucket, flag, self._gen)
                if key in self._compiled:
                    return self._compiled[key]

                @partial(jax.jit, static_argnames=("flag",))
                def fwd(params, batch, flag=flag):
                    return batch

                self._compiled[key] = fwd
                return fwd
    ''')
    project = surf.load_project({"pkg/eng.py": src})
    progs = surf.discover_programs(project)
    assert len(progs) == 1
    assert progs[0].family == "demo"
    assert progs[0].builder == "pkg.eng:Eng._build"
    assert progs[0].key_params == ("bucket", "flag")
    assert progs[0].static_args == ("flag",)


def test_surface_sarif_renders_codeflows(fresh_surface):
    doc = json.loads(surf.render_surface_sarif(fresh_surface))
    results = doc["runs"][0]["results"]
    assert len(results) == 2
    for r in results:
        assert r["codeFlows"]
        loc = r["codeFlows"][0]["threadFlows"][0]["locations"][0]
        assert loc["location"]["physicalLocation"]["artifactLocation"][
            "uri"].endswith(".py")


def test_surface_check_cli_clean(monkeypatch):
    monkeypatch.chdir(REPO)
    assert cli_main(["surface", "--check"]) == 0


def test_surface_check_cli_flags_drift(monkeypatch, tmp_path):
    monkeypatch.chdir(REPO)
    with open(MANIFEST, "r", encoding="utf-8") as f:
        d = json.load(f)
    d["dimensions"]["param_dtypes"]["values"] = ["float32"]
    drifted = tmp_path / "drifted.json"
    drifted.write_text(json.dumps(d))
    assert cli_main(["surface", "--check", "--out", str(drifted)]) == 1


# ---------------------------------------------------- --changed name-status
def test_parse_name_status_rename_delete_modify():
    out = ("M\tpkg/mod.py\n"
           "A\tpkg/new.py\n"
           "D\tpkg/dead.py\n"
           "R087\tpkg/old.py\tpkg/moved.py\n"
           "C075\tpkg/src.py\tpkg/copy.py\n")
    changed, removed = _parse_name_status(out)
    assert changed == {"pkg/mod.py", "pkg/new.py", "pkg/moved.py",
                       "pkg/copy.py"}
    assert removed == {"pkg/dead.py", "pkg/old.py"}


def _git(cwd, *args):
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    *args], cwd=cwd, check=True, capture_output=True)


@pytest.fixture()
def git_repo(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "a.py").write_text("VALUE = 1\n")
    (pkg / "b.py").write_text("import pkg.a\n\nX = pkg.a.VALUE\n")
    for name in ("c", "d", "e", "f"):
        (pkg / f"{name}.py").write_text(f"{name.upper()} = 0\n")
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    return tmp_path


def test_changed_subset_follows_rename(git_repo):
    _git(git_repo, "mv", "pkg/a.py", "pkg/a2.py")
    result = _changed_subset([str(git_repo / "pkg")], str(git_repo),
                             (), "HEAD")
    assert result is not None
    subset, removed = result
    rels = {os.path.relpath(p, str(git_repo)).replace(os.sep, "/")
            for p in subset}
    # The rename target is scanned, and so is the module that imported
    # the old name — its cross-module findings may have shifted.
    assert "pkg/a2.py" in rels
    assert "pkg/b.py" in rels
    assert removed == {"pkg/a.py"}


def test_changed_subset_deletion_rescans_importers(git_repo):
    _git(git_repo, "rm", "-q", "pkg/a.py")
    result = _changed_subset([str(git_repo / "pkg")], str(git_repo),
                             (), "HEAD")
    assert result is not None
    subset, removed = result
    rels = {os.path.relpath(p, str(git_repo)).replace(os.sep, "/")
            for p in subset}
    assert "pkg/b.py" in rels
    assert removed == {"pkg/a.py"}


def test_changed_subset_untouched_repo_full_scan(git_repo):
    assert _changed_subset([str(git_repo / "pkg")], str(git_repo),
                           (), "HEAD") is None


# ------------------------------------------------- baseline staleness gates
PYPROJECT = textwrap.dedent('''
    [tool.vmtlint]
    paths = ["pkg"]
    baseline = "baseline.json"
''')


def _baseline_entry(fingerprint, path):
    return {"fingerprint": fingerprint, "rule": fingerprint.split(":")[0],
            "name": "x", "path": path, "line": 1, "content": "x",
            "justification": "test"}


@pytest.fixture()
def lint_repo(tmp_path):
    (tmp_path / "pyproject.toml").write_text(PYPROJECT)
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "mod.py").write_text("VALUE = 1\n")
    return tmp_path


def test_prune_check_fails_on_stale_entry(lint_repo, monkeypatch):
    (lint_repo / "baseline.json").write_text(json.dumps({
        "version": 1,
        "entries": [_baseline_entry("VMT105:pkg/mod.py:deadbeef0000",
                                    "pkg/mod.py")]}))
    monkeypatch.chdir(lint_repo)
    assert cli_main(["--prune-baseline", "--check"]) == 1


def test_prune_check_fails_on_deleted_file_entry(lint_repo, monkeypatch):
    """The satellite-1 bug class: a baseline entry anchored in a file
    that no longer exists must go stale on a full scan, not linger as a
    dead suppression."""
    (lint_repo / "baseline.json").write_text(json.dumps({
        "version": 1,
        "entries": [_baseline_entry("VMT105:pkg/gone.py:deadbeef0000",
                                    "pkg/gone.py")]}))
    monkeypatch.chdir(lint_repo)
    assert cli_main(["--prune-baseline", "--check"]) == 1


def test_prune_rewrites_then_check_clean(lint_repo, monkeypatch):
    (lint_repo / "baseline.json").write_text(json.dumps({
        "version": 1,
        "entries": [_baseline_entry("VMT105:pkg/gone.py:deadbeef0000",
                                    "pkg/gone.py")]}))
    monkeypatch.chdir(lint_repo)
    assert cli_main(["--prune-baseline"]) == 0
    data = json.loads((lint_repo / "baseline.json").read_text())
    assert data["entries"] == []
    assert cli_main(["--prune-baseline", "--check"]) == 0


def test_prune_check_clean_on_real_repo(monkeypatch):
    monkeypatch.chdir(REPO)
    assert cli_main(["--prune-baseline", "--check"]) == 0


# -------------------------------------------------- runtime↔manifest contract
def test_engine_compiled_keys_covered_by_manifest(tiny_config):
    """Boot the TINY engine on CPU, exercise warmup/run/run_many, and
    assert every key the engine actually compiled maps onto a committed
    manifest record — the drift test that keeps the manifest honest."""
    from vilbert_multitask_tpu.config import EngineConfig, FrameworkConfig
    from vilbert_multitask_tpu.engine import InferenceEngine
    from tests.test_engine import make_regions

    cfg = FrameworkConfig(
        model=tiny_config,
        engine=EngineConfig(
            compute_dtype="float32", max_regions=11,
            use_pallas_coattention=False,
            use_pallas_self_attention=False))
    eng = InferenceEngine(cfg, seed=0)
    eng.warmup(buckets=(1, 2), parallel=False)
    regions = make_regions(2, feat_dim=tiny_config.v_feature_size)
    _, result = eng.run(eng.prepare(1, "what is on the table",
                                    regions[:1]))
    assert result
    many = eng.run_many([eng.prepare(1, "a dog", regions[:1]),
                         eng.prepare(1, "a cat", regions[1:])])
    assert len(many) == 2

    with open(MANIFEST, "r", encoding="utf-8") as f:
        manifest = json.load(f)
    record_keys = {r["key"] for r in manifest["records"]}
    families = {p["family"]
                for p in manifest["dimensions"]["program_families"]}
    topo = manifest["dimensions"]["topologies"][0]["id"]
    param_dtype = cfg.engine.param_dtype
    fused = cfg.engine.fused_task_heads

    assert eng._compiled, "engine compiled nothing — test exercised no path"
    for key in eng._compiled:
        family, bucket, attn = key
        assert family in families, key
        mapped = surf.record_key_for_engine(
            family, bucket, param_dtype, fused, topo, attn)
        assert mapped in record_keys, (
            f"engine compiled {key} but the manifest has no record "
            f"{mapped} — regenerate COMPILE_SURFACE.json")
