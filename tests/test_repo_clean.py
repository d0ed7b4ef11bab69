"""Tier-1 gate: the repo's own code passes its own static analyzer.

Runs vmtlint over the configured scan set (``[tool.vmtlint]`` in
pyproject.toml: the library, scripts/, tests/) and fails on any finding
that is not grandfathered in vmtlint_baseline.json — so a PR that
introduces a host transfer inside jit, a jit-in-loop recompile, a
donated-buffer reuse, or an unblocked timed dispatch fails fast CI, not
a TPU window. Pure AST work: no jax import, runs in well under a second.
"""

import os

from vilbert_multitask_tpu.analysis import baseline as bl
from vilbert_multitask_tpu.analysis.config import load_config
from vilbert_multitask_tpu.analysis.core import analyze_paths
from vilbert_multitask_tpu.analysis.rules import default_rules

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scan():
    cfg, root = load_config(REPO_ROOT)
    assert root == REPO_ROOT, "pyproject.toml with [tool.vmtlint] not found"
    paths = [os.path.join(root, p) for p in cfg.paths]
    findings = analyze_paths(paths, root=root,
                             rules=default_rules(cfg.severity,
                                                 cfg.rule_paths),
                             exclude=cfg.exclude,
                             library_roots=cfg.library_roots,
                             layers=cfg.layers)
    baseline = {}
    if cfg.baseline:
        baseline = bl.load_baseline(os.path.join(root, cfg.baseline))
    return bl.split_baselined(findings, baseline), baseline


def test_repo_has_no_unbaselined_findings():
    (new, _baselined, _stale), _ = _scan()
    assert not new, "vmtlint findings (fix or baseline with justification):\n" \
        + "\n".join(f"  {f.path}:{f.line}: {f.rule} {f.message}"
                    for f in new)


def test_baseline_has_no_stale_entries():
    # Debt that got paid must leave the ledger: a fixed finding's entry is
    # dead weight that would mask a regression at the same fingerprint.
    (_new, _baselined, stale), baseline = _scan()
    assert not stale, "stale baseline entries (remove from " \
        "vmtlint_baseline.json):\n" + "\n".join(
            f"  {fp} ({baseline[fp].get('path')})" for fp in stale)


def test_scan_set_covers_obs_and_vmt109_is_active():
    # The obs/ package must sit inside the configured scan set (it lives
    # under the library root, so no separate path entry is needed) and the
    # wall-clock-duration rule must be registered — otherwise the "obs code
    # is lint-clean" guarantee silently stops meaning anything. VMT115
    # (unbounded-obs-buffer) is scoped to obs/serve paths: it only bites
    # while those paths stay in the scan set, so it is asserted here too.
    cfg, root = load_config(REPO_ROOT)
    obs_dir = os.path.join(root, "vilbert_multitask_tpu", "obs")
    assert os.path.isdir(obs_dir)
    assert any(obs_dir.startswith(os.path.join(root, p)) for p in cfg.paths)
    assert {"VMT109", "VMT115"} <= {r.id for r in default_rules()}


def test_debug_surface_is_wired():
    # The live-health endpoints are load-bearing (check.sh's SLO smoke and
    # the readiness probe poll them); a refactor that drops a route from
    # the dispatch table must fail tier-1, not an incident. Source-level
    # assertion: no server boot, stays jax-free and sub-second.
    api_src = open(os.path.join(
        REPO_ROOT, "vilbert_multitask_tpu", "serve", "http_api.py")).read()
    for route in ("/healthz", "/metrics", "/debug/slo", "/debug/timeseries",
                  "/debug/trace", "/debug/costs", "/debug/traces",
                  "/debug/autopsy", "/debug/autoscale"):
        assert f'"{route}"' in api_src, f"route {route} left the http api"


def test_baseline_entries_carry_justification():
    _, baseline = _scan()
    missing = [fp for fp, e in baseline.items()
               if not str(e.get("justification", "")).strip()]
    assert not missing, f"baseline entries lack a justification: {missing}"


def test_whole_program_rules_active_and_scan_covers_tests():
    # The project-graph rule family must stay registered, the layering
    # contracts declared, and tests/ inside the scan set — otherwise the
    # "whole repo is race/layer clean" guarantee quietly narrows.
    cfg, _root = load_config(REPO_ROOT)
    ids = {r.id for r in default_rules()}
    assert {"VMT110", "VMT111", "VMT112",
            "VMT119", "VMT120", "VMT121", "VMT122", "VMT123",
            "VMT124", "VMT125", "VMT126", "VMT127",
            "VMT128", "VMT129", "VMT130", "VMT131",
            "VMT132", "VMT133", "VMT134", "VMT135", "VMT136",
            "VMT137", "VMT138", "VMT139", "VMT140"} <= ids
    assert cfg.layers, "[tool.vmtlint.layers] contracts disappeared"
    assert any(p == "tests" or p.startswith("tests/") for p in cfg.paths)


def test_layer_contracts_protect_the_analysis_package():
    # analysis/ is the tool itself: it must stay importable without jax
    # (tier-1 lint gating runs before any backend exists). The contract is
    # only as good as its presence in config.
    cfg, _root = load_config(REPO_ROOT)
    assert ("vilbert_multitask_tpu.analysis", "jax") in [
        tuple(c) for c in cfg.layers]
