"""Dataflow-tier vmtlint suite: the CFG builder, the worklist solver, and
the flow-sensitive rules built on them (VMT119/120/121/122).

CFG semantics are asserted through the lock-set domain rather than block
topology — "the lock is released by the time this statement runs" is the
contract the rules depend on, and it survives builder refactors that
shuffle block boundaries.  Rule tests follow the repo's fixture
convention: every rule proves it fires on the minimal hazard AND stays
quiet on the correct twin.
"""

import ast
import json
import os
import subprocess
import textwrap
import time

import pytest

from vilbert_multitask_tpu.analysis import analyze_project
from vilbert_multitask_tpu.analysis.cfg import build_cfg
from vilbert_multitask_tpu.analysis.cli import main as cli_main
from vilbert_multitask_tpu.analysis.dataflow import (
    LockSetAnalysis, ReachingDefs, iter_event_facts, solve)
from vilbert_multitask_tpu.analysis.graph import import_closure

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_LOCK_NAMES = ("a", "b", "c")


def _lock_facts(src):
    """{assigned name: lock-set before the assignment} for a function whose
    locks are the bare names a/b/c.  The single probe the CFG tests use:
    `x = 1` observes which locks are definitely held where it executes."""
    fn = ast.parse(textwrap.dedent(src)).body[-1]
    cfg = build_cfg(fn)

    def resolver(expr):
        if isinstance(expr, ast.Name) and expr.id in _LOCK_NAMES:
            return expr.id
        return None

    analysis = LockSetAnalysis(resolver)
    in_facts = solve(cfg, analysis)
    out = {}
    for event, fact in iter_event_facts(cfg, analysis, in_facts):
        if isinstance(event, ast.Assign) and isinstance(
                event.targets[0], ast.Name):
            name = event.targets[0].id
            out[name] = fact if name not in out else (out[name] & fact)
    return cfg, analysis, in_facts, out


# ------------------------------------------------------------- CFG builder
def test_with_scope_releases_on_exit():
    _, _, _, facts = _lock_facts("""
    def f():
        with a:
            inside = 1
        after = 2
    """)
    assert facts["inside"] == frozenset({"a"})
    assert facts["after"] == frozenset()


def test_branch_join_is_must_intersection():
    # One arm takes only `a`, the other `a` then `b`: after the join, only
    # `a` is *definitely* held.
    _, _, _, facts = _lock_facts("""
    def f(cond):
        if cond:
            a.acquire()
        else:
            a.acquire()
            b.acquire()
        merged = 1
    """)
    assert facts["merged"] == frozenset({"a"})


def test_branch_with_one_armed_acquire():
    _, _, _, facts = _lock_facts("""
    def f(cond):
        if cond:
            with a:
                held = 1
        after = 2
    """)
    assert facts["held"] == frozenset({"a"})
    assert facts["after"] == frozenset()


def test_early_return_unwinds_with_frames():
    # Both the return path and the fall-through path must reach the exit
    # with the lock released — the builder emits the unwinding WithExit
    # markers before the jump edge.
    cfg, analysis, in_facts, facts = _lock_facts("""
    def f(cond):
        with a:
            if cond:
                return 1
            kept = 1
        after = 2
    """)
    assert facts["kept"] == frozenset({"a"})
    assert facts["after"] == frozenset()
    assert in_facts[cfg.exit.id] == frozenset()


def test_break_unwinds_to_loop_depth():
    _, _, _, facts = _lock_facts("""
    def f(items, cond):
        for it in items:
            with a:
                if cond:
                    break
                inside = 1
        after = 2
    """)
    assert facts["inside"] == frozenset({"a"})
    assert facts["after"] == frozenset()


def test_loop_keeps_outer_lock_held():
    _, _, _, facts = _lock_facts("""
    def f(items):
        a.acquire()
        for it in items:
            body = 1
        end = 1
        a.release()
    """)
    assert facts["body"] == frozenset({"a"})
    assert facts["end"] == frozenset({"a"})


def test_try_finally_runs_with_lock_then_releases():
    _, _, _, facts = _lock_facts("""
    def f():
        with a:
            try:
                risky = 1
            finally:
                fin = 1
        after = 2
    """)
    assert facts["risky"] == frozenset({"a"})
    assert facts["fin"] == frozenset({"a"})
    assert facts["after"] == frozenset()


def test_except_handler_joins_boundary_states():
    # The exception may fire before OR after the acquire, so the handler
    # must-set is the intersection: nothing is definitely held there.
    _, _, _, facts = _lock_facts("""
    def f(risky):
        try:
            a.acquire()
            mid = 1
        except Exception:
            handler = 1
        a.release()
    """)
    assert facts["mid"] == frozenset({"a"})
    assert facts["handler"] == frozenset()


def test_while_true_has_no_false_edge():
    # `while True` only exits via break; code after the loop sees the
    # break-path state, not a phantom fall-through from the header.
    _, _, _, facts = _lock_facts("""
    def f(cond):
        a.acquire()
        while True:
            if cond:
                a.release()
                break
        after = 1
    """)
    assert facts["after"] == frozenset()


# ---------------------------------------------------------------- solver
def test_conditional_acquire_loop_converges():
    # The classic lattice stress: a loop that acquires on one path and
    # releases on another.  The worklist must reach a fixed point (this
    # test hanging IS the failure mode) and the must-set degrades to empty
    # rather than oscillating.
    cfg, analysis, in_facts, facts = _lock_facts("""
    def f(items, cond):
        for it in items:
            if cond:
                a.acquire()
            else:
                a.release()
            probe = 1
        done = 1
    """)
    assert facts["probe"] == frozenset()
    assert facts["done"] == frozenset()


def test_reaching_defs_kills_and_joins():
    fn = ast.parse(textwrap.dedent("""
    def f(cond):
        x = 1
        if cond:
            x = 2
        y = x
    """)).body[0]
    cfg = build_cfg(fn)
    analysis = ReachingDefs(frozenset({"x"}), params_line=fn.lineno)
    in_facts = solve(cfg, analysis)
    at_y = None
    for event, fact in iter_event_facts(cfg, analysis, in_facts):
        if isinstance(event, ast.Assign) and isinstance(
                event.targets[0], ast.Name) and event.targets[0].id == "y":
            at_y = fact
    # The entry placeholder is killed by `x = 1`; both real definitions
    # reach the read.
    lines = sorted(line for name, line in at_y)
    assert lines == [3, 5]


# ----------------------------------------------------------------- VMT119
ABBA = {
    "pkg/shared.py": """
    import threading
    lock_a = threading.Lock()
    lock_b = threading.Lock()
    """,
    "pkg/one.py": """
    from pkg.shared import lock_a, lock_b

    def ab():
        with lock_a:
            with lock_b:
                return 1
    """,
    "pkg/two.py": """
    from pkg.shared import lock_a, lock_b

    def ba():
        with lock_b:
            with lock_a:
                return 2
    """,
}


def _findings(sources):
    return analyze_project(
        {p: textwrap.dedent(s) for p, s in sources.items()},
        library_roots=("pkg", "vilbert_multitask_tpu"))


def test_vmt119_cross_module_abba_with_both_witness_chains():
    hits = [f for f in _findings(ABBA) if f.rule == "VMT119"]
    assert len(hits) == 1
    f = hits[0]
    # BOTH conflicting orders must be reported as witness chains.
    assert len(f.flows) == 2
    chain_paths = {step["path"] for chain in f.flows for step in chain}
    assert {"pkg/one.py", "pkg/two.py"} <= chain_paths
    assert all("line" in step and "message" in step
               for chain in f.flows for step in chain)
    assert "lock-order inversion" in f.message
    assert "deadlock" in f.message


def test_vmt119_same_order_everywhere_is_clean():
    clean = dict(ABBA)
    clean["pkg/two.py"] = """
    from pkg.shared import lock_a, lock_b

    def also_ab():
        with lock_a:
            with lock_b:
                return 2
    """
    assert not [f for f in _findings(clean) if f.rule == "VMT119"]


def test_vmt119_one_way_class_lock_pair_is_clean():
    # The engine/runtime.py shape in miniature: _fallback may be held when
    # taking _compile, never the reverse.  Acyclic → silent.
    src = {
        "pkg/eng.py": """
        import threading

        class Eng:
            def __init__(self):
                self._fallback = threading.Lock()
                self._compile = threading.Lock()

            def dispatch(self):
                with self._fallback:
                    with self._compile:
                        return 1

            def warm(self):
                with self._compile:
                    return 2
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT119"]


def test_vmt119_composed_through_call_chain():
    # The inversion only exists through a call: taker holds A and calls a
    # helper that takes B, while another function orders them B then A.
    src = {
        "pkg/mod.py": """
        import threading
        lock_a = threading.Lock()
        lock_b = threading.Lock()

        def helper():
            with lock_b:
                return 1

        def holds_a():
            with lock_a:
                return helper()

        def other():
            with lock_b:
                with lock_a:
                    return 2
        """,
    }
    hits = [f for f in _findings(src) if f.rule == "VMT119"]
    assert len(hits) == 1
    assert len(hits[0].flows) == 2
    # The composed chain walks through the helper call.
    joined = " ".join(step["message"]
                      for chain in hits[0].flows for step in chain)
    assert "helper" in joined


def test_vmt119_regression_real_engine_runtime_not_flagged():
    # Ground truth: engine/runtime.py's lock nesting (_input_cache_lock,
    # _compile_lock, _boot_lock, the per-program locks) is one-way by
    # design.  The detector must stay silent on it.
    path = os.path.join(REPO, "vilbert_multitask_tpu", "engine",
                        "runtime.py")
    with open(path, "r", encoding="utf-8") as fh:
        src = fh.read()
    findings = analyze_project(
        {"vilbert_multitask_tpu/engine/runtime.py": src})
    assert not [f for f in findings if f.rule == "VMT119"]


# ----------------------------------------------------------------- VMT120
def test_vmt120_wait_holding_foreign_lock_fires():
    src = {
        "pkg/w.py": """
        import threading

        class W:
            def __init__(self):
                self._lock = threading.Lock()
                self._cond = threading.Condition()

            def bad(self):
                with self._lock:
                    with self._cond:
                        self._cond.wait()
        """,
    }
    hits = [f for f in _findings(src) if f.rule == "VMT120"]
    assert len(hits) == 1
    assert "W._lock" in hits[0].message


def test_vmt120_wait_under_own_condition_is_clean():
    src = {
        "pkg/w.py": """
        import threading

        class W:
            def __init__(self):
                self._cond = threading.Condition()

            def fine(self):
                with self._cond:
                    while not self.ready:
                        self._cond.wait()
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT120"]


def test_vmt120_composed_wait_through_helper_call():
    # The pool.rolling_swap shape: the caller holds a lock across a call
    # to a helper that blocks on a condition wait.
    src = {
        "pkg/p.py": """
        import threading

        class P:
            def __init__(self):
                self._swap = threading.Lock()
                self._cond = threading.Condition()

            def _wait_ready(self):
                with self._cond:
                    self._cond.wait()

            def swap(self):
                with self._swap:
                    self._wait_ready()
        """,
    }
    hits = [f for f in _findings(src) if f.rule == "VMT120"]
    assert len(hits) == 1
    assert "P._swap" in hits[0].message
    assert "_wait_ready" in hits[0].message


def test_vmt120_queue_get_nonblocking_is_clean():
    src = {
        "pkg/q.py": """
        import threading
        import queue

        class Q:
            def __init__(self):
                self._lock = threading.Lock()
                self._q = queue.Queue()

            def drain(self):
                with self._lock:
                    return self._q.get(block=False)
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT120"]


# ----------------------------------------------------------------- VMT121
def test_vmt121_captured_local_rebound_across_jit_calls():
    src = {
        "pkg/j.py": """
        import jax

        def run(xs):
            scale = 1.0
            f = jax.jit(lambda x: x * scale)
            out = []
            for x in xs:
                out.append(f(x))
                scale = scale + 1.0
            return out
        """,
    }
    hits = [f for f in _findings(src) if f.rule == "VMT121"]
    assert len(hits) == 1
    assert "scale" in hits[0].message
    assert "stale" in hits[0].message


def test_vmt121_single_definition_capture_is_clean():
    src = {
        "pkg/j.py": """
        import jax

        def run(xs):
            scale = 1.0
            f = jax.jit(lambda x: x * scale)
            return [f(x) for x in xs]
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT121"]


def test_vmt121_traced_self_read_rebound_elsewhere():
    src = {
        "pkg/m.py": """
        import jax

        class M:
            def __init__(self):
                self.temperature = 1.0

            def set_temperature(self, t):
                self.temperature = t

            @jax.jit
            def forward(self, x):
                return x / self.temperature
        """,
    }
    hits = [f for f in _findings(src) if f.rule == "VMT121"]
    assert len(hits) == 1
    assert "temperature" in hits[0].message
    assert "set_temperature" in hits[0].message


def test_vmt121_init_only_self_state_is_clean():
    src = {
        "pkg/m.py": """
        import jax

        class M:
            def __init__(self):
                self.scale = 2.0

            @jax.jit
            def forward(self, x):
                return x * self.scale
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT121"]


# ----------------------------------------------------------------- VMT122
KNOBS = {
    "pkg/config.py": """
    class ServingConfig:
        knob_used: int = 1
        knob_dead: int = 2
    """,
    "pkg/app.py": """
    def go(cfg):
        s = cfg.serving
        return s.knob_used
    """,
}


def test_vmt122_dead_knob_flagged_at_declaration():
    hits = [f for f in _findings(KNOBS) if f.rule == "VMT122"]
    assert len(hits) == 1
    assert hits[0].path == "pkg/config.py"
    assert "knob_dead" in hits[0].message


def test_vmt122_typo_read_flagged_with_suggestion():
    src = dict(KNOBS)
    src["pkg/app.py"] = """
    def go(cfg):
        s = cfg.serving
        return s.knob_used + s.knob_usedd + s.knob_dead
    """
    hits = [f for f in _findings(src) if f.rule == "VMT122"]
    assert len(hits) == 1
    assert hits[0].path == "pkg/app.py"
    assert "knob_usedd" in hits[0].message
    assert "knob_used" in hits[0].message  # did-you-mean suggestion


def test_vmt122_all_knobs_read_is_clean():
    src = dict(KNOBS)
    src["pkg/app.py"] = """
    def go(cfg):
        s = cfg.serving
        return s.knob_used + s.knob_dead
    """
    assert not [f for f in _findings(src) if f.rule == "VMT122"]


def test_vmt122_reads_through_annotated_param_and_getattr():
    src = {
        "pkg/config.py": """
        class EngineConfig:
            rows: int = 4
            opt_flag: bool = False
        """,
        "pkg/use.py": """
        from pkg.config import EngineConfig

        def plan(ecfg: EngineConfig):
            return ecfg.rows + int(getattr(ecfg, "opt_flag", 0))
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT122"]


# ----------------------------------------------------------------- VMT123
def test_vmt123_dead_instrument_flagged_at_registration():
    src = {
        "pkg/metrics.py": """
        ALIVE = REGISTRY.counter("vmt_alive_total", "incremented below")
        DEAD = REGISTRY.gauge("vmt_dead_gauge", "never touched again")

        def tick():
            ALIVE.inc()
        """,
    }
    hits = [f for f in _findings(src) if f.rule == "VMT123"]
    assert len(hits) == 1
    assert hits[0].path == "pkg/metrics.py"
    assert "vmt_dead_gauge" in hits[0].message


def test_vmt123_typo_read_flagged_with_suggestion():
    src = {
        "pkg/metrics.py": """
        JOBS = REGISTRY.counter("vmt_jobs_total", "jobs")

        def tick():
            JOBS.inc()
        """,
        # vmtlint: disable-next-line=VMT123  (the typo under test, verbatim)
        "pkg/read.py": """
        def snapshot(snap):
            return snap.get("vmt_job_total", 0)
        """,
    }
    hits = [f for f in _findings(src) if f.rule == "VMT123"]
    assert len(hits) == 1
    assert hits[0].path == "pkg/read.py"
    assert "vmt_job_total" in hits[0].message  # vmtlint: disable=VMT123
    assert "vmt_jobs_total" in hits[0].message  # did-you-mean suggestion


def test_vmt123_exposition_suffixes_and_derived_rates_are_reads():
    # _bucket/_sum/_count normalize to the histogram; the Sampler's
    # derived *_per_s key normalizes to its *_total counter — and a
    # name-string reference anywhere counts as keeping it alive.
    src = {
        "pkg/metrics.py": """
        REGISTRY.histogram("vmt_lat_ms", "latency")
        REGISTRY.counter("vmt_jobs_total", "jobs")
        """,
        "pkg/read.py": """
        def asserts(text, series):
            assert "vmt_lat_ms_bucket{" in text
            assert "vmt_lat_ms_count" in text
            return series["vmt_jobs_per_s"]
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT123"]


def test_vmt123_chained_registration_and_foreign_strings_are_clean():
    src = {
        "pkg/metrics.py": """
        import tempfile

        def hit():
            REGISTRY.counter("vmt_hits_total", "get-or-create idiom").inc()
            # Foreign vmt_ strings (paths, native symbols) are not reads.
            return tempfile.mkdtemp(prefix="vmt_demo_scratch")
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT123"]


def test_vmt123_cross_module_handle_use_marks_alive():
    src = {
        "pkg/metrics.py": """
        SHED = REGISTRY.counter("vmt_shed_jobs_total", "sheds")
        """,
        "pkg/worker.py": """
        from pkg.metrics import SHED

        def drop():
            SHED.inc()
        """,
    }
    assert not [f for f in _findings(src) if f.rule == "VMT123"]


# -------------------------------------------------------- --changed mode
def test_import_closure_reverse_and_forward():
    sources = {
        "pkg/shared.py": "X = 1\n",
        "pkg/leaf.py": "from pkg.shared import X\n",
        "pkg/importer.py": "import pkg.leaf\n",
        "pkg/unrelated.py": "Y = 2\n",
    }
    closure = import_closure(sources, {"pkg/leaf.py"})
    assert closure == {"pkg/shared.py", "pkg/leaf.py", "pkg/importer.py"}


def _scratch_repo(root):
    """A git repo with one cross-module ABBA inversion and enough filler
    modules that the changed-closure scan is measurably cheaper than the
    full scan."""
    os.makedirs(os.path.join(root, "pkg"))
    with open(os.path.join(root, "pyproject.toml"), "w") as fh:
        fh.write('[tool.vmtlint]\npaths = ["pkg"]\n'
                 'library_roots = ["pkg"]\n')
    open(os.path.join(root, "pkg", "__init__.py"), "w").close()
    filler = textwrap.dedent("""
        import threading

        class Box{i}:
            def __init__(self):
                self._lock = threading.Lock()
                self.items = []

            def add(self, x):
                with self._lock:
                    self.items.append(x)

            def snapshot(self):
                with self._lock:
                    return list(self.items)

        def helper_{i}(n):
            box = Box{i}()
            for k in range(n):
                box.add(k * {i})
            return box.snapshot()
        """)
    for i in range(40):
        with open(os.path.join(root, "pkg", f"filler{i:02d}.py"),
                  "w") as fh:
            fh.write(filler.format(i=i))
    with open(os.path.join(root, "pkg", "leaf.py"), "w") as fh:
        fh.write(textwrap.dedent("""
            import threading
            lock_a = threading.Lock()
            lock_b = threading.Lock()

            def ab():
                with lock_a:
                    with lock_b:
                        return 1
            """))
    with open(os.path.join(root, "pkg", "importer.py"), "w") as fh:
        fh.write(textwrap.dedent("""
            from pkg.leaf import lock_a, lock_b

            def ba():
                with lock_b:
                    with lock_a:
                        return 2
            """))

    def git(*argv):
        subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *argv],
            cwd=root, check=True, capture_output=True)

    git("init", "-q")
    git("add", "-A")
    git("commit", "-q", "-m", "seed")
    # The single-file diff: touch leaf.py.
    with open(os.path.join(root, "pkg", "leaf.py"), "a") as fh:
        fh.write("\nTOUCHED = True\n")


def test_changed_scan_parity_and_speed(tmp_path, monkeypatch, capsys):
    _scratch_repo(str(tmp_path))
    monkeypatch.chdir(tmp_path)

    t0 = time.perf_counter()
    cli_main(["--format", "json"])
    t_full = time.perf_counter() - t0
    full = json.loads(capsys.readouterr().out)

    t0 = time.perf_counter()
    cli_main(["--format", "json", "--changed"])
    t_changed = time.perf_counter() - t0
    changed = json.loads(capsys.readouterr().out)

    # The closure of a leaf.py diff is leaf + its importer (+ __init__),
    # not the 40 filler modules.
    assert changed["files_scanned"] < 6
    assert full["files_scanned"] >= 42

    # Identical findings for the changed closure: the ABBA inversion (and
    # anything else in those files) must survive the subset scan exactly.
    closure_paths = {"pkg/leaf.py", "pkg/importer.py"}

    def key(f):
        return (f["rule"], f["path"], f["line"], f["message"])

    full_in_closure = sorted(
        key(f) for f in full["findings"] if f["path"] in closure_paths)
    changed_in_closure = sorted(
        key(f) for f in changed["findings"] if f["path"] in closure_paths)
    assert full_in_closure == changed_in_closure
    assert any(f["rule"] == "VMT119" for f in changed["findings"])

    # Acceptance bar: the subset scan finishes in <25% of the full-scan
    # wall time on a single-file diff.
    assert t_changed < 0.25 * t_full, (t_changed, t_full)


def test_changed_scan_falls_back_when_closure_is_large(tmp_path,
                                                       monkeypatch,
                                                       capsys):
    _scratch_repo(str(tmp_path))
    # Touch a module every filler imports → closure exceeds half the
    # project → the CLI must fall back to a full scan rather than scan a
    # misleading majority-subset.
    with open(os.path.join(str(tmp_path), "pkg", "core.py"), "w") as fh:
        fh.write("SHARED = 1\n")
    for i in range(40):
        path = os.path.join(str(tmp_path), "pkg", f"filler{i:02d}.py")
        with open(path, "a") as fh:
            fh.write("\nfrom pkg.core import SHARED\n")
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    "add", "-A"], cwd=str(tmp_path), check=True,
                   capture_output=True)
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    "commit", "-q", "-m", "wire core"], cwd=str(tmp_path),
                   check=True, capture_output=True)
    with open(os.path.join(str(tmp_path), "pkg", "core.py"), "a") as fh:
        fh.write("MORE = 2\n")
    monkeypatch.chdir(tmp_path)
    cli_main(["--format", "json", "--changed"])
    out = capsys.readouterr()
    data = json.loads(out.out)
    assert data["files_scanned"] >= 42  # full scan, not the subset


# ------------------------------------------------------------------ SARIF
def test_sarif_emits_both_witness_chains_as_codeflows():
    from vilbert_multitask_tpu.analysis.report import render_sarif

    hits = [f for f in _findings(ABBA) if f.rule == "VMT119"]
    doc = json.loads(render_sarif(hits, [], [], files_scanned=3))
    results = doc["runs"][0]["results"]
    assert len(results) == 1
    flows = results[0]["codeFlows"]
    assert len(flows) == 2
    for flow in flows:
        locs = flow["threadFlows"][0]["locations"]
        assert locs, "each witness chain must carry at least one step"
        for loc in locs:
            phys = loc["location"]["physicalLocation"]
            assert phys["artifactLocation"]["uri"].startswith("pkg/")
            assert phys["region"]["startLine"] >= 1
            assert loc["location"]["message"]["text"]
