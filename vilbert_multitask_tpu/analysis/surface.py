"""Compile-surface manifest: the engine's XLA program-key universe.

Drives the shape-tier interpreter (:mod:`analysis.shapes`) over every
compiled-program builder the engine declares and emits
``COMPILE_SURFACE.json`` — one record per program family × bucket ×
param_dtype × fused mode × mesh topology × attention mode, each
dimension carrying witness chains for where its values originate in
source. The manifest is the answer to "what can this engine ever
compile": ROADMAP item 1's AOT cache pre-warms from it, CI pins it with
``vmtlint surface --check``, and the runtime cross-check test asserts
every key the live engine actually compiles maps onto a record.

Discovery is structural, not name-driven: a *program family* is any
function that builds a ``key = ("<family>", ...)`` tuple and stores into
``...._compiled[key]`` — the engine's compile-cache idiom — so new
families (a third program, a training step) appear in the manifest the
moment they adopt the idiom.
"""

from __future__ import annotations

import ast
import json
from typing import Dict, Iterator, List, Optional, Tuple

from vilbert_multitask_tpu.analysis.context import ModuleContext
from vilbert_multitask_tpu.analysis.shapes import (
    BOUNDED_ORIGINS,
    KnobTable,
    Scalar,
    interpret_function,
    knob_table,
)

SURFACE_VERSION = 1
MANIFEST_NAME = "COMPILE_SURFACE.json"

# The quant-mode axis of the key universe (ISSUE/ROADMAP item 1): params
# are served in exactly one of these storages; int8 implies the
# {"int8","scale"} leaf pair and dequant-inside-jit.
PARAM_DTYPES = ("float32", "bfloat16", "int8")


def _witness(path: str, line: int, note: str) -> dict:
    return {"path": path, "line": line, "note": note}


def load_project(sources: Dict[str, str]):
    """Parse {rel_path: source} into a linked ProjectGraph (the same
    construction analyze_project uses, minus the rules pass). Files that
    don't parse are skipped — the lint gate owns reporting those."""
    from vilbert_multitask_tpu.analysis.graph import ProjectGraph

    ctxs = []
    for rel_path in sorted(sources):
        try:
            tree = ast.parse(sources[rel_path])
        except SyntaxError:
            continue
        ctxs.append(ModuleContext(rel_path, sources[rel_path], tree))
    project = ProjectGraph(ctxs)
    for ctx in ctxs:
        ctx.project = project
    return project


# ------------------------------------------------------------- discovery
class ProgramFamily:
    def __init__(self, family: str, builder: str, path: str, line: int,
                 static_args: Tuple[str, ...], key_params: Tuple[str, ...],
                 method: str):
        self.family = family
        self.builder = builder  # "module:Class.method"
        self.path = path
        self.line = line  # the `key = (...)` assignment
        self.static_args = static_args
        self.key_params = key_params  # builder params feeding the key
        self.method = method  # bare method name, for call-site search
        self.static_origins: Dict[str, List[dict]] = {}

    def to_json(self) -> dict:
        return {
            "family": self.family,
            "builder": self.builder,
            "key_witness": _witness(
                self.path, self.line,
                f"compile-cache key built here: "
                f"(\"{self.family}\", {', '.join(self.key_params)})"),
            "jit_static_args": list(self.static_args),
            "key_params": list(self.key_params),
            "static_origins": self.static_origins,
        }


def _compiled_key_fn(fn: ast.AST) -> Optional[Tuple[str, ast.Assign]]:
    """(family, key-assignment) when ``fn`` is a compile-cache builder:
    assigns ``key = ("<family>", ...)`` and stores ``..._compiled[key]``.
    """
    key_assign: Optional[ast.Assign] = None
    family: Optional[str] = None
    stores_key = False
    for node in ast.walk(fn):
        if (isinstance(node, ast.Assign)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == "key"
                and isinstance(node.value, ast.Tuple)
                and node.value.elts
                and isinstance(node.value.elts[0], ast.Constant)
                and isinstance(node.value.elts[0].value, str)):
            key_assign = node
            family = node.value.elts[0].value
        if (isinstance(node, ast.Subscript)
                and isinstance(node.ctx, ast.Store)
                and isinstance(node.value, ast.Attribute)
                and node.value.attr == "_compiled"
                and isinstance(node.slice, ast.Name)
                and node.slice.id == "key"):
            stores_key = True
    if family is not None and key_assign is not None and stores_key:
        return family, key_assign
    return None


def _builder_qualname(ctx: ModuleContext, fn: ast.AST) -> str:
    parts = [getattr(fn, "name", "<lambda>")]
    for anc in ctx.ancestors(fn):
        if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            parts.append(anc.name)
    mod = ctx.rel_path[:-3].replace("/", ".")
    return f"{mod}:{'.'.join(reversed(parts))}"


def discover_programs(project) -> List[ProgramFamily]:
    out: List[ProgramFamily] = []
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        ctx = mod.ctx
        jit_statics = {id(info.body): info.static_params
                       for info in ctx.jit_bodies}
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            hit = _compiled_key_fn(fn)
            if hit is None:
                continue
            family, key_assign = hit
            params = tuple(a.arg for a in fn.args.args if a.arg != "self")
            key_tuple = key_assign.value
            key_params = tuple(
                e.id for e in key_tuple.elts[1:]
                if isinstance(e, ast.Name) and e.id in params)
            statics: Tuple[str, ...] = ()
            for node in ast.walk(fn):
                sp = jit_statics.get(id(node))
                if sp:
                    statics = tuple(sp)
                    break
            out.append(ProgramFamily(
                family, _builder_qualname(ctx, fn), ctx.rel_path,
                key_assign.lineno, statics, key_params,
                getattr(fn, "name", "")))
    out.sort(key=lambda p: p.family)
    return out


# ---------------------------------------------------- static-arg origins
# Builder call sites are searched under the builder method name AND the
# dispatch funnels that forward a (bucket, collect_attention) prefix
# verbatim — the provenance that matters is at the mouth of the funnel,
# not the passthrough hops.
_FUNNELS = ("_call_forward", "_run_rows")


def collect_static_origins(project, programs: List[ProgramFamily],
                           knobs: KnobTable) -> None:
    """For each builder parameter that feeds the compile key, record the
    abstract origins of every value reaching it through direct calls or
    the dispatch funnels. Passthrough hops (a funnel forwarding its own
    parameter) are skipped; what remains is the real key material: bucket
    values from ``bucket_for``/``all_row_buckets``, literals, knobs — or
    an unbounded source, which the manifest surfaces loudly."""
    names = {p.method: p for p in programs}
    targets = set(names) | set(_FUNNELS)
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        ctx = mod.ctx
        if not any(t in ctx.source for t in targets):
            continue
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            calls = [n for n in ast.walk(fn)
                     if isinstance(n, ast.Call)
                     and isinstance(n.func, ast.Attribute)
                     and n.func.attr in targets]
            if not calls or _compiled_key_fn(fn) is not None:
                continue
            interp = None
            for call in calls:
                if ctx.enclosing_function(call) is not fn:
                    continue
                if interp is None:
                    interp = interpret_function(ctx, fn, knobs)
                env = _env_at(interp, call)
                for prog in programs:
                    _record_call(ctx, interp, env, call, prog)


def _env_at(interp, call: ast.Call) -> Dict[str, object]:
    from vilbert_multitask_tpu.analysis.shapes import call_nodes_in

    for event, fact in interp.iter_facts():
        for node in call_nodes_in(event):
            if node is call:
                return fact
    return {}


def _record_call(ctx: ModuleContext, interp, env, call: ast.Call,
                 prog: ProgramFamily) -> None:
    # Positional prefix convention shared by the builders and funnels:
    # (bucket, collect_attention, ...).
    for i, pname in enumerate(prog.key_params):
        if i >= len(call.args):
            continue
        arg = call.args[i]
        if isinstance(arg, ast.Starred):
            continue
        val = interp.eval(arg, env)
        if not isinstance(val, Scalar):
            continue
        if val.origin == "param":
            # A passthrough hop — the origin lives at an outer call site.
            continue
        entry = {
            "origin": val.origin,
            "bounded": val.origin in BOUNDED_ORIGINS,
            "symbol": val.sym,
            "value": val.value if isinstance(val.value,
                                             (int, str, bool)) else None,
            "call_site": _witness(
                ctx.rel_path, call.lineno,
                f"`{ast.unparse(arg)}` flows into `{pname}` of "
                f"`{prog.family}` program dispatch"),
            "witness": [_witness(p, ln, msg)
                        for p, ln, msg in val.witness],
        }
        bucket_entries = prog.static_origins.setdefault(pname, [])
        if entry not in bucket_entries:
            bucket_entries.append(entry)


# ------------------------------------------------------------ dimensions
def _knob_witnesses(knobs: KnobTable, fields: Tuple[str, ...]
                    ) -> List[dict]:
    out = []
    for f in fields:
        knob = knobs.field(f)
        if knob is not None:
            out.append(_witness(knob.path, knob.line,
                                f"declared `{knob.sym} = {knob.value!r}`"))
    return out


def _find_def(project, name: str) -> Optional[Tuple[str, int]]:
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        for node in ast.walk(mod.ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name == name:
                return mod.ctx.rel_path, node.lineno
    return None


def _bucket_dimension(project, knobs: KnobTable) -> dict:
    values: List[int] = []
    for f in ("image_buckets", "throughput_buckets"):
        knob = knobs.field(f)
        if knob is not None and isinstance(knob.value, (tuple, list)):
            values.extend(v for v in knob.value if isinstance(v, int))
    witnesses = _knob_witnesses(knobs, ("image_buckets",
                                        "throughput_buckets"))
    arb = _find_def(project, "all_row_buckets")
    if arb is not None:
        witnesses.append(_witness(
            arb[0], arb[1],
            "all_row_buckets(): the sorted union both warmup and "
            "run_many dispatch from"))
    return {"values": sorted(set(values)), "witnesses": witnesses}


def _dtype_dimension(project, knobs: KnobTable) -> dict:
    witnesses = _knob_witnesses(knobs, ("param_dtype",))
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        for node in ast.walk(mod.ctx.tree):
            if isinstance(node, ast.Assign) \
                    and any(isinstance(t, ast.Attribute)
                            and t.attr == "param_dtype"
                            for t in node.targets):
                witnesses.append(_witness(
                    mod.ctx.rel_path, node.lineno,
                    "engine pins the served param storage dtype here"))
                break
    return {"values": list(PARAM_DTYPES), "witnesses": witnesses}


def _attn_dimension(project) -> dict:
    witnesses: List[dict] = []
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        for info in mod.ctx.jit_bodies:
            if "attn" in info.static_params:
                witnesses.append(_witness(
                    mod.ctx.rel_path, info.body.lineno,
                    "jitted forward marks `attn` static — each value is "
                    "its own program"))
    return {"values": [False, True], "witnesses": witnesses}


def _topology_dimension(knobs: KnobTable) -> List[dict]:
    axes = {}
    for f in ("dp", "tp", "sp"):
        knob = knobs.get("MeshConfig", f)
        axes[f] = knob.value if knob is not None else None
    topo_id = "".join(f"{k}{v}." for k, v in axes.items()
                      if v is not None).rstrip(".")
    return [{
        "id": topo_id or "default",
        "axes": axes,
        "witnesses": _knob_witnesses(knobs, ("dp", "tp", "sp")),
        "note": ("default MeshConfig; a differently-shaped mesh is a "
                 "different XLA program for every record"),
    }]


# --------------------------------------------------------------- surface
def build_surface(project) -> dict:
    """The full manifest as a JSON-ready dict. Deterministic: no
    timestamps, stable ordering — byte-identical output for an unchanged
    tree is what makes ``surface --check`` a meaningful gate."""
    knobs = knob_table(project)
    programs = discover_programs(project)
    collect_static_origins(project, programs, knobs)

    buckets = _bucket_dimension(project, knobs)
    dtypes = _dtype_dimension(project, knobs)
    attn = _attn_dimension(project)
    fused = {
        "values": [True, False],
        "witnesses": _knob_witnesses(knobs, ("fused_task_heads",)),
    }
    topologies = _topology_dimension(knobs)

    records = []
    for prog in programs:
        for bucket in buckets["values"]:
            for dtype in dtypes["values"]:
                for fused_mode in (True, False):
                    for topo in topologies:
                        for a in attn["values"]:
                            records.append({
                                "key": _record_key(prog.family, bucket,
                                                   dtype, fused_mode,
                                                   topo["id"], a),
                                "family": prog.family,
                                "bucket": bucket,
                                "param_dtype": dtype,
                                "fused": fused_mode,
                                "topology": topo["id"],
                                "collect_attention": a,
                            })
    records.sort(key=lambda r: r["key"])

    return {
        "version": SURFACE_VERSION,
        "generator": "vmtlint surface",
        "dimensions": {
            "program_families": [p.to_json() for p in programs],
            "buckets": buckets,
            "param_dtypes": dtypes,
            "fused_modes": fused,
            "collect_attention": attn,
            "topologies": topologies,
        },
        "record_count": len(records),
        "records": records,
    }


def _record_key(family: str, bucket: int, dtype: str, fused: bool,
                topo: str, attn: bool) -> str:
    return (f"{family}/b{bucket}/{dtype}/"
            f"{'fused' if fused else 'perhead'}/{topo}/"
            f"{'attn' if attn else 'plain'}")


def record_key_for_engine(family: str, bucket: int, param_dtype: str,
                          fused: bool, topo: str, collect_attention: bool
                          ) -> str:
    """The manifest key a live ``engine._compiled`` entry maps onto —
    the runtime↔manifest contract used by the CPU cross-check test."""
    return _record_key(family, bucket, param_dtype, fused, topo,
                       collect_attention)


def render_surface(surface: dict) -> str:
    return json.dumps(surface, indent=2, sort_keys=True) + "\n"


# ------------------------------------------------------------------ check
def diff_surface(committed: Optional[dict], fresh: dict) -> List[str]:
    """Human-readable drift between the committed manifest and a fresh
    build — dimension-level first (the actionable story), then the record
    delta."""
    if committed is None:
        return [f"{MANIFEST_NAME} missing — run `vmtlint surface` and "
                f"commit it"]
    msgs: List[str] = []
    if committed.get("version") != fresh.get("version"):
        msgs.append(f"manifest version {committed.get('version')} != "
                    f"generator version {fresh.get('version')}")
    cd = committed.get("dimensions", {})
    fd = fresh.get("dimensions", {})
    for dim in ("buckets", "param_dtypes", "fused_modes",
                "collect_attention"):
        cv = cd.get(dim, {}).get("values")
        fv = fd.get(dim, {}).get("values")
        if cv != fv:
            msgs.append(f"dimension `{dim}` drifted: committed {cv} vs "
                        f"tree {fv}")
    cf = [p.get("family") for p in cd.get("program_families", [])]
    ff = [p.get("family") for p in fd.get("program_families", [])]
    if cf != ff:
        msgs.append(f"program families drifted: committed {cf} vs "
                    f"tree {ff}")
    ct = [t.get("id") for t in cd.get("topologies", [])]
    ft = [t.get("id") for t in fd.get("topologies", [])]
    if ct != ft:
        msgs.append(f"topologies drifted: committed {ct} vs tree {ft}")
    ckeys = {r["key"] for r in committed.get("records", [])}
    fkeys = {r["key"] for r in fresh.get("records", [])}
    gone = sorted(ckeys - fkeys)
    new = sorted(fkeys - ckeys)
    if gone:
        msgs.append(f"{len(gone)} record(s) vanished from the tree "
                    f"(first: {gone[0]})")
    if new:
        msgs.append(f"{len(new)} new record(s) not in the committed "
                    f"manifest (first: {new[0]})")
    if not msgs and committed != fresh:
        msgs.append("manifest metadata drifted (witness lines moved?) — "
                    "regenerate with `vmtlint surface`")
    return msgs


# ------------------------------------------------------------------ sarif
def render_surface_sarif(surface: dict) -> str:
    """SARIF view of the manifest: one informational result per program
    family, its witness chains as codeFlows — the same schema the rule
    findings use, so the same viewers consume it."""
    results = []
    for prog in surface["dimensions"]["program_families"]:
        kw = prog["key_witness"]
        flows = []
        steps = [kw]
        for pname, entries in sorted(prog.get("static_origins",
                                              {}).items()):
            for e in entries:
                chain = list(e.get("witness", [])) + [e["call_site"]]
                flows.append(_sarif_flow(chain))
        n = sum(1 for r in surface["records"]
                if r["family"] == prog["family"])
        results.append({
            "ruleId": "COMPILE-SURFACE",
            "level": "note",
            "message": {"text": (
                f"program family `{prog['family']}` "
                f"({prog['builder']}): {n} records in the compile "
                f"surface")},
            "locations": [_sarif_loc(kw)],
            "codeFlows": flows or [_sarif_flow(steps)],
        })
    doc = {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "vmtlint-surface",
                "informationUri": "",
                "rules": [{
                    "id": "COMPILE-SURFACE",
                    "shortDescription": {
                        "text": "compile-surface manifest witness"},
                }],
            }},
            "results": results,
        }],
    }
    return json.dumps(doc, indent=2, sort_keys=True) + "\n"


def _sarif_loc(w: dict) -> dict:
    return {"physicalLocation": {
        "artifactLocation": {"uri": w["path"]},
        "region": {"startLine": max(1, int(w.get("line", 1)))}},
        "message": {"text": w.get("note", "")}}


def _sarif_flow(steps: List[dict]) -> dict:
    return {"threadFlows": [{"locations": [
        {"location": _sarif_loc(s)} for s in steps]}]}
