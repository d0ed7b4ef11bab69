"""Abstract shape/dtype interpretation — the fourth analyzer tier.

The engine's compile cache is keyed by ``(program, bucket, attn)``
and the AOT roadmap wants executables persisted per
(bucket, dtype, fused/quant mode, topology) — but nothing before this
module could *enumerate* that key universe or prove it bounded. This is
the domain that can: symbolic dimensions bound to config knobs
(``EngineConfig.max_text_len``, the bucket tuples), a dtype lattice with
the NumPy/JAX promotion rules that matter on the bf16/int8 serving path,
and pytree-aware values including the int8 ``{"int8", "scale"}`` pair.

The interpreter is a plain :class:`~.dataflow.ForwardAnalysis` over the
per-function CFGs of :mod:`analysis.cfg` — same worklist, same join
discipline as the lock-set tier — with an environment of abstract values
per local name. Everything tracks *provenance*: a scalar knows whether it
came from a literal, a config knob, a bucketing call, or request data,
and carries a witness chain (path, line, description) for the finding
flows and the compile-surface manifest.

Stdlib-only, like the rest of the package: the layering contract forbids
importing jax or numpy, so dtype promotion is a lookup table, not a call
into ``jnp.promote_types``.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, Iterator, List, Optional, Set, Tuple

from vilbert_multitask_tpu.analysis.cfg import (
    Event,
    WithEnter,
    WithExit,
    build_cfg,
    iter_event_nodes,
)
from vilbert_multitask_tpu.analysis.context import ModuleContext
from vilbert_multitask_tpu.analysis.dataflow import (
    ForwardAnalysis,
    iter_event_facts,
    solve,
)

# --------------------------------------------------------------- dtypes
# Promotion ranks inside each kind. bf16 and f16 share a rank on purpose:
# combining them promotes OUT of the 16-bit lattice to f32 (the JAX rule).
_FLOAT_RANK = {"bfloat16": 1, "float16": 1, "float32": 2, "float64": 3}
_INT_RANK = {"bool": 0, "int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
             "int32": 3, "uint32": 3, "int64": 4, "uint64": 4}
_FLOAT_BY_RANK = {1: "float32", 2: "float32", 3: "float64"}
# The low-precision storage/compute dtypes the serving path is built on;
# a silent promotion out of this set is the VMT125 bug class.
LOW_PRECISION = {"bfloat16", "float16", "int8"}


@dataclasses.dataclass(frozen=True)
class DType:
    """Abstract dtype. ``weak=True`` models Python scalars (they adopt the
    other operand's dtype instead of widening it — the JAX weak-type
    rule). ``ctor_line > 0`` records that this dtype came from a
    default-dtype constructor (``jnp.zeros(shape)`` with no ``dtype=``) at
    that source line — the provenance VMT125 reports."""

    name: str = ""  # "" = unknown
    weak: bool = False
    ctor_line: int = 0

    @property
    def known(self) -> bool:
        return bool(self.name)


UNKNOWN_DT = DType()


def promote(a: DType, b: DType) -> DType:
    """JAX-style binary promotion (subset: the kinds this repo serves)."""
    if not a.known or not b.known:
        return UNKNOWN_DT
    if a.name == b.name:
        return DType(a.name, a.weak and b.weak,
                     a.ctor_line or b.ctor_line)
    # Weak scalars adopt the strong side when kinds are compatible.
    if a.weak and not b.weak:
        a, b = b, a
    if b.weak and not a.weak:
        if b.name in _FLOAT_RANK and a.name in _INT_RANK:
            # int array + python float → default float.
            return DType("float32", weak=True)
        return a
    fa, fb = a.name in _FLOAT_RANK, b.name in _FLOAT_RANK
    if fa and fb:
        ra, rb = _FLOAT_RANK[a.name], _FLOAT_RANK[b.name]
        if ra == rb:  # bf16 × f16 → f32
            return DType("float32")
        hi = a if ra > rb else b
        return DType(hi.name, ctor_line=hi.ctor_line)
    if fa != fb:  # int × float → the float side
        hi = a if fa else b
        return DType(hi.name, ctor_line=hi.ctor_line)
    ra = _INT_RANK.get(a.name, 0)
    rb = _INT_RANK.get(b.name, 0)
    return DType(a.name if ra >= rb else b.name)


def promotion_leak(a: DType, b: DType) -> Optional[Tuple[str, int]]:
    """(low_dtype_name, f32_ctor_line) when combining ``a`` and ``b``
    silently widens a low-precision operand to f32 because the other side
    is a *strong* float32 that a default-dtype constructor produced.
    Explicit ``astype(float32)`` casts (ctor_line == 0) are deliberate and
    never reported."""
    for lo, hi in ((a, b), (b, a)):
        if (lo.name in LOW_PRECISION and hi.name == "float32"
                and not hi.weak and hi.ctor_line > 0):
            return lo.name, hi.ctor_line
    return None


# -------------------------------------------------------------- origins
# Provenance lattice for scalar values, ordered by "how dynamic": joins
# take the max rank, so a value that is data-dependent on ANY path stays
# flagged. BOUNDED origins can only take finitely many values per process
# lifetime — safe compile-cache key material.
_ORIGIN_RANK = {"literal": 0, "config": 1, "bucket": 2, "shape": 3,
                "unknown": 4, "param": 5, "data": 6}
BOUNDED_ORIGINS = {"literal", "config", "bucket", "shape"}
# Witness chains are capped so loop fixed points terminate (a chain that
# grows per iteration would never converge).
_MAX_WITNESS = 6

WitnessStep = Tuple[str, int, str]  # (rel_path, line, description)


def _join_origin(a: str, b: str) -> str:
    return a if _ORIGIN_RANK.get(a, 4) >= _ORIGIN_RANK.get(b, 4) else b


@dataclasses.dataclass(frozen=True)
class Scalar:
    """An abstract Python value (int/str/bool dims, static args)."""

    value: object = None  # concrete value when statically known
    origin: str = "unknown"
    sym: str = ""  # knob binding, e.g. "EngineConfig.max_text_len"
    dtype: DType = UNKNOWN_DT
    witness: Tuple[WitnessStep, ...] = ()

    def with_step(self, step: WitnessStep) -> "Scalar":
        chain = (self.witness + (step,))[:_MAX_WITNESS]
        return dataclasses.replace(self, witness=chain)


@dataclasses.dataclass(frozen=True)
class Array:
    """An abstract array: tuple of Scalar dims (None = unknown rank)."""

    shape: Optional[Tuple[Scalar, ...]] = None
    dtype: DType = UNKNOWN_DT

    @property
    def rank(self) -> Optional[int]:
        return None if self.shape is None else len(self.shape)


@dataclasses.dataclass(frozen=True)
class Tup:
    """A Python tuple/list of abstract values."""

    elts: Tuple[object, ...] = ()


@dataclasses.dataclass(frozen=True)
class Tree:
    """A string-keyed pytree node (the dict idiom of batch/param trees)."""

    items: Tuple[Tuple[str, object], ...] = ()

    def child(self, key: str):
        for k, v in self.items:
            if k == key:
                return v
        return None


def is_int8_pair(val) -> bool:
    """The quantized-leaf convention: ``{"int8": values, "scale": scales}``
    (quant.py). Shape rules must treat the pair as one logical leaf whose
    shape is the values leaf's."""
    return (isinstance(val, Tree)
            and {k for k, _ in val.items} == {"int8", "scale"})


def join_values(a, b):
    """Least upper bound of two abstract values (None = unknown/⊤)."""
    if a is None or b is None:
        return None
    if a == b:
        return a
    if isinstance(a, Scalar) and isinstance(b, Scalar):
        return Scalar(
            value=a.value if a.value == b.value else None,
            origin=_join_origin(a.origin, b.origin),
            sym=a.sym if a.sym == b.sym else "",
            dtype=a.dtype if a.dtype == b.dtype else promote(a.dtype,
                                                             b.dtype),
            witness=a.witness if a.witness == b.witness else ())
    if isinstance(a, Array) and isinstance(b, Array):
        if (a.shape is not None and b.shape is not None
                and len(a.shape) == len(b.shape)):
            shape = tuple(join_values(x, y) or Scalar()
                          for x, y in zip(a.shape, b.shape))
        else:
            shape = None
        dt = a.dtype if a.dtype == b.dtype else UNKNOWN_DT
        return Array(shape, dt)
    if (isinstance(a, Tup) and isinstance(b, Tup)
            and len(a.elts) == len(b.elts)):
        return Tup(tuple(join_values(x, y) for x, y in zip(a.elts, b.elts)))
    if isinstance(a, Tree) and isinstance(b, Tree):
        keys = {k for k, _ in a.items} & {k for k, _ in b.items}
        return Tree(tuple((k, join_values(a.child(k), b.child(k)))
                          for k in sorted(keys)))
    return None


def element_of(val):
    """Abstract element of an iterable value (loop-target binding)."""
    if isinstance(val, Tup):
        out = None
        for e in val.elts:
            out = e if out is None else join_values(out, e)
        return out
    if isinstance(val, Array):
        if val.shape is not None and len(val.shape) > 1:
            return Array(val.shape[1:], val.dtype)
        if val.shape is not None and len(val.shape) == 1:
            return Scalar(origin="data", dtype=val.dtype)
        return Array(None, val.dtype)
    if isinstance(val, Scalar):
        # Iterating something scalar-tracked (a request list, range(n)):
        # elements inherit the provenance.
        return Scalar(origin=val.origin, sym=val.sym, witness=val.witness)
    return None


# ----------------------------------------------------------- knob table
# The config dataclasses whose literal field defaults anchor symbolic
# dims. Collected once per project, AST-only.
KNOB_CLASSES = ("EngineConfig", "ViLBertConfig", "MeshConfig",
                "ServingConfig")


@dataclasses.dataclass(frozen=True)
class Knob:
    cls: str
    field: str
    value: object  # literal default (int/str/bool/tuple) or None
    path: str
    line: int

    @property
    def sym(self) -> str:
        return f"{self.cls}.{self.field}"


class KnobTable:
    """Literal config-knob defaults, indexed by class and by field name."""

    def __init__(self) -> None:
        self.by_class: Dict[str, Dict[str, Knob]] = {}
        self._by_field: Dict[str, Optional[Knob]] = {}

    def add(self, knob: Knob) -> None:
        self.by_class.setdefault(knob.cls, {})[knob.field] = knob
        # Field-name lookup is only trusted when unambiguous across the
        # knob classes — a collision poisons the entry.
        if knob.field in self._by_field:
            self._by_field[knob.field] = None
        else:
            self._by_field[knob.field] = knob

    def get(self, cls: str, field: str) -> Optional[Knob]:
        return self.by_class.get(cls, {}).get(field)

    def field(self, name: str) -> Optional[Knob]:
        return self._by_field.get(name)

    def ints(self) -> Set[int]:
        """Every integer derivable from a knob default (tuple elements
        flattened) — the VMT127 'declared shape vocabulary'."""
        out: Set[int] = set()
        for fields in self.by_class.values():
            for knob in fields.values():
                vals = (knob.value if isinstance(knob.value, (tuple, list))
                        else (knob.value,))
                for v in vals:
                    if isinstance(v, int) and not isinstance(v, bool):
                        out.add(v)
        return out

    @property
    def empty(self) -> bool:
        return not self.by_class


def _literal_default(node: Optional[ast.AST]):
    if node is None:
        return None
    try:
        return ast.literal_eval(node)
    except (ValueError, SyntaxError, TypeError):
        return None


def module_knobs(ctx: ModuleContext, table: KnobTable) -> None:
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.ClassDef)
                and node.name in KNOB_CLASSES):
            continue
        for stmt in node.body:
            if (isinstance(stmt, ast.AnnAssign)
                    and isinstance(stmt.target, ast.Name)):
                table.add(Knob(node.name, stmt.target.id,
                               _literal_default(stmt.value),
                               ctx.rel_path, stmt.lineno))
            elif isinstance(stmt, ast.Assign):
                val = _literal_default(stmt.value)
                for t in stmt.targets:
                    if isinstance(t, ast.Name):
                        table.add(Knob(node.name, t.id, val,
                                       ctx.rel_path, stmt.lineno))


def knob_table(project) -> KnobTable:
    """Project-wide knob table, cached on the ProjectGraph."""
    cached = getattr(project, "_shape_knobs", None)
    if cached is not None:
        return cached
    table = KnobTable()
    for mod in project.modules.values():
        module_knobs(mod.ctx, table)
    project._shape_knobs = table
    return table


# ------------------------------------------------------ jit static info
@dataclasses.dataclass(frozen=True)
class JitBinding:
    """A locally-callable jitted binding plus its static-argument facts —
    the call-site side of the compile-key analysis (VMT124)."""

    name: str  # the name call sites use
    params: Tuple[str, ...]  # wrapped function's parameter names
    static_names: Tuple[str, ...]
    line: int


def jit_static_bindings(ctx: ModuleContext) -> Dict[str, JitBinding]:
    """Callable-name → static-arg facts for every jitted binding with at
    least one static argument: decorated defs (called by their own name)
    and ``f = jax.jit(g, static_arg...)`` assignments (called as ``f``)."""
    out: Dict[str, JitBinding] = {}
    for info in ctx.jit_bodies:
        body = info.body
        if (info.static_params
                and isinstance(body, (ast.FunctionDef,
                                      ast.AsyncFunctionDef))):
            params = tuple(a.arg for a in body.args.args)
            out[body.name] = JitBinding(body.name, params,
                                        tuple(info.static_params),
                                        body.lineno)
    defs: Dict[str, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(node.name, node)
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and ctx.is_jit_entry(node.value.func)
                and node.value.args):
            continue
        target = node.value.args[0]
        if not isinstance(target, ast.Name):
            continue
        body = defs.get(target.id)
        if body is None:
            continue
        statics = ctx._static_params_of(node.value, body)
        if not statics:
            continue
        params = tuple(a.arg for a in body.args.args)
        for t in node.targets:
            if isinstance(t, ast.Name):
                out[t.id] = JitBinding(t.id, params, tuple(statics),
                                       node.lineno)
    return out


# ---------------------------------------------------------- interpreter
_SHAPE_CTORS = {"zeros", "ones", "full", "empty"}
_FLOAT_DEFAULT_CTORS = {"zeros", "ones", "full", "empty", "linspace"}
_ARRAY_NAMESPACES = ("jax.numpy", "numpy")
_DTYPE_NAMES = set(_FLOAT_RANK) | set(_INT_RANK)
_BUCKETIZERS = {"bucket_for", "row_bucket_for"}
_ELEMENTWISE = {"add", "subtract", "multiply", "divide", "maximum",
                "minimum", "where", "matmul", "dot", "einsum", "tensordot"}
# Attribute bases that plausibly denote a config object — the guard that
# keeps `anything.max_text_len` from false-binding to a knob.
_CONFIG_TOKENS = ("cfg", "config", "engine", "serving", "model")


def _looks_config(dotted: str) -> bool:
    parts = dotted.split(".")
    return any(any(tok in p for tok in _CONFIG_TOKENS) for p in parts)


class ShapeInterp(ForwardAnalysis):
    """Forward abstract interpretation of one function body.

    Facts are ``{local name: abstract value}`` environments; the solver is
    the shared worklist in :mod:`analysis.dataflow`. Alongside the facts,
    the interpreter accumulates *promotion incidents* — places where a
    low-precision operand met a strong default-constructed f32 — keyed by
    node id so the fixed-point re-runs of ``transfer`` stay idempotent.
    """

    def __init__(self, ctx: ModuleContext, fn: ast.AST, knobs: KnobTable,
                 param_origin: str = "param") -> None:
        self.ctx = ctx
        self.fn = fn
        self.knobs = knobs
        self.param_origin = param_origin
        # id(node) -> (node, low dtype name, f32 ctor line)
        self.promotions: Dict[int, Tuple[ast.AST, str, int]] = {}
        self._loop_iter: Dict[int, ast.expr] = {}
        for node in ast.walk(fn):
            if isinstance(node, (ast.For, ast.AsyncFor)):
                self._loop_iter[id(node.target)] = node.iter
        self.cfg = build_cfg(fn)
        self.in_facts: Optional[Dict[int, object]] = None

    def run(self) -> "ShapeInterp":
        self.in_facts = solve(self.cfg, self)
        return self

    def iter_facts(self) -> Iterator[Tuple[Event, Dict[str, object]]]:
        assert self.in_facts is not None, "run() first"
        return iter_event_facts(self.cfg, self, self.in_facts)

    # ------------------------------------------------------------ lattice
    def initial(self) -> Dict[str, object]:
        env: Dict[str, object] = {}
        args = getattr(self.fn, "args", None)
        if args is None:
            return env
        names = [a.arg for a in (list(getattr(args, "posonlyargs", ()))
                                 + args.args + args.kwonlyargs)]
        for name in names:
            if name == "self":
                continue
            env[name] = Scalar(
                origin=self.param_origin,
                witness=((self.ctx.rel_path, self.fn.lineno,
                          f"parameter `{name}` of "
                          f"`{getattr(self.fn, 'name', '<lambda>')}` — "
                          f"caller-controlled"),))
        return env

    def join(self, a: Dict[str, object], b: Dict[str, object]
             ) -> Dict[str, object]:
        out: Dict[str, object] = {}
        for name in set(a) | set(b):
            if name in a and name in b:
                out[name] = join_values(a[name], b[name])
            else:
                out[name] = a.get(name, b.get(name))
        return out

    # ----------------------------------------------------------- transfer
    def transfer(self, event: Event, fact: Dict[str, object]
                 ) -> Dict[str, object]:
        if isinstance(event, (WithEnter, WithExit)):
            return fact
        if isinstance(event, ast.Assign):
            val = self.eval(event.value, fact)
            env = dict(fact)
            for t in event.targets:
                self._bind(t, val, env)
            return env
        if isinstance(event, ast.AnnAssign) and event.value is not None:
            val = self.eval(event.value, fact)
            env = dict(fact)
            self._bind(event.target, val, env)
            return env
        if isinstance(event, ast.AugAssign):
            self.eval(event.value, fact)
            env = dict(fact)
            self._bind(event.target, None, env)
            return env
        if (isinstance(event, (ast.Name, ast.Tuple, ast.List))
                and isinstance(getattr(event, "ctx", None), ast.Store)):
            # A loop target appended to the loop header by the CFG builder:
            # bind to an abstract element of the iterable.
            it = self._loop_iter.get(id(event))
            elem = element_of(self.eval(it, fact)) if it is not None \
                else None
            env = dict(fact)
            self._bind(event, elem, env)
            return env
        if isinstance(event, ast.Return) and event.value is not None:
            self.eval(event.value, fact)
            return fact
        if isinstance(event, ast.Expr):
            self.eval(event.value, fact)
            return fact
        if isinstance(event, ast.expr):
            # Branch tests and loop iterables appear as bare expr events.
            self.eval(event, fact)
            return fact
        return fact

    def _bind(self, target: ast.AST, val, env: Dict[str, object]) -> None:
        if isinstance(target, ast.Name):
            env[target.id] = val
            return
        if isinstance(target, (ast.Tuple, ast.List)):
            elts = target.elts
            vals: List[object]
            if isinstance(val, Tup) and len(val.elts) == len(elts):
                vals = list(val.elts)
            else:
                vals = [element_of(val) if val is not None else None] \
                    * len(elts)
            for t, v in zip(elts, vals):
                if isinstance(t, ast.Starred):
                    self._bind(t.value, None, env)
                else:
                    self._bind(t, v, env)

    # --------------------------------------------------------------- eval
    def eval(self, node: Optional[ast.AST], env: Dict[str, object]):
        """Abstract value of an expression under ``env`` (None = ⊤)."""
        if node is None:
            return None
        if isinstance(node, ast.Constant):
            return self._const(node)
        if isinstance(node, ast.Name):
            return env.get(node.id)
        if isinstance(node, (ast.Tuple, ast.List)):
            return Tup(tuple(self.eval(e, env) for e in node.elts))
        if isinstance(node, ast.Dict):
            if all(isinstance(k, ast.Constant) and isinstance(k.value, str)
                   for k in node.keys if k is not None):
                items = tuple(sorted(
                    (k.value, self.eval(v, env))
                    for k, v in zip(node.keys, node.values)
                    if k is not None))
                return Tree(items)
            return None
        if isinstance(node, ast.Attribute):
            return self._attribute(node, env)
        if isinstance(node, ast.Call):
            return self._call(node, env)
        if isinstance(node, ast.BinOp):
            return self._binop(node, env)
        if isinstance(node, ast.UnaryOp):
            return self.eval(node.operand, env)
        if isinstance(node, ast.Subscript):
            return self._subscript(node, env)
        if isinstance(node, ast.IfExp):
            self.eval(node.test, env)
            return join_values(self.eval(node.body, env),
                               self.eval(node.orelse, env))
        if isinstance(node, (ast.Compare, ast.BoolOp)):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.expr):
                    self.eval(child, env)
            return Scalar(dtype=DType("bool", weak=True), origin="unknown")
        if isinstance(node, ast.Starred):
            return self.eval(node.value, env)
        return None

    def _const(self, node: ast.Constant):
        v = node.value
        step = (self.ctx.rel_path, node.lineno, f"literal `{v!r}`")
        if isinstance(v, bool):
            return Scalar(v, "literal", dtype=DType("bool", weak=True),
                          witness=(step,))
        if isinstance(v, int):
            return Scalar(v, "literal", dtype=DType("int32", weak=True),
                          witness=(step,))
        if isinstance(v, float):
            return Scalar(v, "literal", dtype=DType("float32", weak=True),
                          witness=(step,))
        if isinstance(v, str):
            return Scalar(v, "literal", witness=(step,))
        return None

    def _attribute(self, node: ast.Attribute, env: Dict[str, object]):
        base = self.eval(node.value, env)
        attr = node.attr
        if isinstance(base, Array):
            if attr == "shape":
                return Tup(base.shape) if base.shape is not None else None
            if attr == "ndim":
                return (Scalar(base.rank, "literal")
                        if base.rank is not None else None)
            if attr == "dtype":
                return Scalar(value=base.dtype.name or None,
                              origin="literal", dtype=base.dtype,
                              sym="<dtype>")
            if attr == "T":
                shape = (tuple(reversed(base.shape))
                         if base.shape is not None else None)
                return Array(shape, base.dtype)
            return None
        if isinstance(base, Tree):
            return base.child(attr)
        # Config-knob read: `cfg.engine.max_text_len`, `ecfg.image_buckets`.
        knob = self.knobs.field(attr)
        if knob is not None:
            dotted = self.ctx.resolve(node.value)
            src = node.value
            base_name = (src.id if isinstance(src, ast.Name)
                         else src.attr if isinstance(src, ast.Attribute)
                         else "")
            if _looks_config(dotted or base_name):
                return self._knob_scalar(knob, node.lineno)
        if attr == "bucket":
            # `req.bucket` — prepared requests carry an already-bucketed
            # row count (engine.prepare routes through bucket_for).
            return Scalar(origin="bucket", sym=".bucket",
                          witness=((self.ctx.rel_path, node.lineno,
                                    "reads `.bucket` of a prepared "
                                    "request (bucketed upstream by "
                                    "EngineConfig.bucket_for)"),))
        return None

    def _knob_scalar(self, knob: Knob, line: int):
        step = (knob.path, knob.line,
                f"declared `{knob.sym} = {knob.value!r}`")
        use = (self.ctx.rel_path, line, f"reads config knob `{knob.sym}`")
        if isinstance(knob.value, (tuple, list)):
            elts = tuple(
                Scalar(v, "config", sym=knob.sym, witness=(step, use))
                for v in knob.value)
            return Tup(elts)
        return Scalar(knob.value, "config", sym=knob.sym,
                      witness=(step, use))

    # ----------------------------------------------------------- calls
    def _call(self, node: ast.Call, env: Dict[str, object]):
        resolved = self.ctx.resolve(node.func)
        func = node.func
        # Evaluate arguments first — reports (promotions) must fire even
        # for calls the interpreter doesn't model.
        arg_vals = [self.eval(a, env) for a in node.args]
        kw_vals = {kw.arg: self.eval(kw.value, env)
                   for kw in node.keywords if kw.arg}

        if isinstance(func, ast.Name):
            if func.id == "len" and len(node.args) == 1:
                return self._len(node, arg_vals[0])
            if func.id in ("min", "max", "int", "abs", "round") \
                    and node.args:
                return self._scalar_math(node, arg_vals)
            if func.id == "sorted" and node.args:
                return arg_vals[0]
            if func.id == "range":
                return self._scalar_math(node, arg_vals)
        if isinstance(func, ast.Attribute):
            attr = func.attr
            if attr in _BUCKETIZERS:
                arg = arg_vals[0] if arg_vals else None
                chain = tuple(arg.witness) if isinstance(arg, Scalar) \
                    else ()
                return Scalar(
                    origin="bucket", sym=f"EngineConfig.{attr}",
                    witness=(chain + (
                        (self.ctx.rel_path, node.lineno,
                         f"bucketized via `EngineConfig.{attr}()` — "
                         f"domain bounded by the declared buckets"),)
                    )[:_MAX_WITNESS])
            if attr == "all_row_buckets":
                return self._all_row_buckets(node)
            if attr == "astype" and node.args:
                recv = self.eval(func.value, env)
                dt = self._as_dtype(node.args[0], env) or UNKNOWN_DT
                shape = recv.shape if isinstance(recv, Array) else None
                return Array(shape, dataclasses.replace(dt, ctor_line=0))
            if attr == "reshape":
                recv = self.eval(func.value, env)
                dt = recv.dtype if isinstance(recv, Array) else UNKNOWN_DT
                shape_val = (Tup(tuple(arg_vals))
                             if len(node.args) > 1
                             else (arg_vals[0] if arg_vals else None))
                return Array(self._as_shape(shape_val), dt)
            if attr in ("sum", "mean", "squeeze", "flatten", "ravel"):
                recv = self.eval(func.value, env)
                if isinstance(recv, Array):
                    return Array(None, recv.dtype)
                return None
            if attr in ("get", "pop") and node.args:
                recv = self.eval(func.value, env)
                key = arg_vals[0]
                if (isinstance(recv, Tree) and isinstance(key, Scalar)
                        and isinstance(key.value, str)):
                    return recv.child(key.value)
                return None
        ns_call = self._namespace_call(resolved)
        if ns_call is not None:
            return self._array_ctor(ns_call, node, arg_vals, kw_vals, env)
        return None

    @staticmethod
    def _namespace_call(resolved: str) -> Optional[str]:
        for ns in _ARRAY_NAMESPACES:
            if resolved.startswith(ns + "."):
                return resolved[len(ns) + 1:]
        return None

    def _array_ctor(self, name: str, node: ast.Call, arg_vals, kw_vals,
                    env: Dict[str, object]):
        if name in _SHAPE_CTORS or name == "linspace":
            dtype_expr = None
            for kw in node.keywords:
                if kw.arg == "dtype":
                    dtype_expr = kw.value
            dtype_pos = {"zeros": 1, "ones": 1, "empty": 1, "full": 2}
            pos = dtype_pos.get(name)
            if dtype_expr is None and pos is not None \
                    and len(node.args) > pos:
                dtype_expr = node.args[pos]
            dt = self._as_dtype(dtype_expr, env) if dtype_expr is not None \
                else None
            if dt is None:
                dt = (DType("float32", ctor_line=node.lineno)
                      if name in _FLOAT_DEFAULT_CTORS else UNKNOWN_DT)
            shape = self._as_shape(arg_vals[0]) if arg_vals else None
            return Array(shape, dt)
        if name in ("array", "asarray"):
            dtype_expr = None
            for kw in node.keywords:
                if kw.arg == "dtype":
                    dtype_expr = kw.value
            if dtype_expr is None and len(node.args) > 1:
                dtype_expr = node.args[1]
            if dtype_expr is not None:
                dt = self._as_dtype(dtype_expr, env) or UNKNOWN_DT
                return Array(None, dataclasses.replace(dt, ctor_line=0))
            src = arg_vals[0] if arg_vals else None
            if isinstance(src, Array):
                return src
            if isinstance(src, Tup):
                has_float = any(isinstance(e, Scalar)
                                and isinstance(e.value, float)
                                for e in src.elts)
                dt = (DType("float32", ctor_line=node.lineno) if has_float
                      else DType("int32"))
                return Array((Scalar(len(src.elts), "literal"),), dt)
            return Array(None, UNKNOWN_DT)
        if name == "arange":
            any_float = any(isinstance(v, Scalar)
                            and isinstance(v.value, float)
                            for v in arg_vals)
            dt = (DType("float32", ctor_line=node.lineno) if any_float
                  else DType("int32"))
            for kw in node.keywords:
                if kw.arg == "dtype":
                    dt = self._as_dtype(kw.value, env) or UNKNOWN_DT
            return Array(None, dt)
        if name == "broadcast_to" and len(node.args) >= 2:
            src = arg_vals[0]
            dt = src.dtype if isinstance(src, Array) else UNKNOWN_DT
            return Array(self._as_shape(arg_vals[1]), dt)
        if name == "pad" and arg_vals:
            src = arg_vals[0]
            dt = src.dtype if isinstance(src, Array) else UNKNOWN_DT
            return Array(None, dt)
        if name.split(".")[-1] in _ELEMENTWISE:
            return self._combine(node, arg_vals)
        return None

    def _all_row_buckets(self, node: ast.Call):
        img = self.knobs.field("image_buckets")
        thr = self.knobs.field("throughput_buckets")
        values: Set[int] = set()
        for knob in (img, thr):
            if knob is not None and isinstance(knob.value, (tuple, list)):
                values |= {v for v in knob.value if isinstance(v, int)}
        step = (self.ctx.rel_path, node.lineno,
                "iterates `EngineConfig.all_row_buckets()` — the sorted "
                "union of image_buckets and throughput_buckets")
        if values:
            return Tup(tuple(
                Scalar(v, "bucket", sym="EngineConfig.all_row_buckets",
                       witness=(step,))
                for v in sorted(values)))
        return Scalar(origin="bucket",
                      sym="EngineConfig.all_row_buckets", witness=(step,))

    def _len(self, node: ast.Call, arg):
        if isinstance(arg, Tup):
            return Scalar(len(arg.elts), "literal")
        if isinstance(arg, Array) and arg.shape is not None:
            return arg.shape[0] if arg.shape else Scalar(0, "literal")
        if isinstance(arg, Scalar):
            if arg.origin in ("param", "data"):
                stepped = arg.with_step(
                    (self.ctx.rel_path, node.lineno,
                     "`len()` of it — varies with the request payload"))
                return dataclasses.replace(stepped, value=None,
                                           origin="data")
            return dataclasses.replace(arg, value=None)
        return None

    def _scalar_math(self, node: ast.Call, arg_vals):
        origin, sym = "literal", ""
        witness: Tuple[WitnessStep, ...] = ()
        for v in arg_vals:
            if isinstance(v, Scalar):
                if _ORIGIN_RANK.get(v.origin, 4) > _ORIGIN_RANK[origin]:
                    origin, sym, witness = v.origin, v.sym, v.witness
            elif v is None:
                if _ORIGIN_RANK["unknown"] > _ORIGIN_RANK[origin]:
                    origin, sym, witness = "unknown", "", ()
        return Scalar(None, origin, sym=sym, witness=witness)

    def _combine(self, node: ast.AST, vals) -> Optional[Array]:
        """Arithmetic combination: promote dtypes, record promotion leaks,
        and keep an elementwise shape when the ranks agree."""
        dts: List[DType] = []
        shapes: List[Optional[Tuple[Scalar, ...]]] = []
        any_array = False
        for v in vals:
            if isinstance(v, Array):
                any_array = True
                dts.append(v.dtype)
                shapes.append(v.shape)
            elif isinstance(v, Scalar) and v.dtype.known:
                dts.append(v.dtype)
        if not any_array:
            return None
        acc = UNKNOWN_DT
        leaked = False
        for dt in dts:
            if not acc.known:
                acc = dt
                continue
            leak = promotion_leak(acc, dt)
            if leak is not None:
                leaked = True
                if id(node) not in self.promotions:
                    self.promotions[id(node)] = (node, leak[0], leak[1])
            acc = promote(acc, dt)
        if leaked:
            # The widening is reported once at its root; stripping the
            # ctor provenance keeps every downstream use of the (now-f32)
            # result from re-reporting the same leak.
            acc = dataclasses.replace(acc, ctor_line=0)
        shape = None
        known = [s for s in shapes if s is not None]
        if known and all(len(s) == len(known[0]) for s in known):
            shape = known[0]
        return Array(shape, acc)

    def _binop(self, node: ast.BinOp, env: Dict[str, object]):
        lhs = self.eval(node.left, env)
        rhs = self.eval(node.right, env)
        if isinstance(lhs, Array) or isinstance(rhs, Array):
            return self._combine(node, [lhs, rhs])
        if isinstance(lhs, Scalar) and isinstance(rhs, Scalar):
            value = None
            if lhs.value is not None and rhs.value is not None and \
                    isinstance(lhs.value, (int, float)) and \
                    isinstance(rhs.value, (int, float)):
                try:
                    value = _fold_binop(node.op, lhs.value, rhs.value)
                except (ZeroDivisionError, TypeError, ValueError):
                    value = None
            origin = _join_origin(lhs.origin, rhs.origin)
            worse = lhs if _ORIGIN_RANK.get(lhs.origin, 4) >= \
                _ORIGIN_RANK.get(rhs.origin, 4) else rhs
            return Scalar(value, origin, sym=worse.sym,
                          dtype=promote(lhs.dtype, rhs.dtype),
                          witness=worse.witness)
        if isinstance(lhs, Tup) and isinstance(rhs, Tup) and \
                isinstance(node.op, ast.Add):
            return Tup(lhs.elts + rhs.elts)
        return None

    def _subscript(self, node: ast.Subscript, env: Dict[str, object]):
        base = self.eval(node.value, env)
        idx = self.eval(node.slice, env)
        if isinstance(base, Tup):
            if isinstance(idx, Scalar) and isinstance(idx.value, int):
                i = idx.value
                if -len(base.elts) <= i < len(base.elts):
                    return base.elts[i]
            return element_of(base) if not isinstance(node.slice,
                                                      ast.Slice) else base
        if isinstance(base, Tree) and isinstance(idx, Scalar) \
                and isinstance(idx.value, str):
            return base.child(idx.value)
        if isinstance(base, Array):
            if isinstance(node.slice, ast.Slice):
                return Array(None, base.dtype)
            if base.shape is not None and len(base.shape) >= 1 \
                    and not isinstance(node.slice, ast.Tuple):
                if len(base.shape) == 1:
                    return Scalar(origin="data", dtype=base.dtype)
                return Array(base.shape[1:], base.dtype)
            return Array(None, base.dtype)
        return None

    # ------------------------------------------------------------- dtypes
    def _as_dtype(self, expr: Optional[ast.AST], env: Dict[str, object]
                  ) -> Optional[DType]:
        if expr is None:
            return None
        resolved = self.ctx.resolve(expr)
        leaf = resolved.split(".")[-1] if resolved else ""
        if leaf in _DTYPE_NAMES:
            return DType(leaf)
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str) \
                and expr.value in _DTYPE_NAMES:
            return DType(expr.value)
        val = self.eval(expr, env)
        if isinstance(val, Scalar):
            if val.sym == "<dtype>" and val.dtype.known:
                return dataclasses.replace(val.dtype, ctor_line=0)
            if isinstance(val.value, str) and val.value in _DTYPE_NAMES:
                return DType(val.value)
            if isinstance(val.value, str):
                # A config-bound dtype string we don't recognize —
                # treat as explicit (never a default-dtype leak).
                return DType(val.value)
        return None

    def _as_shape(self, val) -> Optional[Tuple[Scalar, ...]]:
        if isinstance(val, Tup):
            return tuple(e if isinstance(e, Scalar) else Scalar()
                         for e in val.elts)
        if isinstance(val, Scalar):
            return (val,)
        return None


def _fold_binop(op: ast.AST, a, b):
    if isinstance(op, ast.Add):
        return a + b
    if isinstance(op, ast.Sub):
        return a - b
    if isinstance(op, ast.Mult):
        return a * b
    if isinstance(op, ast.FloorDiv):
        return a // b
    if isinstance(op, ast.Mod):
        return a % b
    if isinstance(op, ast.Pow) and abs(b) < 64:
        return a ** b
    return None


def interpret_function(ctx: ModuleContext, fn: ast.AST, knobs: KnobTable,
                       param_origin: str = "param") -> ShapeInterp:
    """Build, solve, and return the interpreter for one function."""
    return ShapeInterp(ctx, fn, knobs, param_origin=param_origin).run()


def flows_from(witness: Tuple[WitnessStep, ...],
               final: Optional[WitnessStep] = None) -> List[List[dict]]:
    """Witness chain → the Finding.flows / SARIF codeFlows schema."""
    steps = list(witness) + ([final] if final is not None else [])
    if not steps:
        return []
    return [[{"path": p, "line": ln, "message": msg}
             for p, ln, msg in steps]]


def call_nodes_in(event: Event) -> Iterator[ast.Call]:
    for node in iter_event_nodes(event):
        if isinstance(node, ast.Call):
            yield node
