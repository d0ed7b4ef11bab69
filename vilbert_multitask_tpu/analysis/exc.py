"""Exception-flow analysis: the set of exception classes that can
escape each function, resolved down to every *boundary*.

The seventh analyzer tier.  Where the protocol tier proves lifecycles,
this tier proves the *failure plane*: the reference demo survived on one
broad ``try/except`` around its inference loop; our fleet replaced that
with ~10 daemon threads where an escaping exception kills the thread
silently — the queue backs up, SLOs page late, and nothing names the
culprit.

The engine is a per-function *frame IR* mirroring the CFG's conservative
try-lowering (an exception may surface at any statement boundary of a
try body; a handler observes the union of body escapes): each library
function lowers to a sequence of ``raise`` / ``call`` / ``try`` /
``guard`` items, and a monotone fixed point composes per-function
escape summaries through the call graph — witness chains are frozen on
first appearance, so the key set only grows and termination is
structural.  Raise-site inference covers ``raise X from e`` chains and
bare/alias re-raises; handler narrowing resolves tuple aliases
(``_NET_ERRORS``) through the project graph and subclass hierarchies
through a builtin + curated-external + project-class MRO table.

Boundaries — the places an escape stops being a Python exception and
becomes an operational event — are resolved with their escaping sets:

* ``thread``   — ``threading.Thread``/``Timer`` targets and Thread
  subclass ``run``; an escape here is silent thread death (VMT137)
  unless the body runs under ``obs.crash_guard`` (the runtime twin this
  tier proves complete).
* ``http-verb`` — ``do_*`` handlers; the server's dispatch contains
  escapes, so the verdict is ``server-handled``.
* ``tick``     — ``obs.Sampler`` probe callables; ``Sampler._run``
  catches per tick, so the verdict is ``caller-contained``.
* ``breaker``  — ``RetryPolicy.call(..., breaker=...)`` regions and
  manual ``preflight``/``record_failure`` frames; escapes the recording
  clause never observes are breaker-blind (VMT138).
* ``fault-site`` — every ``fault_point``; the verdict says whether the
  injected fault escapes the enclosing function.

Two cross-tier checks ride on the same flow: a broad handler that
swallows an exception while a claim/checkout still owes its terminal
(VMT139, composed with :mod:`analysis.proto`), and outbound
error/verdict strings drifting from the vocabulary the txn tier
recovered plus the library's own non-handler verdict sites (VMT140,
with did-you-mean).

Run generatively (``python -m vilbert_multitask_tpu.analysis exc``)
the tier emits ``FAILURE_SURFACE.json`` — every boundary with its
escaping set and verdict, the handler inventory, and the project
exception taxonomy — committed and drift-gated (``exc --check`` in
check.sh).

Everything here is stdlib-only (the analysis-layer contract).
"""

from __future__ import annotations

import ast
import builtins
import difflib
import json
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .proto import proto_flow
from .txn import txn_flow

EXC_VERSION = 1
MANIFEST_NAME = "FAILURE_SURFACE.json"

# Paths that never host boundaries or findings: test idioms raise and
# swallow on purpose.
_NON_LIBRARY_HEADS = ("tests", "scripts")

# Witness chains stop growing past this depth (the class keeps
# propagating; only the chain is capped).
_MAX_CHAIN = 6
# A by-name callee fallback unions at most this many candidates.
_MAX_CANDIDATES = 4
# Fixed-point round budget — structural monotonicity converges in a
# handful of rounds; the cap turns a bug into silence, not a hang.
_ROUND_CAP = 24

# Control-flow exceptions that are not failures: a thread exiting on
# SystemExit is a shutdown, not a death.
_EXIT_EXCS = {"SystemExit", "KeyboardInterrupt", "GeneratorExit",
              "StopIteration", "StopAsyncIteration"}

_BROAD = ("Exception", "BaseException")
_THREAD_CTORS = ("threading.Thread", "threading.Timer")
# ``with crash_guard("name"):`` / ``with obs.crash_guard(...):`` marks a
# runtime-guarded region: Exception-rooted escapes are recorded and
# swallowed there (obs/watchdog.py), exit exceptions pass through.
_CRASH_GUARD_NAMES = {"crash_guard"}

# Leaf method names too generic for the by-name union fallback —
# matching ``.get()`` against every project ``get`` method would invent
# escapes out of dictionaries.
_GENERIC_LEAVES = {
    "get", "put", "set", "add", "pop", "update", "items", "keys",
    "values", "append", "extend", "insert", "remove", "clear", "copy",
    "close", "open", "read", "write", "flush", "join", "start", "stop",
    "run", "send", "recv", "encode", "decode", "strip", "split",
    "format", "wait", "notify", "acquire", "release", "register",
    "record", "next", "reset",
}


def _builtin_mros() -> Dict[str, Tuple[str, ...]]:
    table: Dict[str, Tuple[str, ...]] = {}
    for name in dir(builtins):
        obj = getattr(builtins, name, None)
        if isinstance(obj, type) and issubclass(obj, BaseException):
            table[name] = tuple(c.__name__ for c in obj.__mro__
                                if issubclass(c, BaseException))
    return table


_BUILTIN_MRO = _builtin_mros()

# Curated leaves of the stdlib exception classes the serving stack
# actually meets (urllib, sockets, sqlite3, json, queue).  Unknown
# classes default to ``(name, Exception, BaseException)`` — handler
# narrowing stays sound for anything Exception-rooted.
_KNOWN_EXTERNAL: Dict[str, Tuple[str, ...]] = {
    "HTTPError": ("HTTPError", "URLError", "OSError",
                  "Exception", "BaseException"),
    "URLError": ("URLError", "OSError", "Exception", "BaseException"),
    "timeout": ("timeout", "OSError", "Exception", "BaseException"),
    "Empty": ("Empty", "Exception", "BaseException"),
    "Full": ("Full", "Exception", "BaseException"),
    "JSONDecodeError": ("JSONDecodeError", "ValueError",
                        "Exception", "BaseException"),
    "Error": ("Error", "Exception", "BaseException"),
    "DatabaseError": ("DatabaseError", "Error",
                      "Exception", "BaseException"),
    "OperationalError": ("OperationalError", "DatabaseError", "Error",
                         "Exception", "BaseException"),
    "IntegrityError": ("IntegrityError", "DatabaseError", "Error",
                       "Exception", "BaseException"),
}


def _is_library(rel_path: str) -> bool:
    head = rel_path.split("/", 1)[0]
    if head in _NON_LIBRARY_HEADS:
        return False
    base = rel_path.rsplit("/", 1)[-1]
    return not (base.startswith("test_") or base == "conftest.py")


def _witness(path: str, line: int, note: str) -> dict:
    return {"path": path, "line": line, "message": note}


# ---------------------------------------------------------------------------
# The flow
# ---------------------------------------------------------------------------

class ExcFlow:
    """Interprocedural escape facts over the whole project.

    Built once per project (see :func:`exc_flow`) and consumed by the
    VMT137-140 rules and by :func:`build_failure_surface`.  All finding
    lists hold plain dicts ``{"path", "line", "col", "message"[,
    "flows"]}`` so rules stay thin adapters."""

    def __init__(self, project) -> None:
        self.project = project
        self.cg = project.callgraph
        self._mro_cache: Dict[str, Tuple[str, ...]] = {}
        self.classes: Dict[str, dict] = {}
        # Every node of a module's tree in ``ast.walk`` order, listed once:
        # six passes below look at each whole tree, and the walk itself
        # was most of this tier's wall time.
        self._module_nodes: Dict[int, List[ast.AST]] = {}
        self._build_class_index()
        # Leaf method name -> qualname iff unique among library
        # functions, plus the full leaf -> candidates map for the
        # bounded union fallback (``self.queue.claim`` must union both
        # DurableQueue.claim and the remote twin).
        self._unique: Dict[str, Optional[str]] = {}
        self._by_leaf: Dict[str, List[str]] = {}
        # All library quals, fixed BEFORE frame building: frames are
        # built in sort order, so membership in self.frames would drop
        # every callee that sorts after its caller.
        self._library: Set[str] = set()
        for qual in sorted(self.cg.functions):
            fn = self.cg.functions[qual]
            if not _is_library(fn.module.ctx.rel_path):
                continue
            self._library.add(qual)
            leaf = fn.scope[-1]
            self._unique[leaf] = (
                None if leaf in self._unique else qual)
            if fn.cls_scope:
                self._by_leaf.setdefault(leaf, []).append(qual)
        # Frame IR per library function: (items, has_guard).
        self.frames: Dict[str, Tuple[list, bool]] = {}
        for qual in sorted(self.cg.functions):
            fn = self.cg.functions[qual]
            if _is_library(fn.module.ctx.rel_path):
                self.frames[qual] = self._build_frame(fn)
        # qual -> {exception name -> frozen witness chain}.
        self.summaries: Dict[str, Dict[str, tuple]] = {}
        self._solve()
        self.boundaries: List[dict] = []
        self._discover_boundaries()
        # Finding dicts, populated by the passes below.
        self.thread_findings: List[dict] = []
        self.breaker_findings: List[dict] = []
        self.shadow_findings: List[dict] = []
        self.frame_findings: List[dict] = []
        self._check_thread_escapes()
        self._check_breaker_blind()
        self._check_handler_shadows()
        self._check_frame_drift()

    # ------------------------------------------------------------ taxonomy
    def _walk(self, tree: ast.AST) -> List[ast.AST]:
        nodes = self._module_nodes.get(id(tree))
        if nodes is None:
            nodes = self._module_nodes[id(tree)] = list(ast.walk(tree))
        return nodes

    def _build_class_index(self) -> None:
        """Project exception classes: every library ``ClassDef`` whose
        base chain roots in a known exception, to a fixed point (so
        ``class Child(ProjectError)`` lands once ``ProjectError`` has)."""
        candidates: Dict[str, Tuple[List[str], str, int]] = {}
        for mod in sorted(self.project.modules.values(),
                          key=lambda m: m.name):
            ctx = mod.ctx
            if not _is_library(ctx.rel_path):
                continue
            for node in self._walk(ctx.tree):
                if not isinstance(node, ast.ClassDef) or not node.bases:
                    continue
                leaves = []
                for b in node.bases:
                    dotted = ctx.resolve(b)
                    leaf = dotted.rsplit(".", 1)[-1] if dotted else (
                        b.attr if isinstance(b, ast.Attribute) else "")
                    if leaf:
                        leaves.append(leaf)
                candidates.setdefault(
                    node.name, (leaves, ctx.rel_path, node.lineno))
        known = set(_BUILTIN_MRO) | set(_KNOWN_EXTERNAL)
        changed = True
        while changed:
            changed = False
            for name, (bases, path, line) in candidates.items():
                if name in self.classes:
                    continue
                if any(b in known or b in self.classes for b in bases):
                    self.classes[name] = {
                        "bases": bases, "path": path, "line": line}
                    changed = True

    def _mro(self, name: str) -> Tuple[str, ...]:
        cached = self._mro_cache.get(name)
        if cached is not None:
            return cached
        self._mro_cache[name] = (name,)  # cycle guard
        if name in _BUILTIN_MRO:
            out = _BUILTIN_MRO[name]
        elif name in _KNOWN_EXTERNAL:
            out = _KNOWN_EXTERNAL[name]
        elif name in self.classes:
            acc: List[str] = [name]
            for b in self.classes[name]["bases"]:
                for x in self._mro(b):
                    if x not in acc:
                        acc.append(x)
            out = tuple(acc)
        else:
            # Unknown class: assume Exception-rooted (the sound default
            # for handler narrowing — broad handlers still catch it).
            out = (name, "Exception", "BaseException")
        self._mro_cache[name] = out
        return out

    # ------------------------------------------------------------- helpers
    def _rel_path(self, qual: str) -> str:
        return self.cg.functions[qual].module.ctx.rel_path

    def _display(self, qual: str) -> str:
        mod, scope = qual.split(":", 1)
        return f"{mod}.{scope}"

    def _call_candidates(self, fn, call: ast.Call) -> tuple:
        """Project callees a call may reach: exact resolution first,
        then the by-name unique fallback, then a bounded union over
        same-leaf methods (receiver types are invisible — missing
        ``queue.claim``'s remote twin would hide its escapes)."""
        qual = self.cg.resolve_callable(
            fn.module, call.func, fn.scope, fn.cls_scope)
        if qual is not None:
            return (qual,) if qual in self._library else ()
        func = call.func
        if isinstance(func, ast.Attribute) and not (
                isinstance(func.value, ast.Name)
                and func.value.id == "self"):
            leaf = func.attr
            uq = self._unique.get(leaf)
            if uq is not None and uq != fn.qualname \
                    and uq in self._library:
                return (uq,)
            if leaf not in _GENERIC_LEAVES:
                cands = tuple(
                    q for q in self._by_leaf.get(leaf, ())
                    if q != fn.qualname and q in self._library)
                if 0 < len(cands) <= _MAX_CANDIDATES:
                    return cands
        return ()

    # ------------------------------------------------------------ frame IR
    def _raise_name(self, fn, node: ast.Raise,
                    aliases: frozenset) -> Optional[str]:
        """Class name a ``raise`` throws: ``None`` means re-raise the
        active exception (bare raise, or raising the handler alias);
        ``<dynamic>`` means a value only broad handlers can catch."""
        exc = node.exc
        if exc is None:
            return None
        if isinstance(exc, ast.Call):
            exc = exc.func
        if isinstance(exc, ast.Name) and exc.id in aliases:
            return None
        if isinstance(exc, (ast.Name, ast.Attribute)):
            dotted = fn.module.ctx.resolve(exc)
            leaf = dotted.rsplit(".", 1)[-1] if dotted else (
                exc.attr if isinstance(exc, ast.Attribute) else "")
            if leaf and (leaf in _BUILTIN_MRO or leaf in _KNOWN_EXTERNAL
                         or leaf in self.classes or leaf[:1].isupper()):
                return leaf
        return "<dynamic>"

    def _expr_calls(self, fn, expr: Optional[ast.AST]) -> list:
        """``call`` items for every call inside an expression (lambda
        bodies excluded — they don't run at statement time)."""
        items: list = []
        if expr is None:
            return items
        stack: List[ast.AST] = [expr]
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.Lambda, ast.FunctionDef,
                                 ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            if isinstance(node, ast.Call):
                quals = self._call_candidates(fn, node)
                if quals:
                    func = node.func
                    disp = func.attr if isinstance(func, ast.Attribute) \
                        else (func.id if isinstance(func, ast.Name)
                              else "<call>")
                    items.append(("call", quals, node.lineno, disp))
            stack.extend(ast.iter_child_nodes(node))
        return items

    def _handler_type_names(self, mod, expr: Optional[ast.AST],
                            depth: int = 0) -> Optional[Tuple[str, ...]]:
        """Leaf class names a handler clause declares — ``None`` for a
        bare ``except``; tuple aliases resolve through the graph."""
        if expr is None:
            return None
        names = self._type_names(mod, expr, depth)
        return tuple(names) if names else ("BaseException",)

    def _type_names(self, mod, expr: ast.AST, depth: int) -> List[str]:
        if depth > 4:
            return []
        if isinstance(expr, (ast.Tuple, ast.List)):
            out: List[str] = []
            for e in expr.elts:
                out.extend(self._type_names(mod, e, depth + 1))
            return out
        if isinstance(expr, ast.Name):
            leaf = expr.id
            if leaf in _BUILTIN_MRO or leaf in _KNOWN_EXTERNAL \
                    or leaf in self.classes:
                return [leaf]
            local = mod.symbols.get(leaf)
            if isinstance(local, ast.Assign) \
                    and isinstance(local.value, (ast.Tuple, ast.List)):
                return self._type_names(mod, local.value, depth + 1)
            target = mod.refs.get(leaf)
            if target:
                resolved = self.project.resolve_symbol(target)
                if resolved is not None:
                    tmod, sym = resolved
                    node = tmod.symbols.get(sym.split(".")[0]) if sym \
                        else None
                    if isinstance(node, ast.Assign) and isinstance(
                            node.value, (ast.Tuple, ast.List)):
                        return self._type_names(tmod, node.value,
                                                depth + 1)
                    if isinstance(node, ast.ClassDef):
                        return [node.name]
                return [target.rsplit(".", 1)[-1]]
            return [leaf]
        if isinstance(expr, ast.Attribute):
            dotted = mod.ctx.resolve(expr)
            leaf = dotted.rsplit(".", 1)[-1] if dotted else expr.attr
            if leaf in _BUILTIN_MRO or leaf in _KNOWN_EXTERNAL \
                    or leaf in self.classes:
                return [leaf]
            if dotted:
                resolved = self.project.resolve_symbol(dotted)
                if resolved is not None:
                    tmod, sym = resolved
                    node = tmod.symbols.get(sym.split(".")[0]) if sym \
                        else None
                    if isinstance(node, ast.Assign) and isinstance(
                            node.value, (ast.Tuple, ast.List)):
                        return self._type_names(tmod, node.value,
                                                depth + 1)
                    if isinstance(node, ast.ClassDef):
                        return [node.name]
            return [leaf]
        return []

    def _is_crash_guard(self, fn, stmt) -> bool:
        for item in stmt.items:
            call = item.context_expr
            if not isinstance(call, ast.Call):
                continue
            func = call.func
            leaf = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else "")
            if leaf in _CRASH_GUARD_NAMES:
                return True
        return False

    def _build_frame(self, fn) -> Tuple[list, bool]:
        guard_seen = [False]
        params = {a.arg for a in fn.node.args.args} | {
            a.arg for a in fn.node.args.kwonlyargs}

        def build(stmts, aliases: frozenset) -> list:
            items: list = []
            for st in stmts:
                if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                    continue
                if isinstance(st, ast.Raise):
                    items.extend(self._expr_calls(fn, st.exc))
                    items.extend(self._expr_calls(fn, st.cause))
                    items.append(("raise",
                                  self._raise_name(fn, st, aliases),
                                  st.lineno))
                elif isinstance(st, ast.Try):
                    handlers = []
                    for h in st.handlers:
                        types = self._handler_type_names(
                            fn.module, h.type)
                        if types is not None and h.type is not None:
                            exprs = h.type.elts if isinstance(
                                h.type, ast.Tuple) else [h.type]
                            if any(isinstance(e, ast.Name)
                                   and e.id in params for e in exprs):
                                # ``except retry_on`` — the clause's
                                # types only exist at the call site:
                                # catches nothing provable, re-raises
                                # anything.
                                types = ("<dynamic>",)
                        broad = types is None or any(
                            t in _BROAD for t in types)
                        h_aliases = aliases | ({h.name} if h.name
                                               else set())
                        handlers.append(
                            (types, build(h.body, h_aliases),
                             h.lineno, broad))
                    items.append((
                        "try", st.lineno, build(st.body, aliases),
                        handlers, build(st.orelse, aliases),
                        build(st.finalbody, aliases)))
                elif isinstance(st, (ast.With, ast.AsyncWith)):
                    for item in st.items:
                        items.extend(
                            self._expr_calls(fn, item.context_expr))
                    if self._is_crash_guard(fn, st):
                        guard_seen[0] = True
                        items.append(("guard", build(st.body, aliases),
                                      st.lineno))
                    else:
                        items.extend(build(st.body, aliases))
                elif isinstance(st, ast.If):
                    items.extend(self._expr_calls(fn, st.test))
                    items.extend(build(st.body, aliases))
                    items.extend(build(st.orelse, aliases))
                elif isinstance(st, ast.While):
                    items.extend(self._expr_calls(fn, st.test))
                    items.extend(build(st.body, aliases))
                    items.extend(build(st.orelse, aliases))
                elif isinstance(st, (ast.For, ast.AsyncFor)):
                    items.extend(self._expr_calls(fn, st.iter))
                    items.extend(build(st.body, aliases))
                    items.extend(build(st.orelse, aliases))
                else:
                    items.extend(self._expr_calls(fn, st))
            return items

        return build(fn.node.body, frozenset()), guard_seen[0]

    # ---------------------------------------------------------- fixed point
    @staticmethod
    def _merge(out: Dict[str, tuple], name: str, chain: tuple) -> None:
        # Chains freeze on first appearance: the key set is the only
        # thing that grows, which is what makes the solve monotone.
        if name not in out:
            out[name] = chain

    def _caught(self, name: str, types: Optional[Tuple[str, ...]]
                ) -> bool:
        if types is None:
            return True
        if name == "<dynamic>":
            return any(t in _BROAD for t in types)
        mro = self._mro(name)
        return any(t in mro for t in types)

    def _eval_items(self, qual: str, items: list,
                    reraise: Dict[str, tuple],
                    out: Dict[str, tuple]) -> None:
        rel = self._rel_path(qual)
        disp = self._display(qual)
        for it in items:
            kind = it[0]
            if kind == "raise":
                name, line = it[1], it[2]
                if name is None:
                    for n, chain in reraise.items():
                        step = _witness(
                            rel, line, f"re-raised in `{disp}`")
                        new = chain if len(chain) >= _MAX_CHAIN \
                            else chain + (step,)
                        self._merge(out, n, new)
                else:
                    self._merge(out, name, (_witness(
                        rel, line, f"`raise {name}` in `{disp}`"),))
            elif kind == "call":
                quals, line, cdisp = it[1], it[2], it[3]
                for cq in quals:
                    for n, chain in self.summaries.get(cq, {}).items():
                        step = _witness(
                            rel, line,
                            f"escapes `{self._display(cq)}` into "
                            f"`{disp}` via `{cdisp}(...)`")
                        new = chain if len(chain) >= _MAX_CHAIN \
                            else chain + (step,)
                        self._merge(out, n, new)
            elif kind == "guard":
                body_out: Dict[str, tuple] = {}
                self._eval_items(qual, it[1], reraise, body_out)
                for n, chain in body_out.items():
                    # crash_guard records-and-swallows Exception-rooted
                    # escapes; exit exceptions pass through.
                    if "Exception" not in self._mro(n):
                        self._merge(out, n, chain)
            elif kind == "try":
                _line, body, handlers, orelse, final = it[1:]
                body_out = {}
                self._eval_items(qual, body, reraise, body_out)
                remaining = dict(body_out)
                for types, hbody, hline, _broad in handlers:
                    entering = {
                        n: remaining[n] for n in sorted(remaining)
                        if self._caught(n, types)}
                    for n in entering:
                        del remaining[n]
                    hreraise = entering
                    if not hreraise and types is not None:
                        # No proven inflow — a bare re-raise still
                        # forwards whatever the clause declares.
                        hreraise = {
                            t: (_witness(rel, hline,
                                         f"handler for `{t}` in "
                                         f"`{disp}`"),)
                            for t in types}
                    self._eval_items(qual, hbody, hreraise, out)
                for n, chain in remaining.items():
                    self._merge(out, n, chain)
                self._eval_items(qual, orelse, reraise, out)
                self._eval_items(qual, final, reraise, out)

    def _solve(self) -> None:
        for qual in self.frames:
            self.summaries[qual] = {}
        rounds = 0
        changed = True
        while changed and rounds < _ROUND_CAP:
            changed = False
            rounds += 1
            for qual in sorted(self.frames):
                out: Dict[str, tuple] = {}
                self._eval_items(qual, self.frames[qual][0], {}, out)
                summ = self.summaries[qual]
                for n, chain in out.items():
                    if n not in summ:
                        summ[n] = chain
                        changed = True
        self.rounds = rounds

    def escapes(self, qual: str) -> Dict[str, tuple]:
        """Failure escapes of one function (exit exceptions dropped)."""
        return {n: c for n, c in self.summaries.get(qual, {}).items()
                if n not in _EXIT_EXCS}

    # --------------------------------------------------------- boundaries
    def _thread_name(self, mod, call: ast.Call) -> Tuple[str, bool]:
        """(thread name, daemon flag) from a Thread/Timer ctor call —
        ``prefix-*`` for f-strings, module constants resolved, else
        ``<unnamed>``."""
        name = "<unnamed>"
        daemon = False
        for kw in call.keywords:
            if kw.arg == "daemon" and isinstance(kw.value, ast.Constant):
                daemon = bool(kw.value.value)
            if kw.arg != "name":
                continue
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                name = v.value
            elif isinstance(v, ast.JoinedStr):
                prefix = ""
                for part in v.values:
                    if isinstance(part, ast.Constant) \
                            and isinstance(part.value, str):
                        prefix = part.value
                        break
                name = f"{prefix}*"
            elif isinstance(v, (ast.Name, ast.Attribute)):
                resolved = self._constant_str(mod, v)
                name = resolved if resolved is not None else "<dynamic>"
        return name, daemon

    def _constant_str(self, mod, expr: ast.AST) -> Optional[str]:
        """A module-level string constant behind a Name/Attribute, or
        None (``name=obs.SAMPLER_THREAD_NAME`` resolves here)."""
        if isinstance(expr, ast.Name):
            node = mod.symbols.get(expr.id)
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, ast.Constant) and isinstance(
                    node.value.value, str):
                return node.value.value
            dotted = mod.refs.get(expr.id, "")
        else:
            dotted = mod.ctx.resolve(expr)
        if dotted:
            resolved = self.project.resolve_symbol(dotted)
            if resolved is not None:
                tmod, sym = resolved
                node = tmod.symbols.get(sym.split(".")[0]) if sym \
                    else None
                if isinstance(node, (ast.Assign, ast.AnnAssign)):
                    value = node.value
                    if isinstance(value, ast.Constant) and isinstance(
                            value.value, str):
                        return value.value
        return None

    def _entry_quals(self, mod, call: ast.Call) -> Tuple[str, ...]:
        """Entry callables of a thread ctor, exact then by-name."""
        targets = [kw.value for kw in call.keywords
                   if kw.arg in ("target", "function")]
        scope, cls = self.cg._lexical_scope(mod, call)
        out: List[str] = []
        for t in targets:
            qual = self.cg.resolve_callable(mod, t, scope, cls)
            if qual is not None:
                out.append(qual)
            elif isinstance(t, ast.Attribute):
                for q in sorted(self.cg.functions):
                    fnode = self.cg.functions[q]
                    if fnode.scope[-1] == t.attr and fnode.cls_scope \
                            and _is_library(fnode.module.ctx.rel_path):
                        out.append(q)
        return tuple(dict.fromkeys(out))

    def _boundary_escapes(self, quals: Tuple[str, ...]
                          ) -> Dict[str, tuple]:
        merged: Dict[str, tuple] = {}
        for q in quals:
            for n, chain in self.escapes(q).items():
                self._merge(merged, n, chain)
        return merged

    def _add_boundary(self, **kw) -> dict:
        entry = {
            "kind": kw["kind"],
            "name": kw["name"],
            "path": kw["path"],
            "line": kw["line"],
            "entries": sorted(self._display(q) for q in
                              kw.get("quals", ())),
            "daemon": kw.get("daemon", False),
            "guard": kw.get("guard", False),
            "escapes": kw.get("escapes", {}),
            "verdict": kw["verdict"],
        }
        self.boundaries.append(entry)
        return entry

    def _discover_boundaries(self) -> None:
        for mod in sorted(self.project.modules.values(),
                          key=lambda m: m.name):
            ctx = mod.ctx
            if not _is_library(ctx.rel_path):
                continue
            for node in self._walk(ctx.tree):
                if isinstance(node, ast.Call):
                    self._thread_boundary(mod, node)
                    self._tick_boundary(mod, node)
                    self._fault_boundary(mod, node)
                elif isinstance(node, ast.ClassDef):
                    self._class_boundaries(mod, node)
        # breaker boundaries ride on their own pass (they need the
        # recording-clause analysis VMT138 shares).

    def _thread_boundary(self, mod, call: ast.Call) -> None:
        if mod.ctx.resolve(call.func) not in _THREAD_CTORS:
            return
        name, daemon = self._thread_name(mod, call)
        quals = self._entry_quals(mod, call)
        guard = any(self.frames.get(q, ((), False))[1] for q in quals)
        escapes = self._boundary_escapes(quals)
        if not quals:
            verdict = "unresolved"
        elif escapes:
            verdict = "escapes"
        elif guard:
            verdict = "guarded"
        else:
            verdict = "clean"
        self._add_boundary(
            kind="thread", name=name, path=mod.ctx.rel_path,
            line=call.lineno, quals=quals, daemon=daemon, guard=guard,
            escapes=escapes, verdict=verdict)

    def _class_boundaries(self, mod, cls: ast.ClassDef) -> None:
        bases = {mod.ctx.resolve(b) for b in cls.bases}
        handler = bases & self.cg._THREAD_VERB_BASES
        thread_sub = "threading.Thread" in bases
        if not (handler or thread_sub):
            return
        for stmt in cls.body:
            if not isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            fnode = self.cg.by_node.get(id(stmt))
            if fnode is None:
                continue
            if handler and stmt.name.startswith("do_"):
                self._add_boundary(
                    kind="http-verb", name=f"{cls.name}.{stmt.name}",
                    path=mod.ctx.rel_path, line=stmt.lineno,
                    quals=(fnode.qualname,),
                    escapes=self.escapes(fnode.qualname),
                    verdict="server-handled")
            if thread_sub and stmt.name == "run":
                escapes = self.escapes(fnode.qualname)
                guard = self.frames.get(
                    fnode.qualname, ((), False))[1]
                verdict = "escapes" if escapes else (
                    "guarded" if guard else "clean")
                self._add_boundary(
                    kind="thread", name=f"{cls.name}.run",
                    path=mod.ctx.rel_path, line=stmt.lineno,
                    quals=(fnode.qualname,), daemon=True, guard=guard,
                    escapes=escapes, verdict=verdict)

    def _tick_boundary(self, mod, call: ast.Call) -> None:
        func = call.func
        leaf = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        if leaf != "Sampler":
            return
        targets = [kw.value for kw in call.keywords
                   if kw.arg == "sample_fn"]
        if len(call.args) >= 2:
            targets.append(call.args[1])
        scope, cls = self.cg._lexical_scope(mod, call)
        quals: List[str] = []
        for t in targets:
            qual = self.cg.resolve_callable(mod, t, scope, cls)
            if qual is not None:
                quals.append(qual)
        if not quals:
            return
        quals_t = tuple(dict.fromkeys(quals))
        self._add_boundary(
            kind="tick", name="obs-sampler", path=mod.ctx.rel_path,
            line=call.lineno, quals=quals_t,
            escapes=self._boundary_escapes(quals_t),
            verdict="caller-contained")

    def _fault_boundary(self, mod, call: ast.Call) -> None:
        func = call.func
        leaf = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        if leaf != "fault_point" or not call.args:
            return
        site = call.args[0]
        if not (isinstance(site, ast.Constant)
                and isinstance(site.value, str)):
            return
        enclosing = mod.ctx.enclosing_function(call)
        fnode = self.cg.by_node.get(id(enclosing)) \
            if enclosing is not None else None
        quals = (fnode.qualname,) if fnode is not None else ()
        escapes = self._boundary_escapes(quals)
        verdict = "propagates" if "FaultInjected" in escapes \
            else "absorbed"
        self._add_boundary(
            kind="fault-site", name=site.value, path=mod.ctx.rel_path,
            line=call.lineno, quals=quals, escapes=escapes,
            verdict=verdict)

    # ------------------------------------------------------------- VMT137
    def _check_thread_escapes(self) -> None:
        seen: Set[Tuple[str, str]] = set()
        for b in self.boundaries:
            if b["kind"] != "thread" or b["verdict"] != "escapes":
                continue
            key = (b["path"], b["name"])
            if key in seen:
                continue
            seen.add(key)
            names = sorted(b["escapes"])
            shown = ", ".join(f"`{n}`" for n in names[:3])
            if len(names) > 3:
                shown += f" (+{len(names) - 3} more)"
            self.thread_findings.append({
                "path": b["path"], "line": b["line"], "col": 0,
                "message": (
                    f"thread `{b['name']}` entry "
                    f"{' / '.join(b['entries']) or '<target>'} lets "
                    f"{shown} escape — an escaping exception kills the "
                    f"thread silently; run the loop body under "
                    f"`obs.crash_guard(...)` so the death is recorded "
                    f"and `/healthz` turns unready"),
                "flows": [list(b["escapes"][n]) for n in names[:3]],
            })

    # ------------------------------------------------------------- VMT138
    def _breaker_call_sites(self) -> Iterator[Tuple[object, ast.Call]]:
        for mod in sorted(self.project.modules.values(),
                          key=lambda m: m.name):
            if not _is_library(mod.ctx.rel_path):
                continue
            for node in self._walk(mod.ctx.tree):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "call" \
                        and any(kw.arg == "breaker"
                                and not (isinstance(kw.value,
                                                    ast.Constant)
                                         and kw.value.value is None)
                                for kw in node.keywords):
                    yield mod, node

    def _kw_types(self, mod, call: ast.Call, name: str,
                  default: Optional[Tuple[str, ...]]
                  ) -> Optional[Tuple[str, ...]]:
        for kw in call.keywords:
            if kw.arg == name:
                return tuple(self._type_names(mod, kw.value, 0)) or None
        return default

    def _find_try(self, items: list, line: int):
        for it in items:
            if it[0] == "try":
                if it[1] == line:
                    return it
                for sub in (it[2], it[4], it[5]):
                    found = self._find_try(sub, line)
                    if found is not None:
                        return found
                for _t, hbody, _l, _b in it[3]:
                    found = self._find_try(hbody, line)
                    if found is not None:
                        return found
            elif it[0] == "guard":
                found = self._find_try(it[1], line)
                if found is not None:
                    return found
        return None

    def _check_breaker_blind(self) -> None:
        # (a) RetryPolicy.call(..., breaker=...) sites: no_retry classes
        # re-raise without recording by construction, and callee escapes
        # outside retry_on are never seen by the recording clause.
        for mod, call in self._breaker_call_sites():
            ctx = mod.ctx
            site = None
            for kw in call.keywords:
                if kw.arg == "site" and isinstance(kw.value,
                                                   ast.Constant):
                    site = str(kw.value.value)
            label = site or f"{ctx.rel_path}:{call.lineno}"
            retry_on = self._kw_types(mod, call, "retry_on",
                                      ("Exception",))
            no_retry = self._kw_types(mod, call, "no_retry", ()) or ()
            blind: Dict[str, tuple] = {}
            for t in no_retry:
                blind[t] = (_witness(
                    ctx.rel_path, call.lineno,
                    f"`no_retry` re-raises `{t}` without recording a "
                    f"breaker failure"),)
            enclosing = ctx.enclosing_function(call)
            fnode = self.cg.by_node.get(id(enclosing)) \
                if enclosing is not None else None
            if fnode is not None and call.args:
                callee = ast.Call(func=call.args[0], args=[],
                                  keywords=[])
                for cq in self._call_candidates(
                        fnode, ast.copy_location(callee, call)):
                    for n, chain in self.escapes(cq).items():
                        if not self._caught(n, retry_on):
                            self._merge(blind, n, chain)
            self._breaker_boundary(ctx.rel_path, call.lineno, label,
                                   blind)
        # (b) manual regions: preflight() followed by a try whose
        # recording handlers (calling record_failure) define what the
        # breaker observes.
        for qual in sorted(self.frames):
            fn = self.cg.functions[qual]
            pre_lines = [
                n.lineno for n in self.cg.own_call_nodes(fn)
                if isinstance(n.func, ast.Attribute)
                and n.func.attr == "preflight"]
            if not pre_lines:
                continue
            ctx = fn.module.ctx
            trys = [n for n in self.cg._own_nodes(fn.node)
                    if isinstance(n, ast.Try)
                    and n.lineno >= min(pre_lines)]
            label = f"{self._display(qual)}"
            # Parameter-typed clauses (``except retry_on``/``no_retry``
            # inside the policy engine itself) are dynamic — the types
            # only exist at the call site, which pass (a) analyzes.
            params = {a.arg for a in fn.node.args.args} | {
                a.arg for a in fn.node.args.kwonlyargs}

            def is_dynamic(h) -> bool:
                exprs = h.type.elts if isinstance(h.type, ast.Tuple) \
                    else [h.type]
                return any(isinstance(e, ast.Name) and e.id in params
                           for e in exprs if e is not None)

            if not trys:
                blind = self.escapes(qual)
                if blind:
                    self._breaker_boundary(
                        ctx.rel_path, min(pre_lines), label, blind,
                        note="no recording clause after preflight")
                else:
                    self._breaker_boundary(
                        ctx.rel_path, min(pre_lines), label, {})
                continue
            for t in trys:
                if any(is_dynamic(h) for h in t.handlers
                       if h.type is not None):
                    self._add_boundary(
                        kind="breaker", name=label,
                        path=ctx.rel_path, line=t.lineno,
                        escapes={}, verdict="dynamic")
                    continue
                recording: List[str] = []
                for h in t.handlers:
                    if any(isinstance(n, ast.Attribute)
                           and n.attr == "record_failure"
                           for n in ast.walk(h)):
                        types = self._handler_type_names(
                            fn.module, h.type)
                        if types is None:
                            recording = list(_BROAD)
                            break
                        recording.extend(types)
                frame_try = self._find_try(self.frames[qual][0],
                                           t.lineno)
                try_out: Dict[str, tuple] = {}
                if frame_try is not None:
                    self._eval_items(qual, [frame_try], {}, try_out)
                rec_types = tuple(recording)  # () = nothing observed
                blind = {
                    n: c for n, c in try_out.items()
                    if n not in _EXIT_EXCS
                    and not self._caught(n, rec_types)}
                self._breaker_boundary(ctx.rel_path, t.lineno, label,
                                       blind)

    def _breaker_boundary(self, path: str, line: int, label: str,
                          blind: Dict[str, tuple],
                          note: str = "") -> None:
        verdict = "blind" if blind else "observed"
        self._add_boundary(
            kind="breaker", name=label, path=path, line=line,
            escapes=blind, verdict=verdict)
        if not blind:
            return
        names = sorted(blind)
        shown = ", ".join(f"`{n}`" for n in names[:3])
        extra = f" ({note})" if note else ""
        self.breaker_findings.append({
            "path": path, "line": line, "col": 0,
            "message": (
                f"breaker region `{label}` lets {shown} escape without "
                f"recording a failure{extra} — the breaker never trips "
                f"on this class, so a deterministic fault loops at "
                f"full request rate"),
            "flows": [list(blind[n]) for n in names[:3]],
        })

    # ------------------------------------------------------------- VMT139
    def _check_handler_shadows(self) -> None:
        pf = proto_flow(self.project)
        for qual in sorted(pf.summaries):
            info = pf.summaries[qual]
            if not info.acquire_calls:
                continue
            fn = self.cg.functions[qual]
            ctx = fn.module.ctx
            acquire_lines = sorted(a[2] for a in info.acquire_calls)
            terminal_lines = self._terminal_lines(pf, fn)
            for node in self.cg._own_nodes(fn.node):
                if not isinstance(node, ast.Try):
                    continue
                for h in node.handlers:
                    types = self._handler_type_names(fn.module, h.type)
                    broad = types is None or any(
                        t in _BROAD for t in types)
                    if not broad:
                        continue
                    if any(isinstance(n, ast.Raise)
                           for n in ast.walk(h)):
                        continue
                    if self._handler_reaches_terminal(pf, fn, h):
                        continue
                    owing = [
                        a for a in acquire_lines if a < h.lineno
                        and not any(a < t < node.lineno
                                    for t in terminal_lines)]
                    if not owing:
                        continue
                    self.shadow_findings.append({
                        "path": ctx.rel_path, "line": h.lineno,
                        "col": h.col_offset,
                        "message": (
                            f"broad `except` in "
                            f"`{self._display(qual)}` swallows the "
                            f"exception while the handle acquired at "
                            f"line {owing[0]} still owes a terminal — "
                            f"the claim leaks until the visibility "
                            f"sweep; reach `ack`/`nack`/`release` (or "
                            f"`_fail_job`) inside the handler or "
                            f"re-raise"),
                    })

    def _terminal_lines(self, pf, fn) -> List[int]:
        lines: List[int] = []
        for call in self.cg.own_call_nodes(fn):
            func = call.func
            leaf = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else "")
            if leaf and pf.registry.terminal_protocol(leaf) is not None:
                lines.append(call.lineno)
                continue
            cq = pf._resolve_call(fn, call)
            if cq is not None:
                csum = pf.summaries.get(cq)
                if csum is not None and csum.terminal_params:
                    lines.append(call.lineno)
        return lines

    def _handler_reaches_terminal(self, pf, fn, handler) -> bool:
        for node in ast.walk(handler):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            leaf = func.attr if isinstance(func, ast.Attribute) else (
                func.id if isinstance(func, ast.Name) else "")
            if leaf and pf.registry.terminal_protocol(leaf) is not None:
                return True
            cq = pf._resolve_call(fn, node)
            if cq is not None:
                csum = pf.summaries.get(cq)
                if csum is not None and csum.terminal_params:
                    return True
        return False

    # ------------------------------------------------------------- VMT140
    def _check_frame_drift(self) -> None:
        machine = txn_flow(self.project).state_machines.get(
            "jobs", {}).get("status")
        if not machine:
            return
        canonical: Set[str] = {
            v for v in machine.get("values", ()) if v is not None}
        handler_sites: List[Tuple[object, str, ast.AST]] = []
        for mod in sorted(self.project.modules.values(),
                          key=lambda m: m.name):
            ctx = mod.ctx
            if not _is_library(ctx.rel_path):
                continue
            spans = [
                (h.lineno, getattr(h, "end_lineno", h.lineno) or
                 h.lineno)
                for n in self._walk(ctx.tree) if isinstance(n, ast.Try)
                for h in n.handlers]

            def in_handler(node: ast.AST) -> bool:
                return any(a <= node.lineno <= b for a, b in spans)

            for value, node in self._verdict_literals(ctx):
                if in_handler(node):
                    handler_sites.append((mod, value, node))
                else:
                    canonical.add(value)
        vocabulary = sorted(canonical)
        for mod, value, node in handler_sites:
            if value in canonical:
                continue
            hint = difflib.get_close_matches(value, vocabulary, n=1,
                                             cutoff=0.6)
            suggest = f" — did you mean `{hint[0]}`?" if hint else ""
            self.frame_findings.append({
                "path": mod.ctx.rel_path, "line": node.lineno,
                "col": node.col_offset,
                "message": (
                    f"error verdict `{value}` emitted from an "
                    f"exception handler is not in the recovered "
                    f"vocabulary {vocabulary}{suggest} — dashboards "
                    f"keyed on the jobs.status machine will drop this "
                    f"failure class on the floor"),
            })

    def _verdict_literals(self, ctx) -> Iterator[Tuple[str, ast.AST]]:
        """String literals used as an outbound error *verdict*: the 2nd
        positional of ``job_finish``, a ``verdict=`` kwarg, a
        ``"verdict"`` dict value, or a ``verdict`` assignment."""

        def consts(expr: ast.AST) -> Iterator[ast.Constant]:
            if isinstance(expr, ast.Constant) \
                    and isinstance(expr.value, str):
                yield expr
            elif isinstance(expr, ast.IfExp):
                yield from consts(expr.body)
                yield from consts(expr.orelse)
            elif isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
                for elt in expr.elts:
                    yield from consts(elt)

        def is_verdict(expr: ast.AST) -> bool:
            return (isinstance(expr, ast.Name)
                    and expr.id == "verdict") \
                or (isinstance(expr, ast.Attribute)
                    and expr.attr == "verdict")

        for node in self._walk(ctx.tree):
            if isinstance(node, ast.Call):
                func = node.func
                leaf = func.attr if isinstance(func, ast.Attribute) \
                    else (func.id if isinstance(func, ast.Name)
                          else "")
                if leaf == "job_finish" and len(node.args) >= 2:
                    for c in consts(node.args[1]):
                        yield c.value, c
                for kw in node.keywords:
                    if kw.arg == "verdict":
                        for c in consts(kw.value):
                            yield c.value, c
            elif isinstance(node, ast.Dict):
                for key, value in zip(node.keys, node.values):
                    if isinstance(key, ast.Constant) \
                            and key.value == "verdict" \
                            and value is not None:
                        for c in consts(value):
                            yield c.value, c
            elif isinstance(node, ast.Assign) \
                    and len(node.targets) == 1 \
                    and is_verdict(node.targets[0]):
                for c in consts(node.value):
                    yield c.value, c


def exc_flow(project) -> ExcFlow:
    flow = getattr(project, "_exc_flow", None)
    if flow is None:
        flow = ExcFlow(project)
        project._exc_flow = flow
    return flow


# ---------------------------------------------------------------------------
# The committed surface
# ---------------------------------------------------------------------------

def _handler_inventory(project) -> List[dict]:
    out: List[dict] = []
    flow = exc_flow(project)
    for mod in sorted(project.modules.values(), key=lambda m: m.name):
        ctx = mod.ctx
        if not _is_library(ctx.rel_path):
            continue
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Try):
                continue
            for h in node.handlers:
                types = flow._handler_type_names(mod, h.type)
                broad = types is None or any(t in _BROAD for t in types)
                reraises = any(isinstance(n, ast.Raise)
                               for n in ast.walk(h))
                out.append({
                    "path": ctx.rel_path,
                    "line": h.lineno,
                    "types": ["*"] if types is None else sorted(types),
                    "broad": broad,
                    "reraises": reraises,
                })
    out.sort(key=lambda h: (h["path"], h["line"]))
    return out


def build_failure_surface(project) -> dict:
    """The failure surface: every boundary with its escaping set and
    verdict, the handler inventory, and the project exception taxonomy.
    Deterministic by construction (sorted everywhere, no timestamps) so
    the rendering is byte-stable."""
    flow = exc_flow(project)
    boundaries = []
    for b in sorted(flow.boundaries,
                    key=lambda b: (b["path"], b["line"], b["kind"],
                                   b["name"])):
        boundaries.append({
            "kind": b["kind"],
            "name": b["name"],
            "path": b["path"],
            "line": b["line"],
            "entries": b["entries"],
            "daemon": b["daemon"],
            "guard": b["guard"],
            "escapes": {n: list(chain)
                        for n, chain in sorted(b["escapes"].items())},
            "verdict": b["verdict"],
        })
    handlers = _handler_inventory(project)
    exceptions = {
        name: {
            "bases": sorted(info["bases"]),
            "path": info["path"],
            "line": info["line"],
        }
        for name, info in sorted(flow.classes.items())
    }
    surface = {
        "version": EXC_VERSION,
        "generator": "vmtlint exc",
        "boundaries": boundaries,
        "handlers": handlers,
        "exceptions": exceptions,
        "counts": {
            "boundaries": len(boundaries),
            "escaping_boundaries": sum(
                1 for b in boundaries
                if b["verdict"] in ("escapes", "blind")),
            "guarded_boundaries": sum(
                1 for b in boundaries if b["guard"]),
            "handlers": len(handlers),
            "broad_handlers": sum(1 for h in handlers if h["broad"]),
            "exception_classes": len(exceptions),
            "functions_analyzed": len(flow.frames),
        },
    }
    return surface


def render_failure_surface(surface: dict) -> str:
    return json.dumps(surface, indent=2, sort_keys=True) + "\n"


def diff_failure_surface(committed: Optional[dict], fresh: dict
                         ) -> List[str]:
    """Human-readable drift between the committed manifest and a fresh
    build — empty when they agree."""
    if committed is None:
        return [f"{MANIFEST_NAME} missing — run `vmtlint exc` and "
                f"commit it"]
    msgs: List[str] = []
    if committed.get("version") != fresh.get("version"):
        msgs.append(f"manifest version drifted: committed "
                    f"{committed.get('version')!r}, tree expects "
                    f"{fresh.get('version')!r}")
        return msgs

    def bkey(b: dict) -> Tuple[str, str, str]:
        return (b["kind"], b["name"], b["path"])

    cb = {bkey(b): b for b in committed.get("boundaries", [])}
    fb = {bkey(b): b for b in fresh.get("boundaries", [])}
    for key in sorted(set(cb) | set(fb)):
        kind, name, path = key
        label = f"{kind} boundary `{name}` ({path})"
        if key not in cb:
            msgs.append(f"{label} is new in the tree")
            continue
        if key not in fb:
            msgs.append(f"{label} is gone from the tree")
            continue
        if cb[key]["verdict"] != fb[key]["verdict"]:
            msgs.append(f"{label} verdict drifted: "
                        f"{cb[key]['verdict']!r} -> "
                        f"{fb[key]['verdict']!r}")
        cset = sorted(cb[key].get("escapes", {}))
        fset = sorted(fb[key].get("escapes", {}))
        if cset != fset:
            msgs.append(f"{label} escape set drifted: "
                        f"{cset} -> {fset}")
    cexc = set(committed.get("exceptions", {}))
    fexc = set(fresh.get("exceptions", {}))
    for name in sorted(fexc - cexc):
        msgs.append(f"exception class `{name}` is new in the tree")
    for name in sorted(cexc - fexc):
        msgs.append(f"exception class `{name}` is gone from the tree")
    if not msgs and committed != fresh:
        msgs.append("manifest metadata drifted (witness lines moved?)")
    return msgs


# ---------------------------------------------------------------------------
# SARIF rendering
# ---------------------------------------------------------------------------

def _sarif_loc(w: dict) -> dict:
    return {
        "physicalLocation": {
            "artifactLocation": {"uri": w["path"]},
            "region": {"startLine": max(1, int(w.get("line", 1)))},
        },
        "message": {"text": w.get("message", "")},
    }


def _sarif_flow(steps: List[dict]) -> dict:
    return {"threadFlows": [{
        "locations": [{"location": _sarif_loc(s)} for s in steps],
    }]}


def render_failure_surface_sarif(surface: dict) -> str:
    """The surface as SARIF results: one per boundary, warning level
    when the verdict says something escapes, with the raise→escape
    witness chains as codeFlows."""
    results: List[dict] = []
    for b in surface.get("boundaries", []):
        escaping = b["verdict"] in ("escapes", "blind")
        names = sorted(b.get("escapes", {}))
        shown = ", ".join(names) or "nothing"
        result = {
            "ruleId": "EXC-BOUNDARY",
            "level": "warning" if escaping else "note",
            "message": {"text": (
                f"{b['kind']} boundary `{b['name']}` "
                f"[{b['verdict']}]: escaping {shown}")},
            "locations": [_sarif_loc({
                "path": b["path"], "line": b["line"],
                "message": f"{b['kind']} boundary `{b['name']}`"})],
        }
        flows = [_sarif_flow(b["escapes"][n])
                 for n in names if b["escapes"][n]]
        if flows:
            result["codeFlows"] = flows
        results.append(result)
    doc = {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0.json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "vmtlint-exc",
                "informationUri": "",
                "rules": [
                    {"id": "EXC-BOUNDARY",
                     "shortDescription": {
                         "text": "exception-flow boundary"}},
                ],
            }},
            "results": results,
        }],
    }
    return json.dumps(doc, indent=2, sort_keys=True) + "\n"
