"""The vmtlint ruleset: this codebase's real failure modes, as AST checks.

Every rule here traces back to a measured incident or advisor finding:
VMT101 is the round-2 1GB-per-forward host transfer, VMT104 is the
`serve_soak.py` negative-latency timestamp bug, VMT107 is the silent
worker-loop swallow class, etc. Rules are deliberately narrow — a lint
that cries wolf gets disabled; one that encodes the repo's actual
post-mortems gets kept.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from vilbert_multitask_tpu.analysis.context import (
    ModuleContext,
    _is_static_expr,
    _literal_int_tuple,
    is_literal,
    static_names_in,
)
from vilbert_multitask_tpu.analysis.core import Finding, Rule

# --------------------------------------------------------------------- 101
HOST_TRANSFER_CALLS = {
    "jax.device_get": "fetches device buffers to host",
    "numpy.asarray": "materializes a host array from a traced value",
    "numpy.array": "materializes a host array from a traced value",
}
HOST_TRANSFER_METHODS = {"item", "tolist"}
HOST_SCALAR_BUILTINS = {"float", "int", "bool"}


class HostTransferInJit(Rule):
    """np.*/.item()/float()/device_get reachable inside a jit boundary.

    Inside a traced function these either fail at trace time or — worse —
    silently execute per call on concrete inputs, re-shipping host bytes
    every forward (the round-2 23.7 s p50). numpy calls whose args are all
    literals are allowed: they fold to compile-time constants.
    """

    id = "VMT101"
    name = "host-transfer-in-jit"
    severity = "error"
    description = ("host-transfer call (np.asarray/np.array/.item()/"
                   ".tolist()/float()/jax.device_get) inside a "
                   "jit/pjit-compiled function, or in a helper reached "
                   "from one through the call graph")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        seen: Set[int] = set()
        # Lexical jit bodies first, then helpers the project call graph
        # proves reachable from some jit body (possibly in another
        # module) — those inherit traced context wholesale.
        sources: List[Tuple] = [(info, None) for info in ctx.jit_bodies]
        if ctx.project is not None:
            sources += ctx.project.traced_helpers(ctx)
        for info, witness in sources:
            body = info.body
            # Trace-time-static names (static_argnames/nums params, shape
            # tuple unpacks): host math on them is a compile-time constant
            # — the kernel idiom ``scale=1/float(np.sqrt(D))`` is fine.
            static = static_names_in(info)
            scope = body.body if isinstance(body.body, list) else [body.body]
            for stmt in scope:
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call) or id(node) in seen:
                        continue
                    seen.add(id(node))
                    f = self._check_call(ctx, node, static)
                    if f is not None:
                        if witness:
                            f.message += (f" [in a helper reached from "
                                          f"{witness}]")
                        yield f

    def _check_call(self, ctx: ModuleContext, call: ast.Call,
                    static: Set[str]) -> Optional[Finding]:
        resolved = ctx.resolve(call.func)
        args_static = all(_is_static_expr(a, static) for a in call.args)
        if resolved in HOST_TRANSFER_CALLS:
            return self.finding(
                ctx, call, f"`{resolved}` inside a jitted function "
                f"{HOST_TRANSFER_CALLS[resolved]} — every call pays a "
                f"device→host→device round trip; use jnp or hoist out of "
                f"the jit boundary")
        if resolved.startswith("numpy.") and not args_static:
            return self.finding(
                ctx, call, f"`{resolved}` on a non-static value inside a "
                f"jitted function runs on host per call (tracer leak or "
                f"silent host transfer); use the jax.numpy equivalent")
        if (isinstance(call.func, ast.Attribute)
                and call.func.attr in HOST_TRANSFER_METHODS):
            return self.finding(
                ctx, call, f"`.{call.func.attr}()` inside a jitted function "
                f"forces a host transfer per call; return the array and "
                f"convert outside the jit")
        if (isinstance(call.func, ast.Name)
                and call.func.id in HOST_SCALAR_BUILTINS
                and call.args and not args_static):
            return self.finding(
                ctx, call, f"`{call.func.id}()` on a traced value inside a "
                f"jitted function forces a concrete host scalar "
                f"(ConcretizationError at best, per-call sync at worst)")
        return None


# --------------------------------------------------------------------- 102
class RecompileTrigger(Rule):
    """jit cache defeats: a fresh jitted callable per loop iteration, or an
    unhashable literal passed as a static argument.

    ``jax.jit(f)`` keys its compile cache on the wrapped callable's
    identity — building it inside a loop recompiles every iteration.
    A list/dict/set passed for a ``static_argnums`` slot raises
    "unhashable static arguments" at call time.
    """

    id = "VMT102"
    name = "recompile-trigger"
    severity = "error"
    description = ("jax.jit created inside a loop, or an unhashable "
                   "literal passed as a static argument")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if (isinstance(node, ast.Call) and ctx.is_jit_entry(node.func)
                    and ctx.in_loop(node, stop_at_function=False)):
                yield self.finding(
                    ctx, node, "jax.jit inside a loop builds a fresh "
                    "callable each iteration — the compile cache keys on "
                    "callable identity, so every iteration recompiles; "
                    "hoist the jitted function out of the loop")
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    if (ctx.is_jit_entry(deco)
                            and ctx.in_loop(node, stop_at_function=False)):
                        yield self.finding(
                            ctx, node, f"jit-decorated `{node.name}` is "
                            f"defined inside a loop — each iteration "
                            f"creates and compiles a new callable")
        yield from self._unhashable_statics(ctx)
        yield from self._jit_in_traced_helper(ctx)

    def _jit_in_traced_helper(self, ctx: ModuleContext) -> Iterator[Finding]:
        """A helper reached from a jit body that builds a fresh jitted
        callable: the inner callable is recreated every outer trace, so
        its compile cache never hits."""
        if ctx.project is None:
            return
        for info, witness in ctx.project.traced_helpers(ctx):
            for node in ast.walk(info.body):
                if (isinstance(node, ast.Call)
                        and ctx.is_jit_entry(node.func)
                        and not ctx.in_loop(node, stop_at_function=False)):
                    yield self.finding(
                        ctx, node, f"jax.jit built inside "
                        f"`{getattr(info.body, 'name', '<lambda>')}`, "
                        f"which is reached from {witness} — the callable "
                        f"(and its compile cache entry) is recreated on "
                        f"every call; hoist the jitted function out")

    def _unhashable_statics(self, ctx: ModuleContext) -> Iterator[Finding]:
        # static positions per locally-jitted name, from the jit call site.
        static_pos: Dict[str, Tuple[int, ...]] = {}
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and ctx.is_jit_entry(node.func)):
                continue
            for kw in node.keywords:
                if kw.arg != "static_argnums":
                    continue
                pos = _literal_int_tuple(kw.value)
                parent = ctx.parent(node)
                if pos and isinstance(parent, ast.Assign):
                    for t in parent.targets:
                        if isinstance(t, ast.Name):
                            static_pos[t.id] = pos
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in static_pos):
                continue
            for i in static_pos[node.func.id]:
                if i < len(node.args) and isinstance(
                        node.args[i], (ast.List, ast.Dict, ast.Set)):
                    yield self.finding(
                        ctx, node.args[i],
                        f"unhashable literal passed to static arg {i} of "
                        f"jitted `{node.func.id}` — static argument values "
                        f"must be hashable (use a tuple)")


# --------------------------------------------------------------------- 103
class DonatedBufferReuse(Rule):
    """Reading a buffer after passing it to a donate_argnums call.

    Donation hands the input's device memory to XLA for the output; the
    Python reference still exists but the buffer is deleted — touching it
    raises, or on some backends silently reads garbage. The common shape:
    ``loss = step(state, batch)`` in a loop without rebinding ``state``.
    """

    id = "VMT103"
    name = "donated-buffer-reuse"
    severity = "error"
    description = ("variable used again after being passed in a "
                   "donate_argnums position")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        # Names that donate when called: lexically-jitted bindings in this
        # module, widened by the project graph to imported jitted
        # functions and wrappers whose params are transitively donated
        # (donated-buffer escape across call edges).
        donors: Dict[str, Tuple[int, ...]] = {}
        if ctx.project is not None:
            donors.update(ctx.project.local_donors(ctx))
        donors.update(ctx.jit_bound_names)
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_block(ctx, node.body, donors)

    def _donating_calls(self, ctx: ModuleContext, stmt: ast.stmt,
                        donors: Dict[str, Tuple[int, ...]]
                        ) -> Iterator[Tuple[ast.Call, List[str]]]:
        for node in ast.walk(stmt):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)):
                continue
            donate = donors.get(node.func.id)
            if not donate:
                continue
            names = [node.args[i].id for i in donate
                     if i < len(node.args)
                     and isinstance(node.args[i], ast.Name)]
            if names:
                yield node, names

    @staticmethod
    def _bound_names(stmt: ast.stmt) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and isinstance(
                    node.ctx, (ast.Store, ast.Del)):
                out.add(node.id)
        return out

    def _check_block(self, ctx: ModuleContext, block: List[ast.stmt],
                     donors: Dict[str, Tuple[int, ...]]
                     ) -> Iterator[Finding]:
        donated: Dict[str, int] = {}  # name -> line it was donated on
        for stmt in block:
            # Reads happen before this statement's own (re)bindings.
            for node in ast.walk(stmt):
                if (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)
                        and node.id in donated):
                    yield self.finding(
                        ctx, node, f"`{node.id}` was donated to a "
                        f"donate_argnums call on line {donated[node.id]}; "
                        f"its device buffer no longer exists — rebind the "
                        f"result or drop the donation")
                    donated.pop(node.id)
            for call, names in self._donating_calls(ctx, stmt, donors):
                for n in names:
                    donated[n] = call.lineno
            for n in self._bound_names(stmt):
                donated.pop(n, None)
            # Loop bodies: a donation inside whose name is never rebound in
            # the body is read again by the call itself next iteration.
            if isinstance(stmt, (ast.For, ast.While)):
                rebound = set()
                for inner in stmt.body:
                    rebound |= self._bound_names(inner)
                for inner in stmt.body:
                    for call, names in self._donating_calls(ctx, inner,
                                                            donors):
                        for n in names:
                            if n not in rebound:
                                yield self.finding(
                                    ctx, call, f"`{n}` is donated inside "
                                    f"this loop but never rebound in the "
                                    f"loop body — the next iteration reads "
                                    f"a deleted buffer; assign the call's "
                                    f"result back to `{n}`")


# --------------------------------------------------------------------- 104
BLOCKING_CALLS = {"jax.block_until_ready", "jax.device_get",
                  "jax.effects_barrier"}
# Calls that enqueue async device work. Deliberately a list, not "jax.*":
# jax.devices()/default_backend()/config.update() etc. are host-side and
# blocking — flagging a timed backend-init span would be a false positive.
_DISPATCH_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.nn.", "jax.random.",
                      "jax.scipy.")
_DISPATCH_CALLS = {"jax.device_put"}
_SUBMIT_NAME_RE = re.compile(r"(submit|start|begin|t_?0|sent)", re.I)
_IO_METHODS = {"getresponse", "recv", "urlopen", "readinto"}


class BenchTimingHazard(Rule):
    """Timing spans that measure the wrong thing.

    (a) a ``time.perf_counter()`` span around async JAX dispatches with no
    ``block_until_ready``/``device_get`` inside the measured region times
    only the dispatch, not the work; (b) a submit/start timestamp captured
    *after* the blocking I/O it claims to measure — the exact
    ``serve_soak.py:148`` bug that produced negative latency samples.
    """

    id = "VMT104"
    name = "bench-timing-hazard"
    severity = "error"
    description = ("perf_counter span around device dispatches without "
                   "block_until_ready, or a submit timestamp captured "
                   "after the measured I/O")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_spans(ctx, node.body)
            if isinstance(node, (ast.For, ast.While)):
                yield from self._check_spans(ctx, node.body)
                yield from self._late_submit_stamp(ctx, node.body)

    # -- (a) unblocked device span ---------------------------------------
    def _is_perf_counter(self, ctx: ModuleContext, node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and ctx.resolve(node.func) in
                ("time.perf_counter", "time.monotonic", "time.time"))

    def _span_ends(self, ctx: ModuleContext, stmt: ast.stmt
                   ) -> Set[str]:
        """Names t for which this statement computes ``perf_counter() - t``."""
        out: Set[str] = set()
        for node in ast.walk(stmt):
            if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
                    and self._is_perf_counter(ctx, node.left)
                    and isinstance(node.right, ast.Name)):
                out.add(node.right.id)
        return out

    def _device_dispatch(self, ctx: ModuleContext, stmt: ast.stmt
                         ) -> Optional[ast.Call]:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.resolve(node.func)
            if (resolved.startswith(_DISPATCH_PREFIXES)
                    or resolved in _DISPATCH_CALLS
                    or ctx.jitted_call_name(node)):
                return node
        return None

    def _has_blocker(self, ctx: ModuleContext, stmt: ast.stmt) -> bool:
        return any(isinstance(n, ast.Call)
                   and ctx.resolve(n.func) in BLOCKING_CALLS
                   for n in ast.walk(stmt))

    def _check_spans(self, ctx: ModuleContext, block: List[ast.stmt]
                     ) -> Iterator[Finding]:
        open_spans: Dict[str, int] = {}  # timer var -> stmt index
        for i, stmt in enumerate(block):
            for t in self._span_ends(ctx, stmt):
                if t not in open_spans:
                    continue
                span = block[open_spans.pop(t):i]
                dispatch = next(
                    (d for s in span
                     if (d := self._device_dispatch(ctx, s)) is not None),
                    None)
                if dispatch is not None and not any(
                        self._has_blocker(ctx, s) for s in span):
                    yield self.finding(
                        ctx, dispatch, "timed region dispatches JAX work "
                        "but never blocks on it — jax dispatch is async, "
                        "so the span measures launch overhead, not "
                        "compute; add jax.block_until_ready(...) inside "
                        "the measured region")
            if (isinstance(stmt, ast.Assign)
                    and self._is_perf_counter(ctx, stmt.value)):
                for target in stmt.targets:
                    if isinstance(target, ast.Name):
                        open_spans[target.id] = i

    # -- (b) submit stamp after the measured I/O -------------------------
    def _late_submit_stamp(self, ctx: ModuleContext, block: List[ast.stmt]
                           ) -> Iterator[Finding]:
        io_seen = False
        for stmt in block:
            if not io_seen:
                io_seen = any(
                    isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in _IO_METHODS
                    for n in ast.walk(stmt))
                continue
            if not isinstance(stmt, ast.Assign):
                continue
            for node in ast.walk(stmt):
                if not self._is_perf_counter(ctx, node):
                    continue
                for target in stmt.targets:
                    base = target
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    if (isinstance(base, ast.Name)
                            and _SUBMIT_NAME_RE.search(base.id)):
                        yield self.finding(
                            ctx, stmt, f"submit/start timestamp "
                            f"`{base.id}` is captured AFTER blocking I/O "
                            f"in this loop — the measured span excludes "
                            f"the request and can go negative; capture "
                            f"the timestamp before the I/O call")


# --------------------------------------------------------------------- 105
class StrayPrint(Rule):
    """print/jax.debug.print/breakpoint left in library code.

    Serving and training hot paths log through ``logging`` or structured
    stderr writes; a bare print in library code is debug debris (and
    ``jax.debug.print`` inside a jit inserts a host callback into the
    compiled program). CLI entrypoints (``main``/``__main__`` blocks) and
    prints with an explicit ``file=`` are the user interface — exempt.
    """

    id = "VMT105"
    name = "stray-print"
    severity = "warning"
    description = "bare print()/jax.debug.print/breakpoint() in library code"
    library_only = True

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.resolve(node.func)
            if resolved == "breakpoint":
                yield self.finding(ctx, node,
                                   "breakpoint() left in library code")
            elif resolved == "jax.debug.print":
                yield self.finding(
                    ctx, node, "jax.debug.print in library code — inside "
                    "a jit this compiles a host callback into the "
                    "program; remove before shipping")
            elif (resolved == "print" and not ctx.in_main_block(node)
                    and not any(kw.arg == "file" for kw in node.keywords)):
                yield self.finding(
                    ctx, node, "bare print() in library code — use "
                    "logging (or print(..., file=sys.stderr) for "
                    "deliberate diagnostics)")


# --------------------------------------------------------------------- 106
class SqliteThreadSharing(Rule):
    """A sqlite3 connection stored for cross-call reuse without a lock.

    sqlite connections are not thread-safe; the serve tier runs HTTP,
    worker, and push threads against the same databases. The repo pattern
    is a connection lent for one ``with`` block out of a list that a lock
    guards (obs/sqlitestore.py) — a connection parked on ``self``/module
    scope, or ``check_same_thread=False``, without a ``threading.Lock``
    in the same class is a data race.
    """

    id = "VMT106"
    name = "sqlite-thread-sharing"
    severity = "error"
    description = ("sqlite3.connect result shared across threads without "
                   "a lock")

    @staticmethod
    def _has_lock(cls_node: ast.ClassDef, ctx: ModuleContext) -> bool:
        return any(
            isinstance(n, ast.Call) and ctx.resolve(n.func) in
            ("threading.Lock", "threading.RLock")
            for n in ast.walk(cls_node))

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and ctx.resolve(node.func) == "sqlite3.connect"):
                continue
            cross_thread = any(
                kw.arg == "check_same_thread"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value is False
                for kw in node.keywords)
            parent = ctx.parent(node)
            stored = (isinstance(parent, ast.Assign) and any(
                isinstance(t, ast.Attribute) or (
                    isinstance(t, ast.Name)
                    and ctx.enclosing_function(node) is None)
                for t in parent.targets))
            if not (stored or cross_thread):
                continue
            cls = next((a for a in ctx.ancestors(node)
                        if isinstance(a, ast.ClassDef)), None)
            if cls is not None and self._has_lock(cls, ctx):
                continue
            where = ("with check_same_thread=False" if cross_thread
                     else "on shared state")
            yield self.finding(
                ctx, node, f"sqlite3 connection stored {where} without a "
                f"threading.Lock — sqlite connections are not "
                f"thread-safe; borrow one for the call (the "
                f"obs/sqlitestore.py pattern) or guard every use with a "
                f"lock")


# --------------------------------------------------------------------- 107
class SwallowedException(Rule):
    """``except:``/``except Exception:`` whose body only passes.

    In a worker/queue hot loop this turns a poisoned job or a dying
    backend into silent job loss. Narrow exception types are fine;
    ``__del__``/``__exit__`` teardown (where raising is worse) is exempt.

    CFG-aware since the proto tier landed: a ``pass`` handler whose
    continuation still *does* something — reaches any call or a valued
    return before falling off the function or looping back — is a
    deliberate "degrade and carry on" recovery path, not a swallow. The
    walk stops at the try body's own statements and at the enclosing
    loop's header, so "reaches work" means work *after* the handler, not
    the next iteration's re-attempt. All-``continue`` handlers keep
    firing unconditionally (their continuation is by definition the next
    iteration).
    """

    id = "VMT107"
    name = "swallowed-exception"
    severity = "warning"
    description = "broad except clause that silently discards the error"

    _TEARDOWN = {"__del__", "__exit__", "__aexit__"}

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            broad = node.type is None or ctx.resolve(node.type) in (
                "Exception", "BaseException")
            trivial = all(
                isinstance(s, (ast.Pass, ast.Continue)) for s in node.body)
            if not (broad and trivial):
                continue
            fn = ctx.enclosing_function(node)
            if fn is not None and fn.name in self._TEARDOWN:
                continue
            if all(isinstance(s, ast.Pass) for s in node.body) \
                    and fn is not None \
                    and self._continuation_works(ctx, fn, node):
                continue
            caught = ("bare except" if node.type is None
                      else f"except {ctx.resolve(node.type)}")
            yield self.finding(
                ctx, node, f"{caught} swallows every error with "
                f"`{'pass' if isinstance(node.body[0], ast.Pass) else 'continue'}`"
                f" — in a hot loop this silently drops jobs; catch the "
                f"specific exception or at least log it")

    @staticmethod
    def _continuation_works(ctx: ModuleContext, fn: ast.AST,
                            handler: ast.ExceptHandler) -> bool:
        """True when the path leaving ``handler`` still reaches a call
        or a valued return inside ``fn`` — without re-entering the try
        body or crossing the enclosing loop's header."""
        from vilbert_multitask_tpu.analysis.cfg import (
            build_cfg, iter_event_nodes)
        try:
            cfg = build_cfg(fn)
        except RecursionError:  # pragma: no cover
            return False
        tries = [a for a in ctx.ancestors(handler)
                 if isinstance(a, ast.Try) and handler in a.handlers]
        if not tries:
            return False
        body_ids = {id(n) for stmt in tries[0].body
                    for n in ast.walk(stmt)}
        loop = next((a for a in ctx.ancestors(tries[0])
                     if isinstance(a, (ast.While, ast.For))
                     and ctx.enclosing_function(a) is fn), None)
        loop_head_ids: Set[int] = set()
        if loop is not None:
            if isinstance(loop, ast.While):
                loop_head_ids.add(id(loop.test))
            else:
                loop_head_ids.update((id(loop.iter), id(loop.target)))
        start = next((blk for blk in cfg.blocks
                      if any(e is handler.body[-1] for e in blk.events)),
                     None)
        if start is None:
            return False
        seen = {start.id}
        frontier = [start]
        first = True
        while frontier:
            blk = frontier.pop()
            for event in blk.events:
                if first and blk is start:
                    # Skip events up to and including the handler body.
                    continue
                if id(event) in body_ids or id(event) in loop_head_ids:
                    break
                if isinstance(event, ast.Return) \
                        and event.value is not None:
                    return True
                if any(isinstance(n, ast.Call)
                       for n in iter_event_nodes(event)):
                    return True
            else:
                for succ in blk.succs:
                    if succ.id not in seen:
                        seen.add(succ.id)
                        frontier.append(succ)
            first = False
        return False


# --------------------------------------------------------------------- 108
_NP_CONSTRUCTORS = ("numpy.array", "numpy.zeros", "numpy.ones",
                    "numpy.empty", "numpy.full", "numpy.arange",
                    "numpy.linspace", "numpy.eye")
_MUTATING_METHODS = {"fill", "sort", "put", "resize", "partition",
                     "setfield", "itemset"}


class ModuleLevelNumpyMutation(Rule):
    """Functions mutating module-level numpy arrays in place.

    A module-global ndarray mutated from functions is shared mutable state
    that is invisible to jit tracing (baked in as a constant at trace
    time, stale forever after) and unsafe under the serving threads.
    """

    id = "VMT108"
    name = "module-numpy-mutation"
    severity = "warning"
    description = "in-place mutation of a module-level numpy array"

    def _module_arrays(self, ctx: ModuleContext) -> Set[str]:
        out: Set[str] = set()
        for stmt in ctx.tree.body:
            if not (isinstance(stmt, ast.Assign)
                    and isinstance(stmt.value, ast.Call)):
                continue
            if ctx.resolve(stmt.value.func) in _NP_CONSTRUCTORS:
                out.update(t.id for t in stmt.targets
                           if isinstance(t, ast.Name))
        return out

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        arrays = self._module_arrays(ctx)
        if not arrays:
            return
        for node in ast.walk(ctx.tree):
            if ctx.enclosing_function(node) is None:
                continue
            hit: Optional[str] = None
            if (isinstance(node, (ast.Assign, ast.AugAssign))):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    base = t
                    while isinstance(base, ast.Subscript):
                        base = base.value
                    if isinstance(base, ast.Name) and base.id in arrays \
                            and (isinstance(t, ast.Subscript)
                                 or isinstance(node, ast.AugAssign)):
                        hit = base.id
            elif (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _MUTATING_METHODS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in arrays):
                hit = node.func.value.id
            if hit is not None:
                yield self.finding(
                    ctx, node, f"module-level numpy array `{hit}` is "
                    f"mutated in place — jit traces bake it in as a "
                    f"stale constant and the serving threads race on it; "
                    f"pass state explicitly or make it immutable")


# --------------------------------------------------------------------- 109
class WallClockDuration(Rule):
    """``time.time()`` used in duration arithmetic.

    The wall clock steps under NTP slew/adjustment, so a latency computed
    from it can jump or go negative; monotonic ``time.perf_counter()`` is
    the duration clock everywhere in this repo (the obs tracer refuses
    wall clock entirely). Legitimate wall-clock subtraction exists —
    uptime reporting, deadline math against persisted cross-process
    timestamps — and is suppressed inline with a justification.
    """

    id = "VMT109"
    name = "wallclock-duration"
    severity = "error"
    description = ("time.time() used to compute a duration/latency — "
                   "use monotonic time.perf_counter()")

    def _is_walltime(self, ctx: ModuleContext, node: ast.AST) -> bool:
        return (isinstance(node, ast.Call)
                and ctx.resolve(node.func) == "time.time")

    def _anchors(self, ctx: ModuleContext
                 ) -> Tuple[Set[Tuple[int, str]], Set[str]]:
        """Targets assigned from time.time(): plain names scoped to their
        enclosing function (id(fn) or 0 at module level), attribute
        targets (``self._t0 = time.time()``) module-wide by source text."""
        names: Set[Tuple[int, str]] = set()
        attrs: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Assign)
                    and self._is_walltime(ctx, node.value)):
                continue
            fn = ctx.enclosing_function(node)
            scope = id(fn) if fn is not None else 0
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add((scope, t.id))
                elif isinstance(t, ast.Attribute):
                    attrs.add(ast.unparse(t))
        return names, attrs

    def _matches(self, ctx: ModuleContext, operand: ast.AST, scope: int,
                 names: Set[Tuple[int, str]], attrs: Set[str]) -> bool:
        if self._is_walltime(ctx, operand):
            return True
        if isinstance(operand, ast.Name):
            return (scope, operand.id) in names
        if isinstance(operand, ast.Attribute):
            return ast.unparse(operand) in attrs
        return False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        names, attrs = self._anchors(ctx)
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.BinOp)
                    and isinstance(node.op, ast.Sub)):
                continue
            fn = ctx.enclosing_function(node)
            scope = id(fn) if fn is not None else 0
            if (self._matches(ctx, node.left, scope, names, attrs)
                    or self._matches(ctx, node.right, scope, names, attrs)):
                yield self.finding(
                    ctx, node, "duration computed from the wall clock "
                    "(time.time()) — NTP slew makes it jump or go "
                    "negative; measure spans with the monotonic "
                    "time.perf_counter() (or suppress with a "
                    "justification if this really is calendar math)")


# --------------------------------------------------------------------- 110
_LOCK_CTORS = ("threading.Lock", "threading.RLock", "threading.Condition")
_INIT_METHODS = {"__init__", "__new__", "__post_init__", "__del__"}
# self.field.<method>() calls that mutate the container in place.
_CONTAINER_MUTATORS = {"append", "extend", "insert", "add", "remove",
                       "discard", "pop", "popitem", "clear", "update",
                       "setdefault", "appendleft", "popleft"}


class _ClassLockAnalysis:
    """Per-class lock-discipline facts: which fields the lock guards, and
    which accesses happen outside it."""

    def __init__(self, ctx: ModuleContext, cls: ast.ClassDef):
        self.ctx = ctx
        self.cls = cls
        self.methods: Dict[str, ast.AST] = {
            s.name: s for s in cls.body
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))}
        self.locks: Set[str] = self._find_locks()
        # (field, node, method, lexically_guarded, is_write)
        self.accesses: List[Tuple[str, ast.AST, str, bool, bool]] = []
        self.locked_only: Set[str] = set()
        if self.locks:
            self._collect_accesses()
            self._infer_locked_only()

    def _find_locks(self) -> Set[str]:
        out: Set[str] = set()
        for node in ast.walk(self.cls):
            if (isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and self.ctx.resolve(node.value.func) in _LOCK_CTORS):
                out.update(t.attr for t in node.targets
                           if isinstance(t, ast.Attribute)
                           and isinstance(t.value, ast.Name)
                           and t.value.id == "self")
        return out

    def _lexically_guarded(self, node: ast.AST) -> bool:
        """Inside ``with self.<lock>:`` — stopping at function boundaries,
        because a nested def inside a with-block escapes the lock."""
        for anc in self.ctx.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return False
            if isinstance(anc, (ast.With, ast.AsyncWith)):
                for item in anc.items:
                    e = item.context_expr
                    if (isinstance(e, ast.Attribute)
                            and isinstance(e.value, ast.Name)
                            and e.value.id == "self"
                            and e.attr in self.locks):
                        return True
        return False

    def _is_write(self, node: ast.Attribute) -> bool:
        if isinstance(node.ctx, (ast.Store, ast.Del)):
            return True
        parent = self.ctx.parent(node)
        if (isinstance(parent, ast.Subscript) and parent.value is node
                and isinstance(parent.ctx, (ast.Store, ast.Del))):
            return True  # self.d[k] = v
        if (isinstance(parent, ast.Attribute) and parent.value is node
                and isinstance(parent.ctx, (ast.Store, ast.Del))):
            return True  # self.obj.attr = v
        if (isinstance(parent, ast.Attribute) and parent.value is node
                and parent.attr in _CONTAINER_MUTATORS):
            gp = self.ctx.parent(parent)
            if isinstance(gp, ast.Call) and gp.func is parent:
                return True  # self.d.clear() / self.xs.append(...)
        return False

    def _collect_accesses(self) -> None:
        for mname, method in self.methods.items():
            for node in ast.walk(method):
                if not (isinstance(node, ast.Attribute)
                        and isinstance(node.value, ast.Name)
                        and node.value.id == "self"
                        and node.attr not in self.locks):
                    continue
                self.accesses.append((
                    node.attr, node, mname,
                    self._lexically_guarded(node), self._is_write(node)))

    def _infer_locked_only(self) -> None:
        """Private methods whose every intra-class call site holds the
        lock are themselves lock-guarded (the
        ``Histogram._get_series`` pattern). Fixed point so helpers called
        only from locked helpers qualify. __init__ call sites count as
        guarded — construction is single-threaded."""
        sites: Dict[str, List[Tuple[str, bool]]] = {}
        for mname, method in self.methods.items():
            for node in ast.walk(method):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id == "self"
                        and node.func.attr in self.methods):
                    continue
                sites.setdefault(node.func.attr, []).append(
                    (mname, self._lexically_guarded(node)))
        changed = True
        while changed:
            changed = False
            for m, callers in sites.items():
                if (m in self.locked_only or not m.startswith("_")
                        or m.startswith("__")):
                    continue
                if all(guarded or c in self.locked_only
                       or c in _INIT_METHODS for c, guarded in callers):
                    self.locked_only.add(m)
                    changed = True

    def guarded_fields(self) -> Set[str]:
        """Fields the lock demonstrably protects: written at least once
        under it (lexically or in a locked-only method), outside
        construction. Read-only-under-lock fields don't qualify — that
        pattern is usually immutability, not lock discipline."""
        return {field for field, _n, m, guarded, write in self.accesses
                if write and m not in _INIT_METHODS
                and (guarded or m in self.locked_only)}

    def unguarded_writes(self, guarded: Set[str]
                         ) -> Iterator[Tuple[str, ast.AST, str]]:
        for field, node, m, lex, write in self.accesses:
            if (write and field in guarded and m not in _INIT_METHODS
                    and not lex and m not in self.locked_only):
                yield field, node, m


class LockDisciplineRace(Rule):
    """A lock-guarded field written without the lock in a class that runs
    on threads.

    Per class: infer the guarded-field set (fields written under ``with
    self.<lock>`` or inside methods only ever called with the lock held),
    then flag writes that skip the lock — but only when the project call
    graph shows the class actually executes on a thread (a
    ``Thread(target=...)``, executor ``submit``/``map``, HTTP handler
    verb, or anything call-reachable from one). Unguarded *reads* are not
    flagged: lock-free reads of a generation counter or stats snapshot
    are a deliberate, benign pattern in this codebase.
    """

    id = "VMT110"
    name = "unlocked-shared-field"
    severity = "error"
    description = ("field written without the lock that guards its other "
                   "writes, in a class reachable from a thread entry "
                   "point")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if ctx.project is None:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            info = _ClassLockAnalysis(ctx, node)
            if not info.locks:
                continue
            guarded = info.guarded_fields()
            if not guarded:
                continue
            witness = ctx.project.thread_witness(ctx, node)
            if witness is None:
                continue
            lock = sorted(info.locks)[0]
            for field, acc, method in info.unguarded_writes(guarded):
                yield self.finding(
                    ctx, acc, f"`self.{field}` is written in "
                    f"`{node.name}.{method}` without `self.{lock}`, but "
                    f"its other writes hold the lock; `{node.name}` runs "
                    f"on threads ({witness}) — this is a data race: take "
                    f"the lock here or suppress with a justification")


# --------------------------------------------------------------------- 111
class PartitionSpecAxisMismatch(Rule):
    """PartitionSpec axis name matching no declared mesh axis.

    Collects every mesh axis declared anywhere in the project — string
    constants in ``jax.sharding.Mesh(...)`` axis arguments and in
    ``axis_names`` assignments/defaults/keywords (``parallel/mesh.py``,
    ``config.py``) — then validates the constant-string axes of every
    ``PartitionSpec(...)`` call against that set. A typo'd axis fails at
    runtime only on the multi-host path that actually builds the mesh;
    statically it's just a string comparison. Variable axis arguments are
    skipped; a project declaring no axes is silent.
    """

    id = "VMT111"
    name = "partition-spec-axis"
    severity = "error"
    description = ("PartitionSpec uses an axis name not declared by any "
                   "mesh in the project")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        from vilbert_multitask_tpu.analysis.graph import module_mesh_axes

        declared = (ctx.project.mesh_axes() if ctx.project is not None
                    else module_mesh_axes(ctx))
        if not declared:
            return
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call) and ctx.resolve(node.func)
                    == "jax.sharding.PartitionSpec"):
                continue
            for arg in node.args:
                for const in ast.walk(arg):
                    if (isinstance(const, ast.Constant)
                            and isinstance(const.value, str)
                            and const.value not in declared):
                        yield self.finding(
                            ctx, const, f"PartitionSpec axis "
                            f"`{const.value}` is not declared by any mesh "
                            f"in the project (declared: "
                            f"{', '.join(sorted(declared))}) — a typo'd "
                            f"axis only fails at runtime on the mesh "
                            f"path")


# --------------------------------------------------------------------- 112
class LayeringViolation(Rule):
    """Import that breaks a declared layering contract.

    Contracts live in ``[tool.vmtlint.layers]`` in pyproject.toml as
    ``forbid = ["pkg.models -> pkg.serve", ...]`` — dotted module-prefix
    pairs meaning "modules under the left prefix must not import modules
    under the right". Checked against every import in the module,
    including lazy function-level ones (a lazy import still couples the
    layers at runtime).
    """

    id = "VMT112"
    name = "layering-violation"
    severity = "error"
    description = ("import forbidden by a [tool.vmtlint.layers] contract")

    @staticmethod
    def _under(name: str, prefix: str) -> bool:
        return name == prefix or name.startswith(prefix + ".")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        project = ctx.project
        if project is None or not project.layers:
            return
        mod = project.module(ctx)
        if mod is None:
            return
        seen: Set[Tuple[int, str]] = set()
        for src, dst in project.layers:
            if not self._under(mod.name, src):
                continue
            for imp in mod.imports:
                if not any(self._under(t, dst) for t in imp.targets()):
                    continue
                key = (getattr(imp.node, "lineno", 0), dst)
                if key in seen:
                    continue
                seen.add(key)
                yield self.finding(
                    ctx, imp.node, f"import of `{imp.targets()[-1]}` "
                    f"breaks the layering contract `{src} -> {dst}` "
                    f"declared in [tool.vmtlint.layers] — this layer "
                    f"must not depend on that one")


# --------------------------------------------------------------------- 113
_TRANSFER_EFFECTS = {
    "jax.device_put": "uploads host bytes to the device",
    "jax.device_get": "pulls device buffers back to the host",
    "jax.block_until_ready": "stalls the host on device completion",
}


class PerRowTransferInLoop(Rule):
    """Host<->device transfer inside a Python loop on the engine hot path.

    Each host<->device round trip costs a synchronization (the bench's
    ``roundtrip_ms`` probe times it); a
    transfer issued once PER LOOP ITERATION in code reachable from the
    serving entry points (``run``/``run_many``/``predict``) multiplies
    that by the batch — the exact shape the O(1)-leaf row slab removed
    from the rows path (one fused device_put per forward, index gathers
    for cached rows). Flags both direct ``jax.device_put``/``device_get``/
    ``block_until_ready`` calls and calls to project functions the call
    graph proves perform one transitively, but only inside ``for``/
    ``while`` bodies of hot-path functions (comprehensions are not loops
    here: they are the repo's idiom for building ONE fused transfer).
    Deliberate per-chunk transfers (run_many's pipelined dispatch/drain)
    carry baseline justifications rather than suppressions — the finding
    stays visible as the cost it is.
    """

    id = "VMT113"
    name = "per-row-transfer-in-loop"
    severity = "error"
    description = ("host<->device transfer (direct or through a project "
                   "call) inside a loop in a function reachable from the "
                   "engine serving entry points")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if ctx.project is None:
            return
        mod = ctx.project.module(ctx)
        if mod is None:
            return
        cg = ctx.project.callgraph
        for fn, hot in ctx.project.hot_path_functions(ctx):
            for call in cg.own_call_nodes(fn):
                if not ctx.in_loop(call):
                    continue
                resolved = ctx.resolve(call.func)
                if resolved in _TRANSFER_EFFECTS:
                    yield self.finding(
                        ctx, call, f"`{resolved}` inside a loop on the "
                        f"engine hot path ({hot}) "
                        f"{_TRANSFER_EFFECTS[resolved]} once per iteration "
                        f"— hoist it out, batch the rows into one fused "
                        f"transfer, or keep the data device-resident")
                    continue
                target = cg.resolve_callable(mod, call.func, fn.scope,
                                             fn.cls_scope)
                witness = ctx.project.transfer_witness(target)
                if witness:
                    yield self.finding(
                        ctx, call, f"`{target}` performs a host<->device "
                        f"transfer ({witness}) and is called inside a loop "
                        f"on the engine hot path ({hot}) — each iteration "
                        f"pays a transfer round trip; batch the transfers "
                        f"or justify the pipelining in the baseline")


# --------------------------------------------------------------------- 114
# Substrings that mark a sleep delay as jittered/randomized. ``backoff_s``
# is the blessed helper: resilience.RetryPolicy.backoff_s is full-jitter
# by construction.
_JITTER_MARKERS = ("random", "uniform", "jitter", "expovariate",
                   "backoff_s")


class NakedRetryLoop(Rule):
    """An unbounded retry loop: catch + un-jittered sleep, no attempt cap.

    The exact shape ``resilience.RetryPolicy`` exists to replace (and that
    ``serve/remote.py`` used to hand-roll): ``while True`` around a try/
    except with a constant or deterministic-exponential ``time.sleep`` —
    every process that observed the same failure sleeps the same schedule
    and retries in lockstep (thundering herd), and nothing ever gives up,
    so a dead dependency pins the loop forever. A bounded ``for`` over
    attempts is structurally capped and stays clean; so does any delay
    expression that visibly randomizes (random/uniform/jitter/expovariate
    or the RetryPolicy ``backoff_s`` helper). Poll loops with a real exit
    condition (``while not stop.is_set()``) are not retry loops and are
    never flagged.
    """

    id = "VMT114"
    name = "naked-retry-loop"
    severity = "error"
    description = ("unbounded `while True` loop catching an exception and "
                   "time.sleep-ing a constant/un-jittered delay — retries "
                   "in lockstep forever; use resilience.RetryPolicy "
                   "(bounded attempts + full jitter)")

    @staticmethod
    def _is_unbounded(loop: ast.While) -> bool:
        return (isinstance(loop.test, ast.Constant)
                and bool(loop.test.value))

    def _jittered(self, ctx: ModuleContext, delay: ast.AST) -> bool:
        for node in ast.walk(delay):
            text = ""
            if isinstance(node, (ast.Name, ast.Attribute)):
                text = ctx.resolve(node)
            elif isinstance(node, ast.Call):
                text = ctx.resolve(node.func)
            if text and any(m in text.lower() for m in _JITTER_MARKERS):
                return True
        return False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        for loop in ast.walk(ctx.tree):
            if not (isinstance(loop, ast.While)
                    and self._is_unbounded(loop)):
                continue
            catches = any(
                isinstance(n, ast.ExceptHandler)
                for stmt in loop.body for n in ast.walk(stmt))
            if not catches:
                continue
            for stmt in loop.body:
                for node in ast.walk(stmt):
                    if not (isinstance(node, ast.Call)
                            and ctx.resolve(node.func) == "time.sleep"
                            and node.args):
                        continue
                    # Sleeps inside a NESTED bounded loop belong to that
                    # loop, not this retry loop.
                    owner = next(
                        (a for a in ctx.ancestors(node)
                         if isinstance(a, (ast.For, ast.While))), None)
                    if owner is not loop and not (
                            isinstance(owner, ast.While)
                            and self._is_unbounded(owner)):
                        continue
                    if self._jittered(ctx, node.args[0]):
                        continue
                    yield self.finding(
                        ctx, node, "un-jittered time.sleep in an unbounded "
                        "`while True` retry loop — every worker that saw "
                        "the failure retries on the same schedule, forever; "
                        "use resilience.RetryPolicy.call (bounded attempts, "
                        "full jitter, process retry budget)")


# --------------------------------------------------------------------- 115
# The always-on telemetry planes: modules under these path segments run
# for the life of the serving process, so a buffer that only ever grows
# there is a slow memory leak with a pager attached.
_OBS_PLANE_RE = re.compile(r"(^|[\\/])(obs|serve)[\\/]")
_BUFFER_GROWERS = {"append", "appendleft", "extend", "extendleft", "insert"}
_BUFFER_REMOVERS = {"pop", "popleft", "popitem", "remove", "clear"}


class UnboundedObsBuffer(Rule):
    """A telemetry buffer on the obs/serve planes that only ever grows.

    Every long-lived collector in this repo is bounded by construction —
    histogram reservoirs and trace rings are ``deque(maxlen=...)``, the
    time-series store is a ring, the flight recorder rotates its bundles.
    A module-level or instance list (or a deque built WITHOUT ``maxlen``)
    that functions append to, with no removal/truncation anywhere in the
    module, breaks that contract: it grows for the life of the serving
    process. Growth guarded by a ``len(...)`` check (the reservoir idiom)
    or paired with any ``pop``/``clear``/slice-truncation is bounded and
    stays clean.
    """

    id = "VMT115"
    name = "unbounded-obs-buffer"
    severity = "error"
    description = ("append to a module-level/instance list or maxlen-less "
                   "deque on the obs/serve planes with no removal or "
                   "truncation in the module — the buffer grows for the "
                   "process lifetime; use deque(maxlen=...) or trim it")

    def _is_unbounded_ctor(self, ctx: ModuleContext,
                           value: ast.AST) -> bool:
        """Empty list / list() / deque(...) without a bound."""
        if isinstance(value, ast.List) and not value.elts:
            return True
        if not isinstance(value, ast.Call):
            return False
        resolved = ctx.resolve(value.func)
        if resolved == "list" and not value.args:
            return True
        if resolved.endswith("deque"):
            # deque(iterable, maxlen) — a second positional IS the bound.
            if len(value.args) >= 2:
                return False
            return not any(k.arg == "maxlen" for k in value.keywords)
        return False

    def _candidates(self, ctx: ModuleContext
                    ) -> Tuple[Dict[str, ast.AST], Dict[str, ast.AST]]:
        """Unbounded buffer initializers: module-level names and
        ``self.<attr>`` assignments (attr keyed by name module-wide)."""
        names: Dict[str, ast.AST] = {}
        attrs: Dict[str, ast.AST] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            else:
                continue
            if not self._is_unbounded_ctor(ctx, value):
                continue
            for t in targets:
                if (isinstance(t, ast.Name)
                        and ctx.enclosing_function(node) is None):
                    names[t.id] = node
                elif (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    attrs[t.attr] = node
        return names, attrs

    @staticmethod
    def _base(expr: ast.AST) -> Optional[Tuple[str, str]]:
        """Classify a buffer expression: ("name", x) or ("attr", x)."""
        if isinstance(expr, ast.Name):
            return ("name", expr.id)
        if isinstance(expr, ast.Attribute):
            return ("attr", expr.attr)
        return None

    def _removals(self, ctx: ModuleContext) -> Set[Tuple[str, str]]:
        out: Set[Tuple[str, str]] = set()
        for node in ast.walk(ctx.tree):
            # x.pop()/x.clear()/... and del x[...] both shrink the buffer.
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _BUFFER_REMOVERS):
                key = self._base(node.func.value)
                if key:
                    out.add(key)
            elif isinstance(node, ast.Delete):
                for t in node.targets:
                    if isinstance(t, ast.Subscript):
                        key = self._base(t.value)
                        if key:
                            out.add(key)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    # x[:] = ... overwrites in place; x = x[-n:] truncates.
                    if (isinstance(t, ast.Subscript)
                            and isinstance(t.slice, ast.Slice)):
                        key = self._base(t.value)
                        if key:
                            out.add(key)
                if (isinstance(node.value, ast.Subscript)
                        and isinstance(node.value.slice, ast.Slice)):
                    key = self._base(node.value.value)
                    if key:
                        out.add(key)
        return out

    def _len_guarded(self, ctx: ModuleContext, call: ast.Call,
                     buf_text: str) -> bool:
        """Growth under ``if len(<buf>) < cap:`` is the reservoir idiom."""
        for anc in ctx.ancestors(call):
            if not isinstance(anc, (ast.If, ast.While)):
                continue
            for n in ast.walk(anc.test):
                if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                        and n.func.id == "len" and n.args
                        and ast.unparse(n.args[0]) == buf_text):
                    return True
        return False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if not _OBS_PLANE_RE.search(ctx.rel_path):
            return
        names, attrs = self._candidates(ctx)
        if not names and not attrs:
            return
        removed = self._removals(ctx)
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _BUFFER_GROWERS):
                continue
            key = self._base(node.func.value)
            if key is None or key in removed:
                continue
            kind, name = key
            if kind == "name":
                # Import-time table building is static data, not a leak;
                # only growth from inside a function accretes per event.
                if (name not in names
                        or ctx.enclosing_function(node) is None):
                    continue
            elif name not in attrs:
                continue
            if self._len_guarded(ctx, node, ast.unparse(node.func.value)):
                continue
            where = ("module-level list" if kind == "name"
                     else f"instance buffer `self.{name}`")
            yield self.finding(
                ctx, node, f"`.{node.func.attr}` grows {where} `{name}` "
                f"on the obs/serve plane with no removal or truncation "
                f"anywhere in the module — it accretes for the process "
                f"lifetime; bound it (deque(maxlen=...), rotation, or an "
                f"explicit trim)")


# --------------------------------------------------------------------- 116
# Calls that block the calling thread outright. sqlite3.connect covers the
# serving plane's I/O idiom (every DB op opens a per-call connection, so
# the connect call IS the disk touch); the jax entries pin the thread on
# device round trips (same effects table as VMT113).
_BLOCKING_DIRECT = {
    "time.sleep": "sleeps the thread outright",
    "sqlite3.connect": "performs SQLite disk I/O",
    "jax.device_put": "uploads host bytes to the device",
    "jax.device_get": "pulls device buffers back to the host",
    "jax.block_until_ready": "stalls the host on device completion",
}
# The serving plane only: the engine's deliberate device_put under its
# input-cache lock (slab insert) is the documented exception — serialized
# uploads ARE its contract — so this rule scopes to serve/.
_SCHED_PLANE_RE = re.compile(r"(^|[\\/])serve[\\/]")


class BlockingCallUnderSchedulerLock(Rule):
    """A blocking call reachable while a serving-plane lock is held.

    The continuous-batching scheduler's condvar guards the ready list the
    intake pool and dispatch loop share; the worker's inflight lock sits
    on every claim/finish. A device dispatch, ``device_get``, SQLite open,
    or ``time.sleep`` executed with such a lock held turns that one slow
    call into a convoy: every intake thread and the dispatcher pile up on
    the lock for the duration (the latency anatomy's execute window,
    spent inside a mutex). Reuses VMT110's per-class lock inference —
    calls flagged when lexically inside ``with self.<lock>:`` or in a
    method the fixed point proves only ever runs with the lock held — and
    VMT113's call-graph witnesses for project calls that transfer
    transitively. ``Condition.wait`` stays clean (it releases the lock);
    so does everything outside serve/ (the engine's slab insert
    deliberately serializes uploads under its cache lock).
    """

    id = "VMT116"
    name = "blocking-call-under-scheduler-lock"
    severity = "error"
    description = ("device dispatch, device_get, SQLite I/O, or time.sleep "
                   "reachable while holding a serving-plane lock in a "
                   "threaded class — the lock convoy stalls every sharer "
                   "for the call's duration")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if ctx.project is None or not _SCHED_PLANE_RE.search(ctx.rel_path):
            return
        mod = ctx.project.module(ctx)
        if mod is None:
            return
        cg = ctx.project.callgraph
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            info = _ClassLockAnalysis(ctx, cls)
            if not info.locks:
                continue
            # Single-threaded classes can't convoy — same witness bar as
            # VMT110.
            witness = ctx.project.thread_witness(ctx, cls)
            if witness is None:
                continue
            lock = sorted(info.locks)[0]
            for mname, method in info.methods.items():
                if mname in _INIT_METHODS:
                    continue
                locked_method = mname in info.locked_only
                held = (f"`{cls.name}.{mname}` only ever runs with "
                        f"`self.{lock}` held" if locked_method
                        else f"inside `with self.{lock}:`")
                for call in ast.walk(method):
                    if not isinstance(call, ast.Call):
                        continue
                    # Nested defs escape the lock (they run later, on
                    # whatever thread calls them).
                    if ctx.enclosing_function(call) is not method:
                        continue
                    if not (locked_method
                            or info._lexically_guarded(call)):
                        continue
                    resolved = ctx.resolve(call.func)
                    if resolved in _BLOCKING_DIRECT:
                        yield self.finding(
                            ctx, call, f"`{resolved}` "
                            f"{_BLOCKING_DIRECT[resolved]} while "
                            f"{held}; `{cls.name}` runs on threads "
                            f"({witness}) — every sharer convoys on the "
                            f"lock for the call's duration; move the "
                            f"blocking work outside the critical section")
                        continue
                    fn = cg.by_node.get(id(method))
                    if fn is None:
                        continue
                    target = cg.resolve_callable(mod, call.func, fn.scope,
                                                 fn.cls_scope)
                    tw = ctx.project.transfer_witness(target)
                    if tw:
                        yield self.finding(
                            ctx, call, f"`{target}` performs a "
                            f"host<->device transfer ({tw}) while {held}; "
                            f"`{cls.name}` runs on threads ({witness}) — "
                            f"the device round trip convoys every sharer "
                            f"on the lock; dispatch outside the critical "
                            f"section")


_POOL_MODULE_RE = re.compile(r"(^|[\\/])pool\.py$")


class ReplicaAffinityLeak(Rule):
    """A replica handle captured outside the pool's checkout/checkin seam.

    The ReplicaPool's failover and rolling-swap guarantees rest on one
    invariant: an engine handle leaves the pool ONLY through
    ``checkout()`` and comes back through ``checkin()`` in the same
    dispatch scope. A handle stored on ``self`` or at module level pins
    work to one replica past the seam — the pool drains a replica the
    stored handle keeps using (swap corrupts in-flight work), and a dead
    replica's handle keeps receiving dispatches failover can never see.
    A checkout whose result neither checks back in nor escapes via
    return leaks the inflight slot outright: the replica's admission
    budget never recovers and the pool slowly wedges. Scoped to serve/
    (pool.py itself implements the seam and is exempt).
    """

    id = "VMT117"
    name = "replica-affinity-leak"
    severity = "error"
    description = ("replica handle from pool.checkout() stored on self/"
                   "module scope, or checked out with no checkin() and no "
                   "return of the handle in the same function — the "
                   "handle outlives the checkout/checkin seam, pinning "
                   "work to a replica the pool may drain, swap, or "
                   "declare dead")

    @staticmethod
    def _is_checkout(call: ast.AST) -> bool:
        return (isinstance(call, ast.Call)
                and isinstance(call.func, ast.Attribute)
                and call.func.attr == "checkout")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if not _SCHED_PLANE_RE.search(ctx.rel_path):
            return
        if _POOL_MODULE_RE.search(ctx.rel_path):
            return
        # Module-level captures: `REP = pool.checkout()` pins forever.
        for stmt in ctx.tree.body:
            if isinstance(stmt, (ast.Assign, ast.AnnAssign)):
                value = stmt.value
                if value is not None and any(
                        self._is_checkout(n) for n in ast.walk(value)):
                    yield self.finding(
                        ctx, stmt, "replica handle checked out into module "
                        "scope — it outlives every drain/swap/failover; "
                        "checkout per dispatch and checkin in the same "
                        "function")
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            checkouts = [n for n in ast.walk(fn)
                         if self._is_checkout(n)
                         and ctx.enclosing_function(n) is fn]
            if not checkouts:
                continue
            has_checkin = any(
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "checkin"
                for n in ast.walk(fn))
            # Local names bound to a checkout result (x = pool.checkout()).
            handle_names: Set[str] = set()
            stored: List[ast.AST] = []
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                if not any(self._is_checkout(n)
                           for n in ast.walk(node.value)):
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute):
                        # self.rep = pool.checkout(...) — affinity pinned
                        # on the instance, past the seam.
                        stored.append(node)
                    elif isinstance(tgt, ast.Name):
                        handle_names.add(tgt.id)
            for node in stored:
                yield self.finding(
                    ctx, node, "replica handle stored on an attribute — "
                    "the engine stays pinned after the pool drains, "
                    "swaps, or kills that replica; keep the handle local "
                    "and checkin() in the same function")
            if has_checkin:
                continue
            # No checkin: the function must at least hand the handle back
            # to its caller (a seam-forwarding helper returns it).
            # Only the handle ITSELF escaping counts (`return rep` /
            # `return pool.checkout()`): returning a value computed FROM
            # the handle (`return rep.engine.run(...)`) still strands it.
            returns_handle = any(
                isinstance(n, ast.Return) and n.value is not None
                and (self._is_checkout(n.value)
                     or (isinstance(n.value, ast.Name)
                         and n.value.id in handle_names))
                for n in ast.walk(fn))
            if not returns_handle:
                yield self.finding(
                    ctx, checkouts[0], "checkout() with no checkin() and "
                    "no return of the handle in this function — the "
                    "replica's inflight slot leaks and its breaker never "
                    "hears the outcome; pair every checkout with a "
                    "checkin on both success and failure paths")


# --------------------------------------------------------------------- 118
_QUANT_IMPL_RE = re.compile(r"(^|/)quant\.py$")
_DEQUANT_FUNCS = ("quant.dequantize_tree", "quant.dequantize_leaf")


class DequantOutsideJit(Rule):
    """Host-side dequantization of an int8-quantized param tree.

    The point of ``param_dtype="int8"`` is that weight HBM reads stay one
    byte per element: the jitted forward dequantizes in-program
    (engine/runtime.py ``_apply_heads``) so XLA fuses
    ``values.astype(compute) * scale`` into the consuming matmul and no
    fat copy ever exists. Calling ``quant.dequantize_tree`` /
    ``dequantize_leaf`` — or hand-rolling ``pair["int8"].astype(...)`` —
    OUTSIDE a jit boundary materializes the widened tree eagerly
    (host-side: a full second tree in RAM plus a fat re-upload; eager
    device-side: a standing 4× copy), silently refunding everything int8
    storage bought. quant.py itself (the implementation) is exempt, as is
    any function the jit plane provably or plausibly traces: lexical jit
    bodies, call-graph-traced helpers, and functions whose name is
    referenced inside a jit body of the same module (the bound-alias
    ``engine = self`` closure pattern the call graph cannot resolve).
    """

    id = "VMT118"
    name = "dequant-outside-jit"
    severity = "error"
    description = ("quant.dequantize_tree/dequantize_leaf (or a hand-"
                   "rolled pair['int8'].astype(...)) called outside any "
                   "jit boundary — the widened tree materializes eagerly, "
                   "defeating int8 weight storage; dequantize inside the "
                   "compiled program instead")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if _QUANT_IMPL_RE.search(ctx.rel_path):
            return
        traced: Set[int] = {id(info.body) for info in ctx.jit_bodies}
        if ctx.project is not None:
            traced |= {id(info.body)
                       for info, _ in ctx.project.traced_helpers(ctx)}
        # Names referenced inside any jit body here: methods invoked
        # through a captured self-alias inherit traced context even though
        # the call graph cannot prove it. Generous by design — this rule
        # polices the serve/boot/bench planes, not the forward builders.
        referenced: Set[str] = set()
        for info in ctx.jit_bodies:
            for n in ast.walk(info.body):
                if isinstance(n, ast.Attribute):
                    referenced.add(n.attr)
                elif isinstance(n, ast.Name):
                    referenced.add(n.id)

        def is_traced(node: ast.AST) -> bool:
            for anc in ctx.ancestors(node):
                if id(anc) in traced:
                    return True
                if (isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef))
                        and anc.name in referenced):
                    return True
            return False

        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = ctx.resolve(node.func)
            if resolved.endswith(_DEQUANT_FUNCS):
                if not is_traced(node):
                    yield self.finding(
                        ctx, node, f"`{resolved.rsplit('.', 1)[-1]}` "
                        f"outside any jit boundary widens the whole int8 "
                        f"tree eagerly — a standing fat copy per call; "
                        f"dequantize inside the compiled forward (or wrap "
                        f"the call in jax.jit) so HBM reads stay int8")
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "astype"
                  and isinstance(node.func.value, ast.Subscript)
                  and isinstance(node.func.value.slice, ast.Constant)
                  and node.func.value.slice.value == "int8"):
                if not is_traced(node):
                    yield self.finding(
                        ctx, node, "hand-rolled dequant "
                        "(pair['int8'].astype(...)) outside any jit "
                        "boundary — use quant.dequantize_leaf inside the "
                        "compiled program so the widening fuses into the "
                        "consuming matmul")


# --------------------------------------------------------------------- 122
class ConfigKnobDrift(Rule):
    """ServingConfig/EngineConfig fields vs. what the project actually reads.

    Two drift directions, both real after PRs 5-9 added 40+ knobs: a knob
    declared but never read anywhere (dead weight that silently ignores the
    operator's intent), and an attribute read that matches no declared field
    (a typo that returns AttributeError at runtime — or worse, never runs).
    Reads are recognized by their access spelling: ``*.serving.<knob>`` /
    ``*._serving.<knob>`` for ServingConfig, ``*cfg.engine.<knob>`` for
    EngineConfig — the only idioms the codebase uses.
    """

    id = "VMT122"
    name = "config-knob-drift"
    severity = "warning"
    description = ("ServingConfig/EngineConfig knob declared but never read "
                   "anywhere in the project, or an attribute read matching "
                   "no declared knob (typo detector)")

    _SERVING_BASES = ("serving", "_serving")
    _ENGINE_CLS = "EngineConfig"
    _SERVING_CLS = "ServingConfig"

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # Set by the --changed driver: a subset scan cannot prove a knob is
        # read *nowhere*, so the dead-knob direction is suppressed there.
        self.partial_scan = False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if ctx.project is None:
            return
        audit = _knob_audit(ctx.project)
        if not self.partial_scan:
            for cls_name, field, node, rel in audit["declared"]:
                if rel != ctx.rel_path:
                    continue
                if field in audit["reads"].get(cls_name, set()):
                    continue
                yield self.finding(
                    ctx, node,
                    f"`{cls_name}.{field}` is declared but never read "
                    f"anywhere in the scanned project — a dead knob "
                    f"silently ignores whatever the operator sets it to; "
                    f"wire it up or delete it")
        for rel, node, cls_name, attr in audit["suspect_reads"]:
            if rel != ctx.rel_path:
                continue
            import difflib

            close = difflib.get_close_matches(
                attr, sorted(audit["members"].get(cls_name, ())), n=2)
            hint = f" (did you mean {' or '.join(close)}?)" if close else ""
            yield self.finding(
                ctx, node,
                f"`.{attr}` matches no declared {cls_name} field{hint} — "
                f"a typo here raises AttributeError on the serving path, "
                f"or reads a knob that no longer exists")


def _knob_audit(project) -> Dict:
    """Cross-module knob audit, cached on the ProjectGraph."""
    cached = getattr(project, "_knob_audit", None)
    if cached is not None:
        return cached
    audited = (ConfigKnobDrift._SERVING_CLS, ConfigKnobDrift._ENGINE_CLS)
    declared: List[Tuple[str, str, ast.AST, str]] = []
    members: Dict[str, Set[str]] = {}
    for mod in project.modules.values():
        for node in ast.walk(mod.ctx.tree):
            if not (isinstance(node, ast.ClassDef) and node.name in audited):
                continue
            mem = members.setdefault(node.name, set())
            for stmt in node.body:
                if (isinstance(stmt, ast.AnnAssign)
                        and isinstance(stmt.target, ast.Name)):
                    declared.append((node.name, stmt.target.id, stmt,
                                     mod.ctx.rel_path))
                    mem.add(stmt.target.id)
                elif isinstance(stmt, ast.Assign):
                    for t in stmt.targets:
                        if isinstance(t, ast.Name):
                            declared.append((node.name, t.id, stmt,
                                             mod.ctx.rel_path))
                            mem.add(t.id)
                elif isinstance(stmt, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    mem.add(stmt.name)
    reads: Dict[str, Set[str]] = {}
    suspects: List[Tuple[str, ast.AST, str, str]] = []
    seen_suspects: Set[int] = set()

    def record(mod, node: ast.AST, cls_name: str, attr: str) -> None:
        reads.setdefault(cls_name, set()).add(attr)
        if (members.get(cls_name) and attr not in members[cls_name]
                and not attr.startswith("__")
                and id(node) not in seen_suspects):
            seen_suspects.add(id(node))
            suspects.append((mod.ctx.rel_path, node, cls_name, attr))

    for mod in project.modules.values():
        tree = mod.ctx.tree
        module_aliases = _knob_aliases(tree)
        for scope in ast.walk(tree):
            if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                aliases = dict(module_aliases)
                aliases.update(_knob_aliases(scope))
            elif isinstance(scope, ast.Module):
                aliases = module_aliases
            else:
                continue
            for node in ast.walk(scope):
                if (isinstance(node, ast.Attribute)
                        and isinstance(node.ctx, ast.Load)):
                    cls_name = _knob_base_class(node.value)
                    if cls_name is None and isinstance(node.value, ast.Name):
                        cls_name = aliases.get(node.value.id)
                    if cls_name is not None:
                        record(mod, node, cls_name, node.attr)
                elif (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in ("getattr", "hasattr")
                        and len(node.args) >= 2
                        and isinstance(node.args[1], ast.Constant)
                        and isinstance(node.args[1].value, str)):
                    # getattr(api.serving, "admin_token", None) is a read
                    # too — and has a default, so never a typo suspect.
                    base = node.args[0]
                    cls_name = _knob_value_class(base)
                    if cls_name is None and isinstance(base, ast.Name):
                        cls_name = aliases.get(base.id)
                    if cls_name is not None:
                        reads.setdefault(cls_name, set()).add(
                            node.args[1].value)
    audit = {"declared": declared, "members": members, "reads": reads,
             "suspect_reads": suspects}
    project._knob_audit = audit
    return audit


def _knob_aliases(scope: ast.AST) -> Dict[str, str]:
    """Local names that denote an audited config object in ``scope``:
    annotated parameters (``ecfg: EngineConfig``) and assignment aliases
    (``s = cfg.serving``, ``s = serving or ServingConfig()``)."""
    aliases: Dict[str, str] = {}
    if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
        a = scope.args
        for arg in (list(getattr(a, "posonlyargs", ())) + a.args
                    + a.kwonlyargs):
            cls = _annotation_class(arg.annotation)
            if cls is not None:
                aliases[arg.arg] = cls
        stmts: List[ast.AST] = list(ast.walk(scope))
    else:
        # Module scope: only direct top-level statements — function-local
        # names must not leak into the module alias map.
        stmts = list(getattr(scope, "body", ()))
    for node in stmts:
        if isinstance(node, ast.Assign):
            cls = _knob_value_class(node.value)
            if cls is not None:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        aliases[t.id] = cls
        elif (isinstance(node, ast.AnnAssign)
                and isinstance(node.target, ast.Name)):
            cls = (_annotation_class(node.annotation)
                   or (_knob_value_class(node.value)
                       if node.value is not None else None))
            if cls is not None:
                aliases[node.target.id] = cls
    return aliases


def _annotation_class(ann: Optional[ast.expr]) -> Optional[str]:
    """ServingConfig/EngineConfig named anywhere in a type annotation,
    including ``Optional[...]`` wrappers and string annotations."""
    if ann is None:
        return None
    names = (ConfigKnobDrift._SERVING_CLS, ConfigKnobDrift._ENGINE_CLS)
    if isinstance(ann, ast.Constant) and isinstance(ann.value, str):
        for n in names:
            if n in ann.value:
                return n
        return None
    for node in ast.walk(ann):
        if isinstance(node, ast.Name) and node.id in names:
            return node.id
        if isinstance(node, ast.Attribute) and node.attr in names:
            return node.attr
    return None


def _knob_value_class(value: ast.expr) -> Optional[str]:
    """Which audited config class an rvalue expression denotes, if any."""
    if isinstance(value, ast.BoolOp):
        for v in value.values:
            cls = _knob_value_class(v)
            if cls is not None:
                return cls
        return None
    if isinstance(value, ast.Call):
        f = value.func
        term = (f.id if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else None)
        if term in (ConfigKnobDrift._SERVING_CLS,
                    ConfigKnobDrift._ENGINE_CLS):
            return term
        return None
    if isinstance(value, ast.Attribute):
        if value.attr in ConfigKnobDrift._SERVING_BASES:
            return ConfigKnobDrift._SERVING_CLS
        if value.attr == "engine":
            base = value.value
            iterm = (base.id if isinstance(base, ast.Name)
                     else base.attr if isinstance(base, ast.Attribute)
                     else None)
            if iterm is not None and (iterm == "cfg"
                                      or iterm.endswith("_cfg")):
                return ConfigKnobDrift._ENGINE_CLS
    return None


def _knob_base_class(base: ast.expr) -> Optional[str]:
    """Which audited config class an attribute-access base denotes."""
    if isinstance(base, ast.Name):
        term = base.id
    elif isinstance(base, ast.Attribute):
        term = base.attr
    else:
        return None
    if term in ConfigKnobDrift._SERVING_BASES:
        return ConfigKnobDrift._SERVING_CLS
    if term == "engine" and isinstance(base, ast.Attribute):
        inner = base.value
        iterm = (inner.id if isinstance(inner, ast.Name)
                 else inner.attr if isinstance(inner, ast.Attribute)
                 else None)
        if iterm is not None and (iterm == "cfg" or iterm.endswith("_cfg")):
            return ConfigKnobDrift._ENGINE_CLS
    return None


# --------------------------------------------------------------------- 123
class InstrumentNameDrift(Rule):
    """Registered ``vmt_*`` instruments vs. the names the project reads.

    The VMT122 pattern applied to the metrics namespace. Two drift
    directions: an instrument registered (``REGISTRY.counter("vmt_x")``)
    whose handle is never used and whose name no string ever references —
    dead weight every exposition renders and every fleet flush ships —
    and a name-string read (a snapshot key lookup, a test asserting an
    exposition line) that matches no registered instrument: reads by
    name fail SILENTLY (a missing dict key, an assertion against a line
    that can never exist), so a typo here is a metric that quietly
    flatlines. Exposition suffixes (``_bucket``/``_sum``/``_count``) and
    the Sampler's derived ``*_per_s``-from-``*_total`` rates normalize to
    their base instrument; foreign ``vmt_``-prefixed strings (temp dirs,
    native symbols) are ignored unless they sit within typo distance of a
    real instrument name.
    """

    id = "VMT123"
    name = "instrument-name-drift"
    severity = "warning"
    description = ("vmt_* instrument registered but never written or "
                   "referenced anywhere (dead metric), or a name-string "
                   "read matching no registered instrument (typo detector "
                   "for the metrics namespace)")

    # A suspect read must be at least this SequenceMatcher-close to a real
    # name: genuine typos measure >=0.96, while foreign vmt_ strings
    # (vmt_demo, vmt_xla_cache, native symbols) top out near 0.72.
    _TYPO_CUTOFF = 0.85

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # Set by the --changed driver: a subset scan cannot prove a name
        # is unused *anywhere*, so the dead direction is suppressed there.
        self.partial_scan = False

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if ctx.project is None:
            return
        audit = _instrument_audit(ctx.project)
        registered = audit["registered"]
        if not self.partial_scan:
            for name, sites in sorted(registered.items()):
                if name in audit["alive"]:
                    continue
                for node, rel, kind in sites:
                    if rel != ctx.rel_path:
                        continue
                    yield self.finding(
                        ctx, node,
                        f"{kind} `{name}` is registered but nothing ever "
                        f"writes to it or references it by name — a dead "
                        f"instrument that every exposition still renders; "
                        f"wire an observation to it or delete it")
        import difflib

        for rel, node, token in audit["suspect_reads"]:
            if rel != ctx.rel_path:
                continue
            close = difflib.get_close_matches(
                token, sorted(registered), n=2, cutoff=self._TYPO_CUTOFF)
            if not close:
                continue  # foreign vmt_ string, not the metrics namespace
            yield self.finding(
                ctx, node,
                f"`{token}` matches no registered instrument (did you "
                f"mean {' or '.join(close)}?) — a name-string read fails "
                f"silently: the key is absent, the asserted exposition "
                f"line can never exist")


_INSTRUMENT_KINDS = ("counter", "gauge", "histogram")
_METRIC_TOKEN_RE = re.compile(r"vmt_[a-z0-9_]+")


def _canon_metric(token: str, registered) -> Optional[str]:
    """The base instrument a name-string denotes, or None if unknown.
    Handles Prometheus exposition suffixes and the Sampler's derived
    rate keys (``X_total`` -> ``X_per_s``)."""
    if token in registered:
        return token
    for suf in ("_bucket", "_sum", "_count"):
        if token.endswith(suf) and token[: -len(suf)] in registered:
            return token[: -len(suf)]
    if token.endswith("_per_s"):
        base = token[: -len("_per_s")] + "_total"
        if base in registered:
            return base
    if token.endswith("_"):
        # f-string prefix part (f"vmt_foo_{x}"): dynamic suffix — credit
        # every instrument it could expand to, never a typo suspect.
        for name in registered:
            if name.startswith(token):
                return name
    return None


def _instrument_audit(project) -> Dict:
    """Cross-module instrument audit, cached on the ProjectGraph."""
    cached = getattr(project, "_instrument_audit", None)
    if cached is not None:
        return cached
    # name -> [(registration node, rel_path, kind)]
    registered: Dict[str, List[Tuple[ast.AST, str, str]]] = {}
    # Write/use evidence, gathered per direction below.
    chained: Set[str] = set()            # REGISTRY.counter("x").inc()
    bindings: Dict[str, Set[str]] = {}   # metric name -> bound identifiers
    loaded: Set[str] = set()             # identifiers loaded anywhere
    string_reads: List[Tuple[str, ast.AST, str]] = []  # (rel, node, token)

    for mod in project.modules.values():
        tree = mod.ctx.tree
        reg_calls: Dict[int, str] = {}   # id(Call) -> metric name
        reg_args: Set[int] = set()       # id(Constant) of registration names
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _INSTRUMENT_KINDS
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith("vmt_")):
                name = node.args[0].value
                registered.setdefault(name, []).append(
                    (node, mod.ctx.rel_path, node.func.attr))
                reg_calls[id(node)] = name
                reg_args.add(id(node.args[0]))
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute):
                if id(node.value) in reg_calls:
                    chained.add(reg_calls[id(node.value)])
                if isinstance(node.ctx, ast.Load):
                    loaded.add(node.attr)
            elif isinstance(node, ast.Name) and isinstance(node.ctx,
                                                           ast.Load):
                loaded.add(node.id)
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for alias in node.names:
                    loaded.add(alias.asname or alias.name.split(".")[0])
            elif isinstance(node, ast.Assign) and id(node.value) in reg_calls:
                targets = bindings.setdefault(reg_calls[id(node.value)],
                                              set())
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        targets.add(t.id)
                    elif isinstance(t, ast.Attribute):
                        targets.add(t.attr)
            elif (isinstance(node, ast.AnnAssign) and node.value is not None
                    and id(node.value) in reg_calls
                    and isinstance(node.target, ast.Name)):
                bindings.setdefault(reg_calls[id(node.value)],
                                    set()).add(node.target.id)
            elif (isinstance(node, ast.Constant)
                    and isinstance(node.value, str)
                    and id(node) not in reg_args
                    and "vmt_" in node.value):
                for token in _METRIC_TOKEN_RE.findall(node.value):
                    string_reads.append((mod.ctx.rel_path, node, token))

    alive: Set[str] = set(chained)
    for name, idents in bindings.items():
        # A bound handle counts as used when its identifier is loaded
        # anywhere in the project — local increments, `from obs import
        # SHED_COUNTER`, `self._errors.inc()` all qualify. Identifier-
        # level (not scope-aware) on purpose: generous beats false drift.
        if idents & loaded:
            alive.add(name)
    suspects: List[Tuple[str, ast.AST, str]] = []
    seen: Set[Tuple[int, str]] = set()
    for rel, node, token in string_reads:
        canon = _canon_metric(token, registered)
        if canon is not None:
            alive.add(canon)
        elif (id(node), token) not in seen:
            seen.add((id(node), token))
            suspects.append((rel, node, token))
    audit = {"registered": registered, "alive": alive,
             "suspect_reads": suspects}
    project._instrument_audit = audit
    return audit


# --------------------------------------------------------------------- 136
class ExemplarCardinality(Rule):
    """``observe(..., exemplar_trace_id=...)`` alongside an unbounded-
    origin label value. Exemplars live per label series (one slot per
    bucket per labelset, each holding a value + trace id + timestamp) —
    a label fed from request data or an unconstrained parameter mints a
    new series per distinct value, so the exemplar map grows without
    bound exactly where tail-sampling was supposed to bound retention.
    Label values routed through bucketizers/config knobs/literals are
    bounded and clean — the VMT124 origin lattice, applied to the
    metrics→trace link instead of the compile cache.
    """

    id = "VMT136"
    name = "exemplar-cardinality"
    severity = "error"
    description = ("histogram observe() attaching an exemplar while a "
                   "label value is request/caller-derived — an unbounded "
                   "label universe turns the per-series exemplar slots "
                   "into an unbounded map")

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        from vilbert_multitask_tpu.analysis.shaperules import (
            _module_functions,
            _own_scope,
            _project_knobs,
        )
        from vilbert_multitask_tpu.analysis.shapes import (
            Scalar,
            call_nodes_in,
            flows_from,
            interpret_function,
        )

        knobs = None
        seen: Set[Tuple[int, str]] = set()
        for fn in _module_functions(ctx):
            targets = {
                id(n) for n in _own_scope(fn)
                if isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr == "observe"
                and any(kw.arg == "exemplar_trace_id"
                        for kw in n.keywords)
            }
            if not targets:
                continue
            if knobs is None:
                knobs = _project_knobs(ctx)
            interp = interpret_function(ctx, fn, knobs)
            for event, fact in interp.iter_facts():
                for call in call_nodes_in(event):
                    if id(call) not in targets:
                        continue
                    for kw in call.keywords:
                        if kw.arg in (None, "exemplar_trace_id"):
                            continue
                        key = (id(call), kw.arg)
                        if key in seen:
                            continue
                        val = interp.eval(kw.value, fact)
                        if not (isinstance(val, Scalar)
                                and val.origin in ("param", "data")):
                            continue
                        seen.add(key)
                        f = self.finding(
                            ctx, call,
                            f"label `{kw.arg}` on an exemplar-carrying "
                            f"observe() is {_EX_ORIGIN_DESC[val.origin]} "
                            f"— every distinct value mints a label "
                            f"series with its own exemplar slot; route "
                            f"it through a bounded vocabulary (task "
                            f"registry, config knob, bucketizer) before "
                            f"labelling")
                        f.flows = flows_from(
                            val.witness,
                            (ctx.rel_path, call.lineno,
                             f"flows into label `{kw.arg}` of an "
                             f"exemplar-carrying observe() — a new "
                             f"value here is a new exemplar series"))
                        yield f


_EX_ORIGIN_DESC = {
    "param": "caller-controlled (an unconstrained parameter)",
    "data": "derived from request data (e.g. a payload field)",
}


from vilbert_multitask_tpu.analysis.locks import (  # noqa: E402
    JitClosureCapture, LockOrderInversion, WaitHoldingForeignLock)
from vilbert_multitask_tpu.analysis.shaperules import (  # noqa: E402
    BucketShapeDrift, DtypePromotionLeak, PartitionRankMismatch,
    UnboundedCompileKey)
from vilbert_multitask_tpu.analysis.txnrules import (  # noqa: E402
    MultiWriteNoTxn, NondeterministicClaim, RmwDeferredTxn, SqlSchemaDrift)
from vilbert_multitask_tpu.analysis.protorules import (  # noqa: E402
    FaultPointCoverage, JobTerminalProtocol, ResourceLeakOnException,
    TerminalFrameDrift)
from vilbert_multitask_tpu.analysis.excrules import (  # noqa: E402
    BreakerBlindException, ErrorFrameDrift, HandlerShadowsTerminal,
    ThreadRunLoopEscape)

RULES = [HostTransferInJit, RecompileTrigger, DonatedBufferReuse,
         BenchTimingHazard, StrayPrint, SqliteThreadSharing,
         SwallowedException, ModuleLevelNumpyMutation, WallClockDuration,
         LockDisciplineRace, PartitionSpecAxisMismatch, LayeringViolation,
         PerRowTransferInLoop, NakedRetryLoop, UnboundedObsBuffer,
         BlockingCallUnderSchedulerLock, ReplicaAffinityLeak,
         DequantOutsideJit, LockOrderInversion, WaitHoldingForeignLock,
         JitClosureCapture, ConfigKnobDrift, InstrumentNameDrift,
         UnboundedCompileKey, DtypePromotionLeak, PartitionRankMismatch,
         BucketShapeDrift, RmwDeferredTxn, MultiWriteNoTxn, SqlSchemaDrift,
         NondeterministicClaim, JobTerminalProtocol,
         ResourceLeakOnException, FaultPointCoverage, TerminalFrameDrift,
         ThreadRunLoopEscape, BreakerBlindException,
         HandlerShadowsTerminal, ErrorFrameDrift, ExemplarCardinality]


def default_rules(severity_overrides: Optional[Dict[str, str]] = None,
                  rule_paths: Optional[Dict[str, Sequence[str]]] = None,
                  ) -> List[Rule]:
    """Instantiate the registry, applying per-repo severity overrides and
    per-rule path exclusions (keys may be rule ids or names)."""
    over = {k.lower(): v for k, v in (severity_overrides or {}).items()}
    gates = {k.lower(): v for k, v in (rule_paths or {}).items()}
    return [cls(severity=over.get(cls.id.lower(), over.get(cls.name.lower())),
                not_under=gates.get(cls.id.lower(),
                                    gates.get(cls.name.lower(), ())))
            for cls in RULES]
