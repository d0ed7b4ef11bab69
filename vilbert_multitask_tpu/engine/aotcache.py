"""AOT executable cache: serialized XLA programs keyed by the compile surface.

PR 12 proved the engine's compile-key universe is closed and enumerated it
as ``COMPILE_SURFACE.json`` (family × bucket × param_dtype × fused ×
topology × attn). That turns boot-time compilation from runtime shape
discovery into a mechanical iteration — so the executables themselves can
be built once and persisted next to the checkpoint, the same AOT
discipline JAX serving stacks use::

    jax.jit(fwd).lower(*abstract_args).compile()        # trace once
    serialize_executable.serialize(compiled)            # persist
    serialize_executable.deserialize_and_load(payload)  # every boot after

Cache layout (``root`` = ``EngineConfig.aot_cache_dir``)::

    <root>/<fingerprint_hash>/fingerprint.json
    <root>/<fingerprint_hash>/rows__b8__float32__fused__dp-1.tp1.sp1__plain.aotx

Entry names are the manifest record keys (``analysis/surface.py``
``_record_key`` — the runtime↔manifest contract) with ``/`` mapped to
``__``. The fingerprint directory is what makes stale entries MISS instead
of poisoning: it hashes everything that changes the compiled program but
is not in the record key — jax/jaxlib versions, backend, device kind, the
actual mesh shape, the compile-relevant config sections, and a digest of
this package's source (an executable is a build product of the model code:
an edited layer under an unchanged config must miss too). A new jaxlib, a
resized model or a code change lands in a different directory and
recompiles cleanly; nothing ever deserializes an executable built for a
different world.

Each ``.aotx`` file is one pickle of ``{payload, in_tree, out_tree,
fingerprint, key}`` — the exact triple ``deserialize_and_load`` needs
(PyTreeDefs of dict/tuple/None trees pickle fine). Loads verify the
embedded fingerprint as belt-and-braces over the directory hash; any
read/unpickle/deserialize failure is a clean miss (recompile-and-overwrite
heals it), never a crash — but never a silent one either: every swallowed
failure books ``vmt_aot_cache_failures_total{event}`` (:func:`record_failure`),
which ``chip_smoke.py`` requires to stay at zero.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import pickle
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jaxlib

from vilbert_multitask_tpu import obs

ENTRY_SUFFIX = ".aotx"
ENTRY_FORMAT = 1
FINGERPRINT_BASENAME = "fingerprint.json"

# Engine knobs that never shape a compiled program: filesystem locations
# and boot-orchestration switches. Everything else in EngineConfig (shape
# buckets, dtypes, fused mode, kernel flags, slab sizing) stays in the
# fingerprint — a drifted value must miss.
_NON_COMPILE_ENGINE_KNOBS = frozenset({
    "vocab_path", "labels_root", "aot_cache_dir", "parallel_warmup",
})

_HITS = obs.REGISTRY.counter(
    "vmt_aot_cache_hits",
    "AOT-cache entries deserialized instead of compiled.",
    labelnames=("program",))
_MISSES = obs.REGISTRY.counter(
    "vmt_aot_cache_misses",
    "AOT-cache lookups that fell back to trace+compile.",
    labelnames=("program",))
_DESERIALIZE_MS = obs.REGISTRY.histogram(
    "vmt_aot_cache_deserialize_ms",
    "Executable deserialize+load time per cache hit (ms).")
_COMPILE_MS = obs.REGISTRY.histogram(
    "vmt_aot_cache_compile_ms",
    "lower+compile time per cache miss (ms).")


_FAILURES = obs.REGISTRY.counter(
    "vmt_aot_cache_failures_total",
    "Swallowed AOT-cache failures: store_failed / load_failed (a stale or "
    "corrupt entry) / exec_fallback (a deserialized executable refused "
    "its first call and the jitted forward recompiled).",
    labelnames=("event",))


def record_compile_ms(ms: float) -> None:
    """Book one miss-path lower+compile duration (the compile itself runs
    engine-side, next to the jit machinery, so the runtime calls this)."""
    _COMPILE_MS.observe(ms)


def record_failure(event: str, **detail) -> None:
    """Count one swallowed cache failure and offer it to the flight
    recorder (``aot_cache_<event>``). The counter is what makes the failure
    visible when no recorder is installed — boot runs before
    ``ServeApp.start()`` installs one."""
    _FAILURES.inc(event=event)
    obs.record_event(f"aot_cache_{event}", **detail)


@functools.lru_cache(maxsize=1)
def _source_digest() -> str:
    """Short hash over every ``.py`` file of this package (path + bytes)."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for root, dirs, files in os.walk(pkg):
        dirs.sort()
        for name in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(root, name)
            h.update(os.path.relpath(path, pkg).encode())
            with open(path, "rb") as f:
                h.update(f.read())
    return h.hexdigest()[:16]


def topology_id(mesh_cfg) -> str:
    """The manifest's topology dimension id for a MeshConfig — must match
    ``analysis/surface.py::_topology_dimension`` (``dp-1.tp1.sp1`` for the
    defaults)."""
    return f"dp{mesh_cfg.dp}.tp{mesh_cfg.tp}.sp{mesh_cfg.sp}"


def record_key(family: str, bucket: int, param_dtype: str, fused: bool,
               topology: str, attn: bool) -> str:
    """One manifest record key — the same format as
    ``analysis/surface.py::_record_key`` (the runtime↔manifest contract;
    the cross-check test pins the two together)."""
    return (f"{family}/b{bucket}/{param_dtype}/"
            f"{'fused' if fused else 'perhead'}/{topology}/"
            f"{'attn' if attn else 'plain'}")


def compile_fingerprint(cfg, *, mesh=None, heads: bool = True
                        ) -> Dict[str, Any]:
    """Everything that changes a compiled program but is not in the record
    key. ``mesh`` is the LIVE mesh (or None): the record key's topology
    comes from MeshConfig knobs, but ``dp=-1`` resolves against whatever
    devices exist — the actual device grid must fingerprint. ``heads``
    records whether the engine serves fused head slabs (a head-less tree
    lowers a different input pytree under the same record key)."""
    engine = {k: v for k, v in dataclasses.asdict(cfg.engine).items()
              if k not in _NON_COMPILE_ENGINE_KNOBS}
    dev = jax.devices()[0]
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "source": _source_digest(),
        "backend": jax.default_backend(),
        "device_kind": getattr(dev, "device_kind", "unknown"),
        "mesh": ("none" if mesh is None else
                 "x".join(f"{k}{v}" for k, v in mesh.shape.items())),
        "heads": "slabs" if heads else "none",
        "model": dataclasses.asdict(cfg.model),
        "engine": engine,
        "mesh_cfg": dataclasses.asdict(cfg.mesh),
    }


def fingerprint_hash(fingerprint: Dict[str, Any]) -> str:
    """Stable short hash of the fingerprint — the cache subdirectory
    name."""
    blob = json.dumps(fingerprint, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def entry_filename(key: str) -> str:
    return key.replace("/", "__") + ENTRY_SUFFIX


class AotCache:
    """On-disk executable cache (one instance may be shared by a whole
    replica pool — loads are memoized, so replica 1..n-1 boot from memory).

    Thread-safe: disk reads/writes happen outside the lock; the memo and
    prefetch buffers are guarded. All failures are soft — a cache that
    cannot read or write degrades to plain trace+compile, never takes the
    engine down.
    """

    def __init__(self, root: str, fingerprint: Dict[str, Any], *,
                 mesh=None):
        self.root = os.path.abspath(root)
        self.fingerprint = fingerprint
        # The devices cached programs execute on: the mesh's, or the one
        # default device a mesh-less engine jits for. deserialize_and_load
        # otherwise binds the executable to EVERY local device, and a
        # single-device program then refuses its first call on a multi-
        # device host ("expected N shards").
        self._devices = (list(mesh.devices.flat) if mesh is not None
                         else jax.devices()[:1])
        self._lock = threading.Lock()
        # key → loaded executable: the pool fast path.
        self._loaded: Dict[str, Any] = {}
        # path → raw file bytes, filled by prefetch() while the checkpoint
        # restore runs on another thread (disjoint resources: disk here,
        # network/device there).
        self._prefetched: Dict[str, bytes] = {}

    # ------------------------------------------------------------- layout
    def dir(self) -> str:
        return os.path.join(self.root, fingerprint_hash(self.fingerprint))

    def entry_path(self, key: str) -> str:
        return os.path.join(self.dir(), entry_filename(key))

    # ----------------------------------------------------------- prefetch
    def prefetch(self, keys: Optional[List[str]] = None) -> int:
        """Read entry bytes into memory (pure disk I/O — no jax work), so
        boot can overlap this with the checkpoint restore. ``keys=None``
        prefetches every entry in the current fingerprint directory.
        Returns the number of entries buffered."""
        d = self.dir()
        if keys is not None:
            paths = [self.entry_path(k) for k in keys]
        else:
            try:
                paths = [os.path.join(d, n) for n in sorted(os.listdir(d))
                         if n.endswith(ENTRY_SUFFIX)]
            except OSError:
                return 0
        n = 0
        for p in paths:
            try:
                with open(p, "rb") as f:
                    blob = f.read()
            except OSError:
                continue
            with self._lock:
                self._prefetched[p] = blob
            n += 1
        return n

    # ---------------------------------------------------------- load/store
    def load(self, key: str, *, program: str = ""):
        """Deserialize-and-load one entry; None on any miss (absent, wrong
        fingerprint, unreadable, undeserializable — all clean)."""
        with self._lock:
            if key in self._loaded:
                _HITS.inc(program=program or key.split("/", 1)[0])
                return self._loaded[key]
        path = self.entry_path(key)
        t0 = time.perf_counter()
        loaded = self._load_from_disk(path)
        program = program or key.split("/", 1)[0]
        if loaded is None:
            _MISSES.inc(program=program)
            return None
        _HITS.inc(program=program)
        _DESERIALIZE_MS.observe((time.perf_counter() - t0) * 1e3)
        with self._lock:
            self._loaded[key] = loaded
        return loaded

    def _load_from_disk(self, path: str):
        with self._lock:
            blob = self._prefetched.pop(path, None)
        if blob is None:
            try:
                with open(path, "rb") as f:
                    blob = f.read()
            except OSError:
                return None
        try:
            entry = pickle.loads(blob)
            if entry.get("format") != ENTRY_FORMAT:
                raise ValueError(f"entry format {entry.get('format')!r}")
            if entry.get("fingerprint") != self.fingerprint:
                raise ValueError("fingerprint mismatch")
            from jax.experimental import serialize_executable as se

            return se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"],
                execution_devices=self._devices)
        except Exception as e:  # noqa: BLE001 — stale/corrupt entries are
            # misses by design; the recompile overwrites them.
            record_failure("load_failed", path=path, error=repr(e))
            return None

    def store(self, key: str, compiled) -> bool:
        """Serialize one compiled executable; atomic write (tmp+rename) so
        a crashed boot never leaves a torn entry. Best-effort: serialization
        or IO failures are recorded and swallowed — the engine already holds
        the compiled program it needs."""
        try:
            from jax.experimental import serialize_executable as se

            payload, in_tree, out_tree = se.serialize(compiled)
            entry = {
                "format": ENTRY_FORMAT,
                "key": key,
                "fingerprint": self.fingerprint,
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            }
            blob = pickle.dumps(entry)
            d = self.dir()
            os.makedirs(d, exist_ok=True)
            self._write_fingerprint(d)
            path = self.entry_path(key)
            tmp = f"{path}.tmp.{os.getpid()}.{threading.get_ident()}"
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
            return True
        except Exception as e:  # noqa: BLE001 — cache writes must never
            # fail a boot that already compiled its program.
            record_failure("store_failed", key=key, error=repr(e))
            return False

    def _write_fingerprint(self, d: str) -> None:
        """Human-readable fingerprint next to the entries (debugging aid —
        `why did my cache miss` is answered by diffing two of these)."""
        path = os.path.join(d, FINGERPRINT_BASENAME)
        if os.path.exists(path):
            return
        try:
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(self.fingerprint, f, indent=2, sort_keys=True,
                          default=repr)
            os.replace(tmp, path)
        except OSError:
            pass

    # ------------------------------------------------------- introspection
    def entry_count(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.dir())
                       if n.endswith(ENTRY_SUFFIX))
        except OSError:
            return 0
