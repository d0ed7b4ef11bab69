"""TPU serving runtime: bucketed, jit-compiled ViLBERT inference.

Reference capability: the worker's model-driving core — ``load_vilbert_model``
(reference worker.py:463-539), ``custom_prediction`` (worker.py:388-458) and
``prediction`` (worker.py:248-386) — redesigned around XLA's compilation
model:

- **static shape buckets**: text is always ``max_text_len`` (37), regions
  ``max_regions`` (101), and the image/batch axis is padded to one of
  ``EngineConfig.image_buckets`` — every request hits a program compiled
  once, instead of the reference's shape-per-request dynamic batching
  (worker.py:266-284);
- **repeat-batching stays**: NLVR2 pairs and retrieval candidates score in a
  single forward with the question replicated per image row, mirroring
  worker.py:266-284;
- **bf16 compute** on the MXU; softmaxes run in f32. Params are stored in
  ``EngineConfig.param_dtype`` (f32 default; ``"bfloat16"`` is the serving
  mode that halves every weight read and the boot upload — training keeps
  f32 master copies, the cast happens at init/restore time only);
- **mesh-ready**: pass a ``Mesh`` and params are placed via the partition
  rules in :mod:`..parallel.sharding`; without one, single-device jit;
- **host↔device bytes are per-query latency**, so the single-device
  program reads image rows out of a
  device-resident **row slab** (one (S, Nv, ...) tensor per input kind)
  via a per-call index vector: rows for content-stable store images pin
  in their slab slot after first use (LRU input cache), bucket padding
  reuses the permanent pad slot 0, and features ship in bf16 when the
  engine computes in bf16 — repeat queries upload ~KB of text instead of
  ~MB of features. The compiled forward signature is O(1) in bucket rows
  (params + 3 slab leaves + one packed text/index tree), so per-dispatch
  argument marshalling no longer scales with batch size;
- label maps load once at boot (fixes the per-request pickle reload,
  SURVEY.md §2.4).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from vilbert_multitask_tpu.config import (
    FrameworkConfig,
    TASK_REGISTRY,
    TaskSpec,
)
from vilbert_multitask_tpu.engine import aotcache
from vilbert_multitask_tpu.engine import decode as dec
from vilbert_multitask_tpu.engine.labels import LabelMapStore
from vilbert_multitask_tpu.features.pipeline import (
    GLOBAL_BOX,
    RegionFeatures,
    batch_images,
    clip_regions,
    encode_image,
)
from vilbert_multitask_tpu.features.store import FeatureStore
from vilbert_multitask_tpu.models.heads import (
    SERVING_HEAD_MODULES,
    build_head_slabs,
)
from vilbert_multitask_tpu.models.vilbert import (
    ViLBertForVLTasks,
    ViLBertOutput,
    fused_head_output,
)
from vilbert_multitask_tpu.parallel import sharding as shd
from vilbert_multitask_tpu import quant
from vilbert_multitask_tpu.resilience import (
    CircuitBreaker,
    DeadlineExceeded,
    ReplicaKilled,
)
from vilbert_multitask_tpu.resilience.faults import fault_point
from vilbert_multitask_tpu import assets, obs

# XLA compiles are the dominant "why did THIS request take 4 s" answer;
# the counter makes them visible next to the queue gauges in /metrics.
_COMPILES = obs.REGISTRY.counter(
    "vmt_engine_compiles_total",
    "jit program compilations by program family.",
    labelnames=("program",))
from vilbert_multitask_tpu.text.pipeline import EncodedText, encode_question
from vilbert_multitask_tpu.text.wordpiece import FullTokenizer


def _gather_rows(leaf, rows):
    """``leaf[rows]`` for the rows program's static bucket, as one dynamic
    slice a row, stacked. XLA's gather of two rows or more wants the slab's
    ``features`` in another tiling than the one it is stored in, so every
    such program first re-laid the whole slab (3.19 GB at the served size);
    a dynamic slice reads each row where it lies, which is what XLA already
    emits for a one-row gather. ``tests/test_gated_delta.py`` compiles both
    forms for the chip and looks for the copy."""
    return jnp.stack([
        jax.lax.dynamic_index_in_dim(leaf, rows[i], keepdims=False)
        for i in range(rows.shape[0])])


class _AotProgram:
    """One compiled program behind a manifest record key, resolved lazily.

    The forward builders run under ``_compile_lock`` and must stay cheap —
    that lock is what lets parallel warmup overlap bucket compiles — so
    when the AOT cache is on they install THIS wrapper instead of doing
    any cache or compile work inline. Resolution happens at the first
    call, under a per-program lock (concurrent buckets still resolve in
    parallel): deserialize the cached executable on a hit, or
    ``fwd.lower(*abstract_args).compile()`` on a miss and backfill the
    cache with the serialized result.

    A deserialized executable is proven by its first successful call. If
    that first call fails (an executable serialized against a world the
    fingerprint failed to distinguish), the wrapper permanently falls
    back to the plain jitted forward, counts the recompile and books
    ``vmt_aot_cache_failures_total{event="exec_fallback"}``. After the
    first proven call errors propagate unwrapped — transient device
    failures must reach the breaker, not be masked as cache fallbacks.
    """

    def __init__(self, engine: "InferenceEngine", family: str, bucket: int,
                 attn: bool, fwd, rec_key: str):
        self._engine = engine
        self._family = family
        self._bucket = bucket
        self._attn = attn
        self._fwd = fwd
        self.record_key = rec_key
        self._lock = threading.Lock()
        self._fn = None
        self._proven = False
        self.from_cache = False
        self.fell_back = False

    @property
    def resolved(self) -> bool:
        return self._fn is not None

    def ensure(self, load_only: bool = False) -> Optional[str]:
        """Resolve the callable: ``"hit"`` (deserialized from the cache),
        ``"compiled"`` (traced+compiled, cache backfilled), or None when
        ``load_only`` and the cache missed (nothing compiled — the caller
        decides whether to pay the compile)."""
        with self._lock:
            if self._fn is not None:
                return "hit" if self.from_cache else "compiled"
            eng = self._engine
            t0 = time.perf_counter()
            loaded = eng._aot.load(self.record_key, program=self._family)
            if loaded is not None:
                eng.book_boot_time("cache_load_s",
                                   time.perf_counter() - t0)
                self._fn = loaded
                self.from_cache = True
                return "hit"
            if load_only:
                return None
            t0 = time.perf_counter()
            args = eng._abstract_forward_args(self._family, self._bucket)
            compiled = self._fwd.lower(*args).compile()
            dt = time.perf_counter() - t0
            _COMPILES.inc(program=self._family)
            aotcache.record_compile_ms(dt * 1e3)
            eng.book_boot_time("compile_s", dt)
            eng._aot.store(self.record_key, compiled)
            self._fn = compiled
            return "compiled"

    def __call__(self, *args):
        self.ensure()
        fn = self._fn
        if self._proven:
            return fn(*args)
        try:
            out = fn(*args)
        except Exception as e:  # noqa: BLE001 — only the unproven
            # deserialized-executable case is handled; everything else
            # (including compile errors from ensure's lower) propagates to
            # the dispatch funnel and its breaker.
            if not self.from_cache:
                raise
            aotcache.record_failure("exec_fallback", key=self.record_key,
                                    error=repr(e))
            with self._lock:
                self._fn = self._fwd
                self.from_cache = False
                self.fell_back = True
            _COMPILES.inc(program=self._family)
            out = self._fwd(*args)
        self._proven = True
        return out


@dataclasses.dataclass(frozen=True)
class RowFrame:
    """What :meth:`InferenceEngine.decode` needs of one image row on the
    host, 2 KB: the picture's size (``ImageMeta``: ranking, grounding) and
    the row's normalized boxes (grounding). The engine keeps one beside a
    row's slab slot from the moment the row is inserted, so a request about
    a resident row decodes from the bytes the read path would have made."""

    width: int
    height: int
    spatials: np.ndarray  # (Nv, 5) f32


@dataclasses.dataclass
class PreparedRequest:
    """Host-side buffers for one request, already bucketed.

    ``features`` is stored in the engine's *transfer dtype*: bf16 when the
    engine computes in bf16 (the model's first dense layer casts inputs to
    the compute dtype anyway — see models/embeddings.py ImageEmbeddings — so
    pre-casting on the host is bit-identical and halves the dominant
    host→device payload), f32 otherwise (test/golden-fixture engines).

    Where the intake found rows on the device (``frames``), the three image
    arrays hold the OTHER rows only, in request order and unpadded, and are
    None when there is no other row: read them through :meth:`host_rows`.
    """

    spec: TaskSpec
    n_images: int
    bucket: int
    text: EncodedText  # (bucket, Nt)
    features: Optional[np.ndarray]  # (bucket, Nv, D) transfer dtype
    spatials: Optional[np.ndarray]  # (bucket, Nv, 5) f32
    image_mask: Optional[np.ndarray]  # (bucket, Nv)
    task_ids: np.ndarray  # (bucket, 1)
    images: List[dec.ImageMeta]
    # Stable per-image identities for the device input cache (one string
    # per REAL image row, length n_images), or None for novel uploads /
    # synthetic defaults. Row-level so any bucket size shares entries.
    cache_keys: Optional[List[str]] = None
    # One entry per REAL image row: the frame of a row prepare_from_store
    # found on the device (no host tensors were made for it), None for a
    # row whose tensors this request carries. None: every row is carried.
    frames: Optional[List[Optional[RowFrame]]] = None

    def host_rows(self) -> List[Optional[dict]]:
        """Per real image row its host tensors, or None where the intake
        found the row on the device."""
        frames = self.frames or [None] * self.n_images
        rows: List[Optional[dict]] = []
        at = 0  # the carried rows lie in request order
        for frame in frames:
            if frame is not None:
                rows.append(None)
                continue
            rows.append(dict(features=self.features[at],
                             spatials=self.spatials[at],
                             image_mask=self.image_mask[at]))
            at += 1
        return rows

    @property
    def first_spatials(self) -> np.ndarray:
        """Row 0's normalized boxes (grounding decodes against them)."""
        if self.frames is not None and self.frames[0] is not None:
            return self.frames[0].spatials
        return self.spatials[0]


class _Row(NamedTuple):
    """One image row on its way into a pack."""

    host: Optional[dict]  # features/spatials/image_mask; None: resident
    key: Optional[str]  # device-cache identity; None: scratch slot
    image: Optional[dec.ImageMeta] = None  # size; the path of a late read
    req: Optional[PreparedRequest] = None  # whose row ``index`` this is
    index: int = 0

    def frame(self) -> RowFrame:
        """The frame of a row that carries its tensors (a copy: 2 KB must
        not keep a request's stacked arrays alive)."""
        return RowFrame(self.image.width, self.image.height,
                        np.array(self.host["spatials"], np.float32))


class InferenceEngine:
    """One engine per process: owns params, tokenizer, stores, compile cache."""

    def __init__(
        self,
        cfg: Optional[FrameworkConfig] = None,
        *,
        params=None,
        tokenizer: Optional[FullTokenizer] = None,
        feature_store: Optional[FeatureStore] = None,
        label_store: Optional[LabelMapStore] = None,
        mesh=None,
        seed: int = 0,
        replica_id: Optional[str] = None,
        aot_cache: Optional[aotcache.AotCache] = None,
    ):
        self.cfg = cfg or FrameworkConfig()
        # Replica identity (serve/pool.py): None for standalone engines.
        # Threads through the breaker name, live_stats keys, and forward
        # spans so N same-process replicas stay distinguishable in every
        # telemetry surface.
        self.replica_id = replica_id
        # Flipped by ReplicaPool.kill() (chaos) or by the pool when a
        # health probe declares this replica dead: every subsequent
        # dispatch fails fast with ReplicaKilled so in-flight batches fail
        # over instead of completing against a corpse.
        self.killed = False
        ecfg = self.cfg.engine
        self.compute_dtype = jnp.dtype(ecfg.compute_dtype)
        # Storage dtype of the served param tree (EngineConfig.param_dtype).
        # bf16 halves every weight read at serving shapes — where the MXU is
        # weight-read-bound, that is the roofline (see engine/flops.py) —
        # and halves the one-time boot upload. "int8" halves it again:
        # floating matrix leaves become per-channel {"int8", "scale"} pairs
        # (quant.py) and the jitted forward dequantizes them in-program
        # right before the matmuls, so HBM reads stay int8. Training never
        # sees this: the trainer builds/restores its own f32 master tree.
        self.param_dtype = jnp.dtype(ecfg.param_dtype)
        self.param_quantized = self.param_dtype == jnp.dtype(jnp.int8)
        if not (self.param_quantized
                or jnp.issubdtype(self.param_dtype, jnp.floating)):
            raise ValueError(
                f"engine.param_dtype must be a floating dtype or 'int8', "
                f"got {ecfg.param_dtype!r}")
        # Engine kernel knobs win over the model config, unconditionally —
        # kernel selection must not depend on which config carried a flag.
        model_cfg = dataclasses.replace(
            self.cfg.model,
            use_pallas_coattention=ecfg.use_pallas_coattention,
            use_pallas_self_attention=ecfg.use_pallas_self_attention,
        )
        # Sequence-parallel routing: a mesh with a real "sp" axis
        # (MeshConfig.sp > 1) opts the visual stream into ring attention
        # for buckets at/above ring_min_regions — the long-context path.
        # Demo-scale buckets (≤101 regions) stay dense; the decision is
        # static per compiled bucket (RingContext.engages).
        from vilbert_multitask_tpu.parallel.ring import RingContext

        self._ring_v = RingContext.from_mesh(
            mesh, min_seq=ecfg.ring_min_regions)
        self.model = ViLBertForVLTasks(model_cfg, ring_v=self._ring_v,
                                       kernel_mesh=mesh,
                                       dtype=self.compute_dtype)
        # Default assets: the committed vocab/label files — real file-loading
        # paths (reference worker.py:537-539, 299-315), not in-memory toys.
        self.tokenizer = tokenizer or FullTokenizer.from_vocab_file(
            ecfg.vocab_path or assets.default_vocab_path())
        self._check_vocab_coherence()
        self.feature_store = feature_store
        self.labels = label_store or LabelMapStore(
            root=ecfg.labels_root or assets.default_labels_root(),
            sizes={"vqa": self.cfg.model.num_labels,
                   "gqa": self.cfg.model.gqa_num_labels}
        )
        self.mesh = mesh
        # Boot-phase timing split (restore_s is stamped by the serving
        # layer that owns the checkpoint read; cache_load_s/compile_s
        # accumulate as programs resolve; upload_s below).
        self.boot_times: Dict[str, float] = {}
        self._boot_lock = threading.Lock()
        # Task-id → label-head gather table for the fused decode program
        # (index 1 = the GQA head, 0 = the VQA head): a static python tuple
        # the jitted _fused_bundle embeds as a tiny constant.
        n_tasks = max(TASK_REGISTRY) + 1
        self._gqa_gather = tuple(
            1 if (t in TASK_REGISTRY
                  and TASK_REGISTRY[t].head == "vil_prediction_gqa") else 0
            for t in range(n_tasks))
        # The fused head-slab stacking program, built before the first
        # params publish below (the setter runs it when fused heads are on).
        self._head_slab_builder = self._make_head_slab_builder()
        if params is None:
            # One-time boot transfer: PRNGKey materializes its seed scalar
            # host→device. Explicitly allowed so engine construction stays
            # legal under the tests' jax.transfer_guard("disallow")
            # sanitizer (tests/conftest.py) — this is the only implicit
            # upload on the boot path, and it is intentional.
            with jax.transfer_guard("allow"):
                boot_key = jax.random.PRNGKey(seed)
            params = self.init_params(boot_key)
        t_up = time.perf_counter()
        params = self._place_params(params)
        jax.block_until_ready(params)
        self.params = params
        self.book_boot_time("upload_s", time.perf_counter() - t_up)
        # AOT executable cache (engine/aotcache.py): a shared instance from
        # the serving layer (one per pool, prefetched during restore) wins;
        # otherwise built here from the config knob. Constructed AFTER the
        # params publish so the fingerprint records whether this engine
        # actually serves fused head slabs.
        if aot_cache is not None:
            self._aot: Optional[aotcache.AotCache] = aot_cache
        elif ecfg.aot_cache_dir:
            self._aot = aotcache.AotCache(
                ecfg.aot_cache_dir,
                aotcache.compile_fingerprint(
                    self.cfg, mesh=mesh, heads=self.head_slabs is not None),
                mesh=mesh)
        else:
            self._aot = None
        # keyed ('batched'|'rows', bucket, collect_attention) — see
        # _forward / _forward_rows
        self._compiled: Dict[Tuple[str, int, bool], callable] = {}
        # Guards the _compiled dict itself (parallel warmup threads race
        # check-then-insert in the builders).
        self._compile_lock = threading.Lock()
        # Breaker over the forward funnel (_call_forward): sustained device
        # failures (lost device, OOM loop) fail jobs fast toward the queue's
        # dead-letter path instead of stalling the worker on each one. The
        # threshold is deliberately laxer than the transport breaker's —
        # one-off runtime errors (worst case: one bad request per window)
        # must not poison a shared engine.
        breaker_name = ("engine.forward" if replica_id is None
                        else f"engine.forward.{replica_id}")
        self._breaker = CircuitBreaker(
            name=breaker_name, failure_threshold=8, window_s=60.0,
            reset_timeout_s=15.0)
        # Device input cache: encoded region tensors for content-stable
        # (store-backed) images, pinned in HBM after first use — the input
        # analogue of the one-time param device_put above. Rows live in the
        # row slab (see _row_slab); the cache maps key → slab slot, LRU
        # over EngineConfig.device_input_cache_entries.
        self._input_cache: "OrderedDict[str, int]" = OrderedDict()
        # key → RowFrame, the same key set as _input_cache at every release
        # of the lock: what decode needs of a row whose tensors stayed on
        # the device (prepare_from_store asks here BEFORE it reads a file).
        self._input_frames: Dict[str, RowFrame] = {}
        self._input_cache_lock = threading.Lock()
        self._input_cache_hits = 0
        self._input_cache_misses = 0
        # Row slab state (built lazily under _input_cache_lock): the slab
        # tensors, the free cache-slot pool, the scratch rotor, and the
        # jitted single-row insert program.
        self._slab: Optional[dict] = None
        self._slab_free: List[int] = []
        self._slab_scratch0 = 0
        self._slab_scratch_n = 0
        self._scratch_next = 0
        self._slab_insert_fn = None

    # ----------------------------------------------------- served tree state
    # The served weights publish as ONE attribute write of a (params,
    # head_slabs) pair, so a dispatch can never observe a new tree with the
    # previous tree's fused head slabs (or vice versa) mid-swap.

    @property
    def params(self):
        """The served param tree (published atomically with its fused
        head slabs — see :meth:`load_params`)."""
        return self._served[0]

    @params.setter
    def params(self, tree):
        # Head-less trees (e.g. boot probes with params={}) publish without
        # slabs; decode falls back to the per-head path until a full tree
        # lands.
        build = (self.cfg.engine.fused_task_heads
                 and all(n in tree for n in SERVING_HEAD_MODULES))
        slabs = self._build_head_slabs(tree) if build else None
        self._served = (tree, slabs)

    @property
    def head_slabs(self):
        """Device-resident fused decode-head slabs (models/heads.py:
        build_head_slabs over the served tree; int8 kernel slabs when the
        storage mode is quantized). None when fused_task_heads is off."""
        return self._served[1]

    def _place_params(self, params):
        """Cast/quantize + device-pin a param tree — the ONE placement
        path __init__ and load_params share.

        Device-pinning mirrors the reference's one-time ``model.cuda(0)``
        (worker.py:534-536): without it every jitted forward re-uploads
        ~1 GB of f32 weights host→TPU. Host trees (checkpoint restores, test fixtures)
        cast — or int8-quantize — host-side first, so the upload ships the
        small representation; already-committed device trees (init_params)
        quantize under jit instead, because an eager quantize's scalar
        constants would be implicit transfers (the conftest sanitizer).
        """
        if self.mesh is not None:
            return shd.shard_params(params, self.mesh,
                                    dtype=self.param_dtype)
        host = any(isinstance(x, np.ndarray)
                   for x in jax.tree_util.tree_leaves(params))
        if self.param_quantized and not host:
            return jax.jit(quant.quantize_tree)(params)
        return jax.device_put(shd.cast_floating(params, self.param_dtype))

    def _make_head_slab_builder(self):
        """Jitted head-slab stacker, built once in ``__init__`` (same
        shapes across swaps — load_params stays zero-recompile for the
        forward programs and pays only this tiny stacking program). In
        int8 mode the wide kernel slabs are re-quantized after stacking so
        slab HBM reads stay int8 too; LN scales and biases stay floating —
        they are a rounding error of the byte budget and
        precision-critical.
        """
        mcfg = self.cfg.model
        quantized = self.param_quantized

        def build(tree):
            heads = {n: tree[n] for n in SERVING_HEAD_MODULES}
            if quantized:
                heads = quant.dequantize_tree(heads, jnp.float32)
            slabs = build_head_slabs(heads, mcfg)
            if quantized:
                slabs = {k: (quant.quantize_leaf(v)
                             if k.endswith("kernel") else v)
                         for k, v in slabs.items()}
            return slabs

        return jax.jit(build)

    def _build_head_slabs(self, params):
        """Stack the nine task heads into the fused slab tree, on device
        (:meth:`_make_head_slab_builder`'s compiled program)."""
        slabs = self._head_slab_builder(params)
        jax.block_until_ready(slabs)
        return slabs

    # ------------------------------------------------------------------ init
    def _check_vocab_coherence(self) -> None:
        """Boot-time guard: the loaded vocab must fit the embedding table.

        A vocab larger than ``vocab_size`` would emit token ids that index
        out of the embedding table — on TPU that's a silent gather clamp,
        not an error, so every over-range token would quietly read row
        vocab_size-1. Fail loudly here instead. The inverse gap (table much
        wider than the vocab, e.g. the 30,522-row serving table over the
        committed 1,037-token synthetic vocab) is legal but worth a log
        line: those rows are dead weight until the real vocab is swapped in
        (config.py EngineConfig.vocab_path).
        """
        n_vocab = len(self.tokenizer.vocab)
        n_rows = self.cfg.model.vocab_size
        if n_vocab > n_rows:
            raise ValueError(
                f"vocab file has {n_vocab} tokens but ViLBertConfig."
                f"vocab_size is {n_rows}: token ids would index out of the "
                f"embedding table. Fix vocab_path or vocab_size.")
        if n_rows > 2 * n_vocab:
            import logging

            logging.getLogger(__name__).warning(
                "embedding table has %d rows but the vocab only %d tokens "
                "(%.0f%% dead weight) — expected with the committed "
                "synthetic vocab; swap EngineConfig.vocab_path to the real "
                "bert-base-uncased vocab for score parity",
                n_rows, n_vocab, 100 * (1 - n_vocab / n_rows))

    def _dummy_host(self, batch: int) -> dict:
        """Host-side all-zeros batch in exactly the dtypes prepare() ships."""
        ecfg, mcfg = self.cfg.engine, self.cfg.model
        return dict(
            input_ids=np.zeros((batch, ecfg.max_text_len), np.int32),
            # Same dtype prepare() ships (transfer_dtype): a different input
            # dtype is a different XLA program — warmup must compile the one
            # live requests hit.
            features=np.zeros((batch, ecfg.max_regions, mcfg.v_feature_size),
                              self.transfer_dtype),
            spatials=np.zeros((batch, ecfg.max_regions, 5), np.float32),
            segment_ids=np.zeros((batch, ecfg.max_text_len), np.int32),
            input_mask=np.ones((batch, ecfg.max_text_len), np.int32),
            image_mask=np.ones((batch, ecfg.max_regions), np.int32),
            task_ids=np.zeros((batch, 1), np.int32),
        )

    def _dummy_batch(self, batch: int):
        # One explicit fused upload instead of seven implicit jnp.zeros
        # scalar-fill transfers — keeps warmup legal under
        # jax.transfer_guard("disallow") (the conftest sanitizer fixture).
        return jax.device_put(self._dummy_host(batch))

    def init_params(self, rng):
        """Random init, entirely on device (even batch so the paired NLVR2
        head materializes).

        The whole init runs under one jit so the tree is born on the chip —
        no device→host→device round trip. Params land in
        ``EngineConfig.param_dtype`` (f32 default; bf16 serving mode);
        compute casts to the compute dtype inside the model either way.
        """
        d = self._dummy_batch(2)
        # Init through an XLA-attention twin: the Pallas and XLA paths create
        # the IDENTICAL param tree (they share the projection submodules and
        # differ only in the attention computation), so the one-shot init
        # program need not compile the kernels — warmup() is where Mosaic
        # first sees them, and where a refusal fails the boot.
        init_model = ViLBertForVLTasks(
            dataclasses.replace(
                self.model.config,
                use_pallas_coattention=False,
                use_pallas_self_attention=False),
            dtype=self.compute_dtype)

        # int8 trees quantize at the placement seam (_place_params) — the
        # init jit itself keeps f32 leaves.
        pdt = (self.param_dtype
               if jnp.issubdtype(self.param_dtype, jnp.floating)
               else jnp.dtype(jnp.float32))

        def _init(rng):
            variables = init_model.init(
                rng, d["input_ids"], d["features"], d["spatials"],
                d["segment_ids"], d["input_mask"], d["image_mask"], None,
                d["task_ids"], deterministic=True,
            )
            return jax.tree_util.tree_map(
                lambda x: x.astype(pdt)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                variables["params"],
            )

        return jax.jit(_init)(rng)

    def load_params(self, params) -> None:
        """Hot-swap the served param tree (rolling checkpoint deploy).

        The compiled programs take params as a call argument, not a
        closure (``fwd(params, ...)``), so a same-shape tree swaps in with
        ZERO recompiles: placement/cast mirrors ``__init__``
        (:meth:`_place_params` — shard under a mesh, cast/quantize +
        device-pin otherwise, so an int8 engine RE-QUANTIZES a swapped f32
        checkpoint instead of silently serving it fat) and the publish is
        one attribute write of the (params, head_slabs) pair — an
        in-flight forward finishes against the pair it started with, the
        next dispatch reads the new one.
        """
        params = self._place_params(params)
        # Block BEFORE publishing: a half-uploaded tree must never be
        # observable, and the swap caller's timing should measure the
        # upload, not leak it into the next request's forward.
        jax.block_until_ready(params)
        self.params = params

    # -------------------------------------------------------------- compile
    # Max label-decode fanout (TaskSpec.top_k ≤ 3 for the labels family).
    _TOPK = 3

    @classmethod
    def _decode_bundle(cls, out: ViLBertOutput):
        """Device-side decode prep: softmax/top-k INSIDE the jitted forward.

        Every device→host fetch is a synchronization; pulling the wide
        answer heads (3129/1533 logits per row) after the forward costs a
        transfer per head. Everything each decode family needs is reduced
        on device to a few KB and fetched as ONE pytree (the reference
        pulls whole head tensors, worker.py:287-289).
        """
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        vqa_v, vqa_i = jax.lax.top_k(
            jax.nn.softmax(f32(out.vil_prediction), axis=-1), cls._TOPK)
        gqa_v, gqa_i = jax.lax.top_k(
            jax.nn.softmax(f32(out.vil_prediction_gqa), axis=-1), cls._TOPK)
        return {
            "labels_top": {"vil_prediction": (vqa_v, vqa_i),
                           "vil_prediction_gqa": (gqa_v, gqa_i)},
            "vil_logit": f32(out.vil_logit),
            "vil_tri_prediction": f32(out.vil_tri_prediction),
            "vision_logit": f32(out.vision_logit),
            # The paired NLVR2 head only exists for even batches
            # (models/vilbert.py) — odd buckets never decode "binary".
            **({"vil_binary_prediction": f32(out.vil_binary_prediction)}
               if out.vil_binary_prediction is not None else {}),
        }

    @classmethod
    def _fused_bundle(cls, out: ViLBertOutput, label_logits, task_ids,
                      gqa_gather):
        """Decode bundle for the fused-head program: ONE f32 softmax/top-k
        over the label head GATHERED per row by task id (the in-program
        gather — stacked label logits never leave the device), written
        under BOTH label keys so :meth:`decode` stays family-agnostic.
        Padded label columns sit at heads.PAD_LOGIT_BIAS and underflow to
        probability zero, so top-k matches the per-head softmax."""
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        table = jnp.asarray(gqa_gather, jnp.int32)
        sel = table[jnp.clip(task_ids[:, 0], 0, table.shape[0] - 1)]
        row = jnp.take_along_axis(
            f32(label_logits), sel[:, None, None], axis=1)[:, 0]
        pair = jax.lax.top_k(jax.nn.softmax(row, axis=-1), cls._TOPK)
        return {
            "labels_top": {"vil_prediction": pair,
                           "vil_prediction_gqa": pair},
            "vil_logit": f32(out.vil_logit),
            "vil_tri_prediction": f32(out.vil_tri_prediction),
            "vision_logit": f32(out.vision_logit),
            **({"vil_binary_prediction": f32(out.vil_binary_prediction)}
               if out.vil_binary_prediction is not None else {}),
        }

    def _apply_heads(self, model, params, heads, batch, attn):
        """Shared trace body of the two forward builders: in-program int8
        dequant → trunk or full module apply → per-head or fused-slab
        heads → device-side decode bundle. Runs under jit only."""
        cdt = self.compute_dtype
        if self.param_quantized:
            # The fused values.astype(compute) * scales sits right before
            # each consuming matmul after XLA fusion — weight HBM reads
            # stay int8; only the trainer ever holds fat masters.
            params = quant.dequantize_tree(params, cdt)
        if heads is not None:
            trunk_out = model.apply(
                {"params": params},
                batch["input_ids"], batch["features"], batch["spatials"],
                batch["segment_ids"], batch["input_mask"],
                batch["image_mask"], None, batch["task_ids"],
                deterministic=True, output_all_attention_masks=attn,
                method="trunk",
            )
            slabs = (quant.dequantize_tree(heads, jnp.float32)
                     if self.param_quantized else heads)
            out, label_logits = fused_head_output(
                model.config, slabs, trunk_out, batch["image_mask"], cdt)
            with jax.named_scope("decode_bundle"):
                bundle = self._fused_bundle(
                    out, label_logits, batch["task_ids"], self._gqa_gather)
            return out, bundle
        out = model.apply(
            {"params": params},
            batch["input_ids"], batch["features"], batch["spatials"],
            batch["segment_ids"], batch["input_mask"],
            batch["image_mask"], None, batch["task_ids"],
            deterministic=True, output_all_attention_masks=attn,
            # serving decodes never read the masked-LM/region heads
            compute_pretraining_heads=False,
        )
        with jax.named_scope("decode_bundle"):
            return out, InferenceEngine._decode_bundle(out)

    def _forward(self, bucket: int, collect_attention: bool):
        """Batched-input program (the mesh path: inputs are device_put with
        batch shardings as one (bucket, ...) tree per call). Signature is
        ``fwd(params, heads, batch)`` — ``heads`` is the persistent fused
        head-slab tree (None when fused_task_heads is off)."""
        key = ("batched", bucket, collect_attention)
        with self._compile_lock:
            if key in self._compiled:
                return self._compiled[key]
            model = self.model
            engine = self

            @partial(jax.jit, static_argnames=("attn",))
            def fwd(params, heads, batch, attn=collect_attention):
                return engine._apply_heads(model, params, heads, batch, attn)

            fn = self._aot_resolve("batched", bucket, collect_attention, fwd)
            self._compiled[key] = fn
            return fn

    def _forward_rows(self, bucket: int, collect_attention: bool):
        """Row-slab program (the single-device serving path): image rows
        live in the device-resident slab (:meth:`_row_slab`) and the
        per-call ``pack`` carries the text tensors plus one (bucket,)
        int32 slot-index vector; the (bucket, ...) batch is GATHERED from
        the slab inside the compiled program, a row at a time and in
        place (:func:`_gather_rows`). Rows that are already slab-resident
        (the input cache, the permanent pad slot 0) upload
        nothing. The flattened argument list is params + 3 slab leaves +
        5 pack leaves — constant in bucket size, so per-dispatch argument
        marshalling no longer scales with batch rows. The pack is freshly uploaded every
        call and never referenced again, so it is donated to XLA on
        backends that implement input donation (the slab, persistent
        cross-call state, must never be)."""
        key = ("rows", bucket, collect_attention)
        with self._compile_lock:
            if key in self._compiled:
                return self._compiled[key]
            model = self.model
            engine = self
            donate = (("pack",)
                      if jax.default_backend() in ("tpu", "gpu") else ())

            @partial(jax.jit, static_argnames=("attn",),
                     donate_argnames=donate)
            def fwd(params, heads, slab, pack, attn=collect_attention):
                rows = pack["rows"]
                # Scopes are profile metadata (op_name prefixes): the
                # executable stays ``jit_fwd``.
                with jax.named_scope("slab_gather"):
                    images = {k: _gather_rows(slab[k], rows) for k in
                              ("features", "spatials", "image_mask")}
                batch = dict(
                    input_ids=pack["input_ids"],
                    segment_ids=pack["segment_ids"],
                    input_mask=pack["input_mask"],
                    task_ids=pack["task_ids"],
                    **images,
                )
                return engine._apply_heads(model, params, heads, batch, attn)

            fn = self._aot_resolve("rows", bucket, collect_attention, fwd)
            self._compiled[key] = fn
            return fn

    def _aot_resolve(self, family: str, bucket: int, attn: bool, fwd):
        """What the builders install under their compile key. Without the
        AOT cache: the plain jitted forward, counted as a compile here
        (first call traces+compiles — the pre-cache behavior, unchanged).
        With it: an :class:`_AotProgram` wrapper; the compile counter
        moves to the wrapper's resolution, so ``vmt_engine_compiles_total``
        keeps meaning REAL compiles. Runs under ``_compile_lock`` — no IO,
        no compile, just key formatting."""
        if self._aot is None:
            _COMPILES.inc(program=family)
            return fwd
        ecfg = self.cfg.engine
        rec = aotcache.record_key(
            family, bucket, ecfg.param_dtype, ecfg.fused_task_heads,
            aotcache.topology_id(self.cfg.mesh), attn)
        return _AotProgram(self, family, bucket, attn, fwd, rec)

    def _abstract_forward_args(self, family: str, bucket: int):
        """ShapeDtypeStruct argument trees for ``fwd.lower()`` — exactly
        the live call's shapes/dtypes (and, under a mesh, shardings), so
        the AOT-compiled executable binds to what dispatch actually ships.
        The static ``attn`` argument keeps its closure default, so only
        the array arguments appear here."""
        params, heads = self._served
        if self.mesh is not None:
            def sds(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                            sharding=x.sharding)
        else:
            def sds(x):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
        params_a = jax.tree_util.tree_map(sds, params)
        heads_a = (None if heads is None
                   else jax.tree_util.tree_map(sds, heads))
        if family == "batched":
            host = self._dummy_host(bucket)
            if self.mesh is not None:
                shards = shd.batch_shardings(host, self.mesh)
                batch_a = jax.tree_util.tree_map(
                    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                      sharding=s),
                    host, shards)
            else:
                batch_a = {n: jax.ShapeDtypeStruct(v.shape, v.dtype)
                           for n, v in host.items()}
            return (params_a, heads_a, batch_a)
        # rows: the slab shapes mirror _row_slab, the pack mirrors
        # _run_rows' explicit device_put.
        ecfg, mcfg = self.cfg.engine, self.cfg.model
        n_rows = (1 + ecfg.device_input_cache_entries
                  + ecfg.max_batch_rows())
        nv = ecfg.max_regions
        slab_a = dict(
            features=jax.ShapeDtypeStruct(
                (n_rows, nv, mcfg.v_feature_size), self.transfer_dtype),
            spatials=jax.ShapeDtypeStruct((n_rows, nv, 5), np.float32),
            image_mask=jax.ShapeDtypeStruct((n_rows, nv), np.int32))
        text_shape = (bucket, ecfg.max_text_len)
        pack_a = dict(
            input_ids=jax.ShapeDtypeStruct(text_shape, np.int32),
            segment_ids=jax.ShapeDtypeStruct(text_shape, np.int32),
            input_mask=jax.ShapeDtypeStruct(text_shape, np.int32),
            task_ids=jax.ShapeDtypeStruct((bucket, 1), np.int32),
            rows=jax.ShapeDtypeStruct((bucket,), np.int32))
        return (params_a, heads_a, slab_a, pack_a)

    def book_boot_time(self, phase: str, seconds: float) -> None:
        """Accumulate one boot-phase duration (restore_s / cache_load_s /
        compile_s / upload_s). The serving layer stamps restore_s; the
        engine books the rest. Surfaces in live_stats() → /healthz."""
        with self._boot_lock:
            self.boot_times[phase] = (
                self.boot_times.get(phase, 0.0) + seconds)

    def boot_from_cache(self, buckets: Optional[Sequence[int]] = None
                        ) -> bool:
        """Warm-boot path: install every warmup program from the AOT cache
        WITHOUT compiling anything. True iff every bucket's program
        deserialized — the pool then skips warmup() entirely (executables
        are proven by their first live call; a stale one falls back to the
        jitted forward, see :class:`_AotProgram`). On any miss nothing was
        compiled here — the caller falls back to warmup(), which compiles
        the misses and backfills the cache."""
        if self._aot is None:
            return False
        buckets = list(buckets if buckets is not None
                       else self.cfg.engine.all_row_buckets())
        builder = self._forward if self.mesh is not None \
            else self._forward_rows
        ok = True
        for b in buckets:
            fn = builder(b, False)
            if isinstance(fn, _AotProgram):
                ok = (fn.ensure(load_only=True) is not None) and ok
        return ok

    def aot_compile_record(self, family: str, bucket: int, attn: bool
                           ) -> str:
        """Prewarm one manifest record: ``"hit"`` if already cached, else
        lower+compile+serialize → ``"compiled"`` (the engine.prewarm CLI's
        per-record primitive)."""
        if self._aot is None:
            raise RuntimeError("aot_compile_record needs the AOT cache "
                               "(set EngineConfig.aot_cache_dir)")
        builder = self._forward if family == "batched" \
            else self._forward_rows
        fn = builder(bucket, attn)
        if not isinstance(fn, _AotProgram):
            return "compiled"
        return fn.ensure() or "compiled"

    @property
    def pallas_enabled(self) -> bool:
        """Whether the served model runs the Pallas attention kernels."""
        return (self.model.config.use_pallas_coattention
                or self.model.config.use_pallas_self_attention)

    def _call_forward(self, bucket: int, collect_attention: bool, *args,
                      rows: bool = False):
        """All device forwards funnel through here — resilience gate first.

        ``fault_point("engine.dispatch")`` lets a chaos plan flap/slow the
        device path; the breaker turns SUSTAINED dispatch failures (lost
        device, OOM loop) into fast fails so jobs drain toward dead-letter
        instead of each stalling the worker. There is no kernel fallback:
        a program the compiler refuses raises here with the compiler's
        message (out of warmup(), that fails the boot) — the remedy is
        turning ``EngineConfig.use_pallas_*`` off, explicitly.
        """
        fault_point("engine.dispatch")
        if self.killed:
            raise ReplicaKilled(
                f"engine replica {self.replica_id or '?'} is dead")
        self._breaker.preflight()
        builder = self._forward_rows if rows else self._forward
        # One atomic read of the (params, head_slabs) pair: a concurrent
        # load_params can never hand this dispatch a new tree with the old
        # tree's fused head slabs.
        params, heads = self._served
        try:
            result = builder(bucket, collect_attention)(params, heads, *args)
        except Exception:
            self._breaker.record_failure()
            raise
        self._breaker.record_success()
        return result

    def warmup(self, buckets: Optional[Sequence[int]] = None,
               parallel: Optional[bool] = None) -> None:
        """Pre-compile every shape bucket so first requests pay no compile.

        With ``parallel`` (default from EngineConfig), buckets compile
        concurrently: XLA compilation is C++ and releases the GIL, so the
        full bucket set warms in roughly the longest single compile instead
        of the sum. The first worker exception (a compiler refusal
        included) propagates to the caller.
        """
        # Default set covers everything serving dispatches: the image
        # buckets (run()) AND the throughput buckets (run_many under
        # backlog) — otherwise the first big batch stalls on a mid-serving
        # compile, breaking this method's contract.
        buckets = list(buckets if buckets is not None
                       else self.cfg.engine.all_row_buckets())
        if parallel is None:
            parallel = self.cfg.engine.parallel_warmup

        def _warm_one(b: int) -> None:
            if self.mesh is not None:
                # Match run()'s input shardings exactly — a different input
                # sharding is a different XLA program (fresh compile).
                batch = shd.place_batch(self._dummy_batch(b), self.mesh)
                _, bundle = self._call_forward(b, False, batch)
            else:
                # Warm the slab program run()/run_many() actually use —
                # dummy rows route through the scratch slots, which also
                # warms the slab insert program.
                host = self._dummy_host(b)
                text = {k: host[k] for k in
                        ("input_ids", "segment_ids", "input_mask", "task_ids")}
                rows = [_Row(dict(features=host["features"][i],
                                  spatials=host["spatials"][i],
                                  image_mask=host["image_mask"][i]), None)
                        for i in range(b)]
                _, bundle = self._run_rows(b, False, text, rows)
            jax.block_until_ready(bundle["vil_logit"])

        if parallel and len(buckets) > 1:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=len(buckets)) as pool:
                # list() propagates the first worker exception to the caller.
                list(pool.map(_warm_one, buckets))
        else:
            for b in buckets:
                _warm_one(b)

    # -------------------------------------------------------------- prepare
    def prepare_from_store(
        self, task_id: int, question: str, image_paths: Sequence[str], *,
        resident: Optional[Callable[[Sequence[str]],
                                    List[Optional[RowFrame]]]] = None,
    ) -> PreparedRequest:
        """prepare() from the attached feature store, asking the device
        before the disk. The single place the store→cache-key contract
        lives; serving (_intake) and predict() both come through here.

        Per image, in this order: (1) ``store.identity``: the file is
        resolved and stat'd, nothing is read; (2) residency: does the
        device cache hold a row under that identity (``resident``, by
        default this engine's :meth:`resident_frames`; a pool passes one
        that asks every replica a batch may land on); (3) only a row the
        device does NOT hold is read (``store.fetch``, whose own identity,
        captured before its read, is the key the row is inserted under: the
        cache can never bind a fresh key to stale tensors), clipped,
        encoded and carried to the pack. A resident row costs a ``stat`` and
        a dictionary look-up: no read, no encode, no host tensor; decode
        gets its size and boxes from the frame kept beside the slot. The
        identity is taken anew for every request, so a replaced or edited
        file is a miss and is read. The pack stays the authority
        (:meth:`_pack_rows`): a row that left the device since is read
        there, late.

        Step 2 needs the slab path (no mesh, ``device_input_cache_entries``
        above 0) and a store with ``identity`` and ``fetch``; otherwise every
        row is read as before. Stores without fetch() (minimal test
        doubles) also skip device caching."""
        if self.feature_store is None:
            raise RuntimeError("prepare_from_store() needs a FeatureStore; "
                               "use prepare() with in-memory regions instead")
        store = self.feature_store
        fetch = getattr(store, "fetch", None)
        identity = getattr(store, "identity", None)
        n = len(image_paths)
        frames: List[Optional[RowFrame]] = [None] * n
        cache_keys: Optional[List[Optional[str]]] = None
        with obs.span("engine.features", n_images=n, task_id=task_id) as sp:
            if fetch is None:
                regions = store.get_batch(image_paths)
            else:
                cache_keys = [None] * n
                if (identity is not None and self.mesh is None
                        and self.cfg.engine.device_input_cache_entries > 0):
                    cache_keys = [identity(p) for p in image_paths]
                    frames = (resident or self.resident_frames)(cache_keys)
                regions = [None] * n
                for i, path in enumerate(image_paths):
                    if frames[i] is None:
                        regions[i], cache_keys[i] = fetch(path)
            n_resident = sum(f is not None for f in frames)
            sp.set(resident=n_resident, read=n - n_resident)
        obs.INTAKE_ROWS_RESIDENT.inc(n_resident)
        obs.INTAKE_ROWS_READ.inc(n - n_resident)
        return self.prepare(task_id, question, regions, image_paths,
                            cache_keys=cache_keys,
                            frames=frames if n_resident else None)

    def resident_frames(self, keys: Sequence[str]
                        ) -> List[Optional[RowFrame]]:
        """Per identity, the frame of the row the device cache holds under
        it, else None; one lock hold for a request's keys. A row found is
        moved to the young end of the LRU (what an intake was just promised
        should be the last to go before its pack), but neither hit nor miss
        is counted: the pack counts, once a row."""
        with self._input_cache_lock:
            frames = [self._input_frames.get(k) for k in keys]
            for key, frame in zip(keys, frames):
                if frame is not None:
                    self._input_cache.move_to_end(key)
        return frames

    def _read_late(self, gone: Sequence[_Row]) -> List[_Row]:
        """The pack's late path: rows the intake called resident, read and
        encoded from the store after all, through the steps of
        prepare_from_store's read path. Each comes back carrying its
        tensors under ``fetch``'s own identity, and its request learns the
        key, size and frame of what it is now packed from (the file may
        have been replaced since the intake's ``stat``)."""
        pairs = [self.feature_store.fetch(r.image.path) for r in gone]
        regions, feats, spatials, masks = self._encode_rows(
            [region for region, _ in pairs])
        rows = []
        for i, (old, region, (_, key)) in enumerate(
                zip(gone, regions, pairs)):
            row = old._replace(
                host=dict(features=feats[i], spatials=spatials[i],
                          image_mask=masks[i]),
                key=key, image=dec.ImageMeta(
                    old.image.path, region.image_width, region.image_height))
            req, at = row.req, row.index
            req.images[at], req.cache_keys[at] = row.image, key
            req.frames[at] = row.frame()
            rows.append(row)
        return rows

    def _encode_rows(self, regions: Sequence[RegionFeatures],
                     pad_to: Optional[int] = None):
        """Clip, ``encode_image``, stack (padded to ``pad_to`` rows) and
        cast to the transfer dtype: (clipped regions, features, spatials,
        image mask). Feature files are confidence-ordered (extractor top-K
        order, same as the reference's .npy dumps), so an over-provisioned
        store clips to this engine's region budget instead of erroring."""
        ecfg = self.cfg.engine
        regions = clip_regions(regions, ecfg.max_regions,
                               num_features=ecfg.num_features)
        feats, spatials, image_mask = batch_images(
            [encode_image(r, ecfg.max_regions) for r in regions],
            pad_to=pad_to)
        return (regions, feats.astype(self.transfer_dtype, copy=False),
                spatials, image_mask)

    @property
    def transfer_dtype(self) -> np.dtype:
        """Dtype region features ship to the device in: the compute dtype
        when it's a 16-bit float (bit-identical — the model casts inputs to
        compute dtype at its first dense layer — and half the bytes over the
        host↔TPU link), f32 otherwise."""
        if (jnp.issubdtype(self.compute_dtype, jnp.floating)
                and self.compute_dtype.itemsize == 2):
            return self.compute_dtype
        return np.dtype(np.float32)

    def prepare(
        self,
        task_id: int,
        question: str,
        regions: Sequence[RegionFeatures],
        image_paths: Optional[Sequence[str]] = None,
        *,
        cache_keys: Optional[Sequence[str]] = None,
        frames: Optional[Sequence[Optional[RowFrame]]] = None,
    ) -> PreparedRequest:
        """Host-side preprocessing: validate, tokenize, encode, bucket.

        Mirrors ``custom_prediction`` (worker.py:388-458) + the repeat
        semantics in ``prediction`` (worker.py:256-284).

        ``cache_keys`` (one stable identity string per image, e.g. the
        store path) opts this request's region tensors into the device
        input cache — pass them ONLY for content-stable images; never
        derived from the synthetic ``image_paths`` defaults.

        ``frames`` is :meth:`prepare_from_store`'s: where ``frames[i]`` is
        given, row i is on the device under ``cache_keys[i]``,
        ``regions[i]`` is None and nothing is encoded for it.
        """
        if task_id not in TASK_REGISTRY:
            raise ValueError(f"unknown task_id {task_id}")
        spec = TASK_REGISTRY[task_id]
        n = len(regions)
        spec.validate_num_images(n)
        ecfg = self.cfg.engine
        bucket = n if n == 1 else ecfg.bucket_for(n)

        with obs.span("engine.tokenize", task_id=task_id):
            text = encode_question(
                self.tokenizer, question, ecfg.max_text_len, task_id=task_id,
            ).stack(bucket)
        carried = [r for r in regions if r is not None]
        feats = spatials = image_mask = None
        if carried:
            with obs.span("engine.encode", n_images=len(carried),
                          task_id=task_id):
                # Bucket padding is the batched (mesh) program's; the slab
                # path pads with slot 0, and only it has resident rows.
                carried, feats, spatials, image_mask = self._encode_rows(
                    carried, pad_to=bucket if frames is None else None)
        task_ids = np.full((bucket, 1), task_id, np.int32)
        if cache_keys is not None:
            if len(cache_keys) != n:
                raise ValueError(
                    f"got {len(cache_keys)} cache keys for {n} images")
            cache_keys = (list(cache_keys)
                          if ecfg.device_input_cache_entries > 0 else None)
        paths = list(image_paths or [f"image_{i}" for i in range(n)])
        if len(paths) != n:
            raise ValueError(
                f"got {len(paths)} image paths for {n} feature sets"
            )
        sized = iter(carried)  # the clipped regions, in request order
        images = []
        for path, frame in zip(paths, frames or [None] * n):
            if frame is None:
                region = next(sized)
                width, height = region.image_width, region.image_height
            else:
                width, height = frame.width, frame.height
            images.append(dec.ImageMeta(path, width, height))
        return PreparedRequest(spec, n, bucket, text, feats, spatials,
                               image_mask, task_ids, images,
                               cache_keys=cache_keys,
                               frames=list(frames) if frames else None)

    # ---------------------------------------------------------------- decode
    def decode(self, req: PreparedRequest, bundle, row: int = 0
               ) -> dec.TaskResult:
        """Decode one request from the host decode bundle, batch row ``row``.

        ``bundle`` is the already-fetched pytree from :meth:`_decode_bundle`
        — pure numpy from here on; no device traffic in this method.
        """
        spec = req.spec
        if spec.decode == "labels":
            top_p, top_i = bundle["labels_top"][spec.head]
            return dec.decode_labels_topk(
                spec, np.asarray(top_i)[row], np.asarray(top_p)[row],
                self.labels)
        if spec.decode == "binary":
            # paired head: batch row 2k/2k+1 → pair row k (row must be even)
            return dec.decode_binary(
                spec, np.asarray(bundle["vil_binary_prediction"])[row // 2])
        if spec.decode == "trinary":
            return dec.decode_trinary(
                spec, np.asarray(bundle["vil_tri_prediction"])[row])
        if spec.decode == "ranking":
            scores = np.asarray(bundle["vil_logit"])[
                row : row + len(req.images)]
            return dec.decode_ranking(spec, scores, req.images)
        if spec.decode == "grounding":
            return dec.decode_grounding(
                spec, np.asarray(bundle["vision_logit"])[row],
                req.first_spatials, req.images[0])
        raise ValueError(f"unknown decode family {spec.decode}")

    # ---------------------------------------------------------------- serve
    def _row_slab(self) -> dict:
        """The device-resident row slab: one (S, Nv, ...) tensor per image
        input kind, S = 1 pad slot + cache slots + scratch slots.

        - slot 0 is the permanent padding row (zero features, global box,
          mask[0]=1 — features/pipeline.py batch_images): bucket padding
          references it by index and uploads nothing, ever;
        - slots 1..cache_entries hold content-stable store rows (LRU, keyed
          by the cache_keys from prepare()) — the round-3 input cache,
          relocated from loose per-row device dicts into slab slots so the
          forward can GATHER them with one index vector instead of taking
          3×bucket leaf arguments;
        - the trailing max_batch_rows() scratch slots receive novel/keyless
          uploads, rotor-allocated per pack.

        Built lazily ON DEVICE (a jitted zeros/constant program — no
        multi-MB boot upload). Updates are functional (``.at[slot].set``),
        so a forward dispatched against an older slab value keeps reading
        consistent rows while later packs insert — which is what makes
        run_many's bounded pipelining and scratch-rotor reuse safe.
        """
        if self._slab is None:
            with self._input_cache_lock:
                if self._slab is None:
                    ecfg, mcfg = self.cfg.engine, self.cfg.model
                    cache_slots = ecfg.device_input_cache_entries
                    scratch = ecfg.max_batch_rows()
                    n_rows = 1 + cache_slots + scratch
                    nv, dim = ecfg.max_regions, mcfg.v_feature_size
                    tdt = self.transfer_dtype
                    box = tuple(float(v) for v in GLOBAL_BOX)

                    def _build():
                        spat = jnp.zeros((n_rows, nv, 5), jnp.float32)
                        spat = spat.at[0, 0].set(jnp.array(box, jnp.float32))
                        mask = jnp.zeros((n_rows, nv), jnp.int32)
                        mask = mask.at[0, 0].set(1)
                        return dict(
                            features=jnp.zeros((n_rows, nv, dim), tdt),
                            spatials=spat, image_mask=mask)

                    self._slab_scratch0 = 1 + cache_slots
                    self._slab_scratch_n = scratch
                    self._slab_free = list(range(1, 1 + cache_slots))
                    self._slab = jax.jit(_build)()
        return self._slab

    def _slab_insert(self, slot: int, host_row: dict) -> None:
        """Upload one image row and write it into slab ``slot`` (caller
        holds _input_cache_lock). One fused explicit device_put per row —
        the same per-miss upload cost as the pre-slab cache — then one
        tiny constant-leaf jitted update dispatch."""
        if self._slab_insert_fn is None:
            def _ins(slab, row):
                i = row["slot"]
                return {k: slab[k].at[i].set(row[k].astype(slab[k].dtype))
                        for k in slab}

            self._slab_insert_fn = jax.jit(_ins)
        with obs.span("engine.slab_insert", slot=slot):
            placed = jax.device_put(dict(
                features=host_row["features"], spatials=host_row["spatials"],
                image_mask=host_row["image_mask"],
                slot=np.asarray(slot, np.int32)))
            self._slab = self._slab_insert_fn(self._slab, placed)
        obs.INPUT_CACHE_INSERTS.inc()

    def _row_slot_locked(self, row: _Row, pinned: set) -> int:
        """Slab slot for one image row (caller holds _input_cache_lock):
        cache hit → existing slot; keyed miss → LRU cache slot + insert,
        and the row's frame kept beside it; keyless → next scratch slot +
        insert. ``pinned`` are the slots this pack already points at (the
        slot returned joins them): a cache smaller than a pack must not
        hand one of them out again, so a miss that could only evict a
        pinned row rides a scratch slot, uncached. A resident row
        (``row.host`` None) must be a hit here: :meth:`_pack_rows` reads
        what is gone before it calls."""
        key = row.key
        if key is not None:
            slot = self._input_cache.get(key)
            if slot is not None:
                self._input_cache.move_to_end(key)
                self._input_cache_hits += 1
                obs.INPUT_CACHE_HITS.inc()
                pinned.add(slot)
                return slot
            self._input_cache_misses += 1
            obs.INPUT_CACHE_MISSES.inc()
            if self._slab_free:
                slot = self._slab_free.pop()
            else:
                # Cache full: reuse the LRU entry's slot. In-flight
                # forwards captured the pre-insert slab value, so the
                # overwrite cannot corrupt a dispatched batch.
                lru, slot = next(iter(self._input_cache.items()))
                if slot in pinned:
                    key = None
                else:
                    del self._input_cache[lru], self._input_frames[lru]
        if key is not None:
            self._input_cache[key] = slot
            self._input_frames[key] = row.frame()
        else:
            # No stable identity → scratch rotor. One pack needs at most
            # max_batch_rows slots (= the scratch region size), and the
            # pack captures its slab value before releasing the lock, so
            # rotor wrap-around by later packs is invisible to it.
            slot = self._slab_scratch0 + (
                self._scratch_next % self._slab_scratch_n)
            self._scratch_next += 1
        self._slab_insert(slot, row.host)
        pinned.add(slot)
        return slot

    @property
    def input_cache_stats(self) -> Dict[str, int]:
        """entries/hits/misses of the device input cache (observability)."""
        with self._input_cache_lock:
            return {"entries": len(self._input_cache),
                    "hits": self._input_cache_hits,
                    "misses": self._input_cache_misses}

    def live_stats(self) -> Dict[str, float]:
        """Point-in-time engine internals for the obs sampler: slab/cache
        occupancy, compiled-program count, dispatch-breaker state (the
        knobs an operator watches during a soak). Cheap — two lock holds,
        no device work."""
        cache_slots = self.cfg.engine.device_input_cache_entries
        with self._input_cache_lock:
            # Before the slab is lazily built every cache slot is free.
            free = (len(self._slab_free) if self._slab is not None
                    else cache_slots)
            stats = {
                "engine_cache_entries": float(len(self._input_cache)),
                "engine_slab_slots_used": float(cache_slots - free),
                "engine_slab_slots_total": float(cache_slots),
            }
        with self._compile_lock:
            stats["engine_compiled_programs"] = float(len(self._compiled))
            progs = [f for f in self._compiled.values()
                     if isinstance(f, _AotProgram)]
        if self._aot is not None:
            stats["engine_aot_hits"] = float(
                sum(1 for p in progs if p.from_cache))
            stats["engine_aot_compiled"] = float(
                sum(1 for p in progs if p.resolved and not p.from_cache
                    and not p.fell_back))
            stats["engine_aot_fallbacks"] = float(
                sum(1 for p in progs if p.fell_back))
        with self._boot_lock:
            for phase, secs in self.boot_times.items():
                stats[f"engine_boot_{phase}"] = float(secs)
        stats["engine_breaker_open"] = float(
            self._breaker.state != "closed")
        return stats

    def _pack_rows(self, rows: Sequence[_Row], bucket: int
                   ) -> Tuple[dict, np.ndarray]:
        """Resolve each row to a slab slot and return (slab value,
        (bucket,) int32 slot vector); pad slots are 0. The whole pack runs
        under one lock hold and captures the slab value before releasing
        it, so concurrent packs can never recycle this pack's scratch slots
        out from under its forward.

        The pack is the authority on residency, the intake's answer a
        promise that may have lapsed (LRU eviction since; a replica that
        never held the row). A resident row the cache no longer holds is
        read and encoded here, late (:meth:`_read_late`: the same files
        through the same steps), with the lock released, then packed as
        the miss it now is, and its request learns the frame it is decoded
        with. Rows the cache holds are resolved before anything is
        inserted, so no insert of this pack evicts a row of this pack."""
        self._row_slab()  # built outside the (non-reentrant) lock hold
        with self._input_cache_lock:
            late = [i for i, r in enumerate(rows)
                    if r.host is None and r.key not in self._input_cache]
            if not late:
                pinned: set = set()
                slots = [self._row_slot_locked(r, pinned)
                         if r.key in self._input_cache else None
                         for r in rows]
                slots = [s if s is not None
                         else self._row_slot_locked(r, pinned)
                         for r, s in zip(rows, slots)]
                slots.extend([0] * (bucket - len(slots)))
                return self._slab, np.asarray(slots, np.int32)
        rows = list(rows)
        with obs.span("engine.late_read", rows=len(late)):
            for i, row in zip(late, self._read_late([rows[i] for i in late])):
                rows[i] = row
        obs.INTAKE_ROWS_LATE.inc(len(late))
        # Again from the top: every row read now carries its tensors, so
        # this ends; another row may have left the device meanwhile.
        return self._pack_rows(rows, bucket)

    def _run_rows(self, bucket: int, collect_attention: bool,
                  text_host: dict, rows: Sequence[_Row]):
        """Dispatch the O(1)-leaf rows program: pack the image rows into
        the slab, then ship text + slot indices as ONE fused explicit
        device_put (the donated ``pack`` argument)."""
        slab, slots = self._pack_rows(rows, bucket)
        pack = jax.device_put({**text_host, "rows": slots})
        return self._call_forward(bucket, collect_attention, slab, pack,
                                  rows=True)

    def _request_rows(self, req: PreparedRequest) -> List[_Row]:
        """A request's real image rows, in order, for :meth:`_pack_rows`."""
        keys = req.cache_keys or [None] * req.n_images
        return [_Row(host, keys[i], req.images[i], req, i)
                for i, host in enumerate(req.host_rows())]

    def run(self, req: PreparedRequest, *, collect_attention: bool = False,
            deadline=None):
        """Device forward for a prepared request → (output, decoded result).

        ``deadline`` (a :class:`resilience.Deadline`) is checked at entry:
        dispatching a forward for a client that already gave up is the most
        expensive possible no-op, so an expired budget raises
        :class:`DeadlineExceeded` before any device work.
        """
        if deadline is not None and deadline.expired():
            raise DeadlineExceeded(
                f"deadline expired {-deadline.remaining_s():.2f}s before "
                f"dispatch (task {req.spec.task_id})")
        text = dict(
            input_ids=req.text.input_ids, segment_ids=req.text.segment_ids,
            input_mask=req.text.input_mask, task_ids=req.task_ids,
        )
        # The forward span closes only after the blocking device_get below —
        # jax dispatch is async, so fencing on the fetch is what makes the
        # span measure device time instead of enqueue time.
        with obs.span("engine.forward", bucket=req.bucket,
                      task_id=req.spec.task_id,
                      replica=self.replica_id or ""):
            if self.mesh is not None:
                # Mesh serving ships the batched tree with batch shardings (a
                # local multi-chip host: PCIe upload is cheap; the row cache
                # is a single-device optimization).
                batch = {**text, "features": req.features,
                         "spatials": req.spatials,
                         "image_mask": req.image_mask}
                batch = shd.place_batch(batch, self.mesh)
                out, bundle = self._call_forward(req.bucket,
                                                 collect_attention, batch)
            else:
                # Slab path: cached rows resolve to slot indices (zero
                # upload); text + the index vector ship as one explicit
                # device_put inside _run_rows.
                out, bundle = self._run_rows(
                    req.bucket, collect_attention, text,
                    self._request_rows(req))
            # One blocking fetch of the few-KB decode bundle: the span
            # includes the single device→host round trip; decode is then
            # pure host math.
            bundle = jax.device_get(bundle)
        with obs.span("engine.decode", task_id=req.spec.task_id):
            result = self.decode(req, bundle)
        return out, result

    def run_many(
        self, reqs: Sequence[PreparedRequest], *,
        chunk_rows: Optional[int] = None, deadline=None,
        on_result=None,
    ) -> List[dec.TaskResult]:
        """Cross-task micro-batching: many single-image requests, ONE forward.

        The BASELINE.md "full 12-task round-robin batch (shared trunk, all
        heads hot)" serving mode — every head computes over the whole batch
        anyway (the trunk dominates), and per-row ``task_ids`` keep the
        task-token embeddings per-request, so any mix of tasks packs into
        MXU-efficient batches. Multi-image requests (NLVR2 pairs,
        retrieval) batch too — MIXED image counts share chunks: a
        request's rows stay consecutive inside a chunk, every decode
        family reads its own row span (see :meth:`decode`), and
        even-image-count requests lead each chunk so NLVR2 pairs keep the
        binary head's 2k/2k+1 alignment (see :meth:`chunk_plan`).

        ``on_result(pos, result)`` streams each member's decoded result as
        its chunk drains — the continuous-batching scheduler hands results
        to its completion stage while later chunks are still on the
        device. Exceptions from the callback propagate (the caller owns
        per-member error handling).
        """
        if not reqs:
            return []
        # Entry-only deadline check (batches carry per-job deadlines — the
        # worker sheds expired members BEFORE packing; this guards callers
        # that pass one shared budget for the whole batch, e.g. evals).
        if deadline is not None and deadline.expired():
            raise DeadlineExceeded(
                f"deadline expired {-deadline.remaining_s():.2f}s before "
                f"batch dispatch ({len(reqs)} requests)")
        # Oversized batches split into max-bucket chunks rather than erroring
        # (callers pick batch sizes; compiled buckets cap per-forward rows).
        # Bounded pipelining: up to _MAX_INFLIGHT_CHUNKS chunks dispatch
        # ahead of the oldest fetch — jax dispatch is async, so the host
        # packs/uploads chunk k+1 while the device computes chunk k (upload
        # hides behind compute) without letting
        # an arbitrarily long request list pile every chunk's buffers into
        # HBM at once.
        from collections import deque

        plan = self.chunk_plan([r.n_images for r in reqs],
                               chunk_rows=chunk_rows)
        chunks: List[List[Tuple[int, PreparedRequest]]] = [
            [(pos, reqs[pos]) for pos in idxs] for idxs in plan
        ]
        out: List[Optional[dec.TaskResult]] = [None] * len(reqs)
        pending: deque = deque()

        def _drain_one() -> None:
            c, bundle = pending.popleft()
            # The fetch is where the host waits for the device: the
            # dispatch returned as soon as the program was enqueued.
            with obs.span("engine.result_wait",
                          rows=sum(r.n_images for _, r in c)):
                bundle = jax.device_get(bundle)
            with obs.span("engine.decode", n_requests=len(c)):
                row = 0
                for pos, r in c:
                    out[pos] = self.decode(r, bundle, row=row)
                    row += r.n_images
                    if on_result is not None:
                        on_result(pos, out[pos])

        with obs.span("engine.run_many", replica=self.replica_id or "",
                      n_requests=len(reqs),
                      n_chunks=len(chunks)):
            for c in chunks:
                rows = sum(r.n_images for _, r in c)
                with obs.span("engine.dispatch", rows=rows,
                              bucket=self.cfg.engine.row_bucket_for(rows)
                              ) as sp:
                    # Plain reads: another thread dispatching on this
                    # engine (warm-up) can blur the two attributes, never
                    # the counters.
                    hits, misses = (self._input_cache_hits,
                                    self._input_cache_misses)
                    pending.append((c, self._dispatch_many([r for _, r in c])))
                    sp.set(hits=self._input_cache_hits - hits,
                           misses=self._input_cache_misses - misses)
                if len(pending) >= self._MAX_INFLIGHT_CHUNKS:
                    _drain_one()
            while pending:
                _drain_one()
        return out

    # At most this many chunks in flight (inputs + un-fetched bundles in
    # HBM) during a chunked run_many: 2 gives full upload/compute overlap;
    # more only grows the memory footprint.
    _MAX_INFLIGHT_CHUNKS = 2

    def chunk_plan(self, image_counts: Sequence[int], *,
                   chunk_rows: Optional[int] = None) -> List[List[int]]:
        """run_many's packing, exposed: request indices per chunk.

        Chunks pack at the largest throughput bucket when configured — the
        10-row retrieval cap on the image buckets doesn't bound a packed
        chunk; a 32-row chunk keeps the MXU fed instead of paying a
        dispatch round trip per 10 rows. ``chunk_rows`` overrides for
        callers tuning backlog shape; it must fit a compiled bucket.

        Mixed image counts SHARE chunks (round 5; the per-count grouping
        before it paid one partial chunk per count — a ragged
        NLVR2+retrieval+VQA backlog dispatched 3 forwards where one
        suffices). Two invariants make that safe:

        - a request's rows stay consecutive (each chunk lists whole
          requests; _dispatch_many packs spans in plan order);
        - EVEN-image-count requests precede odd ones inside a chunk, so
          every even-count request starts at an even row offset — the
          binary head pairs batch rows 2k/2k+1, and NLVR2's pair must BE
          one of those pairs (decode reads pair row offset//2). Sums of
          even numbers are even, so ordering evens first guarantees it
          without knowing task ids.
        """
        max_bucket = (chunk_rows if chunk_rows is not None
                      else self.cfg.engine.max_batch_rows())
        self.cfg.engine.row_bucket_for(max_bucket)  # raises on <1 or misfit
        for n in image_counts:
            if n > max_bucket:
                raise ValueError(
                    f"request with {n} images exceeds the "
                    f"{max_bucket}-row chunk; raise throughput_buckets or "
                    f"chunk_rows")
        order = ([i for i, n in enumerate(image_counts) if n % 2 == 0]
                 + [i for i, n in enumerate(image_counts) if n % 2])
        chunks: List[List[int]] = []
        cur: List[int] = []
        cur_rows = 0
        for i in order:
            n = image_counts[i]
            if cur_rows + n > max_bucket:
                chunks.append(cur)
                cur, cur_rows = [], 0
            cur.append(i)
            cur_rows += n
        if cur:
            chunks.append(cur)
        return chunks

    def _dispatch_many(self, reqs: Sequence[PreparedRequest]):
        """Pack one ≤max-bucket chunk and dispatch its forward; returns the
        un-fetched device decode bundle. A request's rows (one per image,
        text replicated — the multi-image contract of :meth:`prepare`) stay
        consecutive, in request order."""
        spans = [(r, i) for r in reqs for i in range(r.n_images)]
        n = len(spans)
        bucket = self.cfg.engine.row_bucket_for(n)
        pad = bucket - n

        def pack(rows, pad_row):
            rows = list(rows) + [pad_row] * pad
            return np.stack(rows, axis=0)

        text = dict(
            input_ids=pack([r.text.input_ids[i] for r, i in spans],
                           reqs[-1].text.input_ids[-1]),
            segment_ids=pack([r.text.segment_ids[i] for r, i in spans],
                             reqs[-1].text.segment_ids[-1]),
            input_mask=pack([r.text.input_mask[i] for r, i in spans],
                            reqs[-1].text.input_mask[-1]),
            task_ids=pack([r.task_ids[i] for r, i in spans],
                          reqs[-1].task_ids[-1]),
        )
        if self.mesh is not None:
            batch = dict(
                text,
                features=pack([r.features[i] for r, i in spans],
                              reqs[-1].features[-1]),
                spatials=pack([r.spatials[i] for r, i in spans],
                              reqs[-1].spatials[-1]),
                image_mask=pack([r.image_mask[i] for r, i in spans],
                                reqs[-1].image_mask[-1]),
            )
            batch = shd.place_batch(batch, self.mesh)
            _, bundle = self._call_forward(bucket, False, batch)
        else:
            # Slab rows: store-backed rows ride the device cache here too —
            # under queue backlog (the batched path) repeat images resolve
            # to cached slab slots and cost no upload, same as solo
            # serving. Pad slots reference the permanent pad slot 0
            # (discarded at decode). Packed text + the slot-index vector
            # move in one deliberate device_put inside _run_rows — the
            # compiled signature stays O(1) in chunk rows.
            rows = [row for r in reqs for row in self._request_rows(r)]
            _, bundle = self._run_rows(bucket, False, text, rows)
        return bundle

    def predict(
        self,
        task_id: int,
        question: str,
        image_paths: Sequence[str],
        *,
        collect_attention: bool = False,
    ) -> dec.TaskResult:
        """Full request path: feature lookup → prepare → forward → decode.

        The library-level equivalent of one queue callback's model section
        (worker.py:556-576) — requires a ``FeatureStore``.
        """
        if self.feature_store is None:
            raise RuntimeError("predict() needs a FeatureStore; use "
                               "prepare()+run() with in-memory regions instead")
        # Identity, residency, and a store read only for what the device
        # does not hold.
        req = self.prepare_from_store(task_id, question, image_paths)
        _, result = self.run(req, collect_attention=collect_attention)
        return result
