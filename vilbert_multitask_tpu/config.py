"""One typed config tree for the whole framework.

The reference spreads configuration over five uncoordinated mechanisms
(SURVEY.md §5): hardcoded SimpleNamespace blobs (reference worker.py:67-76,
470-493), a BertConfig JSON plus post-hoc attribute pokes (worker.py:495-522),
a YAML task registry (worker.py:496-503), a YACS detector config (worker.py:79),
and Django settings. This module collapses all five into frozen dataclasses:

- :class:`ViLBertConfig`   — the model (mirrors config/bert_base_6layer_6conect.json
  plus the overrides applied at worker.py:509-522).
- :class:`TaskSpec` / :data:`TASK_REGISTRY` — the 8 served task types
  (UI dropdown result.html:318-336; dispatch worker.py:250-263).
- :class:`EngineConfig`    — inference runtime (shape buckets, dtypes, mesh).
- :class:`ServingConfig`   — queue/HTTP/websocket/DB tier.
- :class:`FrameworkConfig` — the root aggregate.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Mapping, Sequence


@dataclasses.dataclass(frozen=True)
class ViLBertConfig:
    """Two-stream ViLBERT architecture knobs.

    Field names follow the reference config JSON (``bert_base_6layer_6conect.json``,
    loaded at reference worker.py:472,495) so checkpoints and configs translate
    1:1. Defaults are the values the reference demo actually serves with,
    including the runtime overrides at worker.py:509-523 (``v_target_size=1601``,
    ``predict_feature=False``, ``task_specific_tokens=True``,
    ``visualization=True``, ``num_labels=3129``).
    """

    # --- text stream (BERT-base) ---
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12

    # --- visual stream ---
    v_feature_size: int = 2048
    v_target_size: int = 1601
    v_hidden_size: int = 1024
    v_num_hidden_layers: int = 6
    v_num_attention_heads: int = 8
    v_intermediate_size: int = 1024
    v_hidden_act: str = "gelu"
    v_hidden_dropout_prob: float = 0.1
    v_attention_probs_dropout_prob: float = 0.1
    v_initializer_range: float = 0.02

    # --- co-attention bridge ---
    bi_hidden_size: int = 1024
    bi_num_attention_heads: int = 8
    bi_intermediate_size: int = 1024
    # Text layer i in t_biattention_id co-attends with visual layer j at the
    # same position in v_biattention_id ("6 connect" in the config name).
    v_biattention_id: Sequence[int] = (0, 1, 2, 3, 4, 5)
    t_biattention_id: Sequence[int] = (6, 7, 8, 9, 10, 11)
    fusion_method: str = "mul"  # pooled_t ∘ pooled_v fusion for vil_* heads

    # --- behavior flags (reference worker.py:509-523) ---
    predict_feature: bool = False
    task_specific_tokens: bool = True
    num_task_tokens: int = 20  # task-token embedding table size
    dynamic_attention: bool = False
    visualization: bool = True  # return per-layer attention maps (10th output)
    # Run the co-attention bridges through the Pallas flash kernel
    # (ops/coattention.py). Off when attention maps are requested — the
    # blockwise kernel never materializes probabilities.
    use_pallas_coattention: bool = False
    # Same kernel for the single-stream self-attention; a stream only takes
    # the kernel path when its head_dim fills 128-lane tiles exactly (the
    # 1024/8 visual stream does; BERT-base text's 64 would waste half the
    # MXU, so it stays on XLA).
    use_pallas_self_attention: bool = False
    # Run those kernels in the Pallas interpreter instead of compiling them
    # under Mosaic: the explicit choice of a CPU test or rehearsal. Never
    # inferred from the backend — with it off, Pallas on a non-TPU backend
    # raises at trace time instead of serving slowly.
    pallas_interpret: bool = False
    # Rematerialize encoder layers in the backward pass (jax.checkpoint via
    # nn.remat): trades ~30% more FLOPs for activation memory that scales
    # with ONE layer instead of the full 18-layer stack — the standard HBM
    # lever for large-batch training.
    remat: bool = False

    # --- heads ---
    num_labels: int = 3129  # VQA answer space (worker.py:523)
    gqa_num_labels: int = 1533  # GQA answer space (12-in-1 head width)

    def __post_init__(self):
        if len(self.v_biattention_id) != len(self.t_biattention_id):
            raise ValueError("v_biattention_id and t_biattention_id must pair up")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide num_attention_heads")
        if self.v_hidden_size % self.v_num_attention_heads:
            raise ValueError("v_hidden_size must divide v_num_attention_heads")
        if self.bi_hidden_size % self.bi_num_attention_heads:
            raise ValueError("bi_hidden_size must divide bi_num_attention_heads")

    @property
    def num_connection_layers(self) -> int:
        return len(self.v_biattention_id)

    @classmethod
    def from_json_file(cls, path: str) -> "ViLBertConfig":
        """Load a reference-format config JSON (ignores unknown keys)."""
        with open(path) as f:
            raw = json.load(f)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in known})

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["v_biattention_id"] = list(self.v_biattention_id)
        d["t_biattention_id"] = list(self.t_biattention_id)
        return json.dumps(d, indent=2, sort_keys=True)

    def tiny(self, **overrides) -> "ViLBertConfig":
        """A scaled-down config for CPU tests (same topology, small dims)."""
        small = dict(
            # >= the committed assets/wordpiece_vocab.txt size, so tiny
            # models accept ids from the default serving tokenizer.
            vocab_size=1088,
            hidden_size=48,
            num_hidden_layers=4,
            num_attention_heads=4,
            intermediate_size=64,
            max_position_embeddings=64,
            v_feature_size=32,
            v_target_size=11,
            v_hidden_size=32,
            v_num_hidden_layers=2,
            v_num_attention_heads=2,
            v_intermediate_size=32,
            bi_hidden_size=32,
            bi_num_attention_heads=2,
            bi_intermediate_size=32,
            v_biattention_id=(0, 1),
            t_biattention_id=(2, 3),
            num_labels=17,
            gqa_num_labels=13,
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)


@dataclasses.dataclass(frozen=True)
class TaskSpec:
    """One served task type (reference: UI dropdown result.html:318-336 +
    worker dispatch worker.py:250-263,295-386)."""

    task_id: int
    name: str
    head: str  # which model output decodes this task
    decode: str  # decode family: "labels" | "binary" | "trinary" | "ranking" | "grounding"
    min_images: int
    max_images: int
    top_k: int  # how many ranked answers the demo shows
    label_map: str | None = None  # key into the label-map store, if any
    description: str = ""
    placeholder: str = ""

    def validate_num_images(self, n: int) -> None:
        """Image-count gating, matching the asserts at worker.py:256-263."""
        if not (self.min_images <= n <= self.max_images):
            raise ValueError(
                f"task {self.task_id} ({self.name}) requires "
                f"{self.min_images}..{self.max_images} images, got {n}"
            )


GENERATE_TASK_ID = 20

# The 8 served task types (and the text-generation task of the decoder). task_id values are the reference's wire protocol —
# they appear in queue messages (demo/sender.py:26-31) and the UI (result.html:318-336).
TASK_REGISTRY: Mapping[int, TaskSpec] = {
    t.task_id: t
    for t in [
        TaskSpec(1, "VQA", head="vil_prediction", decode="labels", min_images=1,
                 max_images=1, top_k=3, label_map="vqa",
                 description="Visual question answering (VQAv2)",
                 placeholder="e.g. What is the man holding?"),
        TaskSpec(2, "VQA-variant", head="vil_prediction", decode="labels", min_images=1,
                 max_images=1, top_k=3, label_map="vqa",
                 description="Alias of VQA; decodable but absent from the reference UI "
                             "(worker.py:295,564 vs result.html:318-336)"),
        TaskSpec(15, "GQA", head="vil_prediction_gqa", decode="labels", min_images=1,
                 max_images=1, top_k=3, label_map="gqa",
                 description="Spatial-reasoning QA (GQA)",
                 placeholder="e.g. Is the bowl to the right of the mug?"),
        TaskSpec(4, "Visual7W", head="vision_logit", decode="grounding", min_images=1,
                 max_images=1, top_k=3,
                 description="Pointing QA — answer is a box",
                 placeholder="e.g. Which object can you eat?"),
        TaskSpec(11, "RefCOCO", head="vision_logit", decode="grounding", min_images=1,
                 max_images=1, top_k=3,
                 description="Referring-expression grounding",
                 placeholder="e.g. the woman in the red coat"),
        TaskSpec(16, "GuessWhat", head="vision_logit", decode="grounding", min_images=1,
                 max_images=1, top_k=3,
                 description="Referring dialog grounding (Q:..? A:.. format)",
                 placeholder="e.g. Q: is it a person? A: no Q: is it red? A: yes"),
        TaskSpec(13, "SNLI-VE", head="vil_tri_prediction", decode="trinary", min_images=1,
                 max_images=1, top_k=3,
                 description="Visual entailment: contradiction/neutral/entailment",
                 placeholder="e.g. Two dogs are playing in the snow."),
        TaskSpec(12, "NLVR2", head="vil_binary_prediction", decode="binary", min_images=2,
                 max_images=2, top_k=2,
                 description="Does the caption describe the image pair? True/False",
                 placeholder="e.g. Both images contain exactly two wolves."),
        TaskSpec(7, "Retrieval", head="vil_logit", decode="ranking", min_images=2,
                 max_images=10, top_k=0,  # top_k=#images, resolved at decode time
                 description="Caption-based image retrieval over the uploaded set",
                 placeholder="e.g. A man riding a horse on the beach."),
        # Text generation (serve/http_api.py: ``prompt_ids``,
        # ``max_new_tokens``, ``logit_ids``). Served only by an app built
        # around a GenerateEngine (GenerateConfig.model set): that app
        # serves no ViLBERT task, and a ViLBERT app refuses this one.
        TaskSpec(GENERATE_TASK_ID, "Generate", head="lm_head",
                 decode="generate", min_images=0, max_images=0, top_k=0,
                 description="Greedy text generation from a prompt of token "
                             "ids; one terminal frame with the tokens and "
                             "the logits asked for"),
    ]
}

# Decode label maps that are fixed (not loaded from disk).
NLVR2_LABELS = ("False", "True")  # worker.py:327
SNLI_VE_LABELS = ("contradiction (false)", "neutral", "entailment (true)")  # worker.py:342


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Inference-runtime knobs (replaces the SimpleNamespace blob at
    reference worker.py:470-493 and the implicit shapes in custom_prediction)."""

    max_text_len: int = 37  # wordpiece tokens incl. [CLS]/[SEP] (worker.py:408)
    max_regions: int = 101  # 100 detector boxes + 1 global feature (worker.py:71,433)
    num_features: int = 100  # detector boxes kept per image (worker.py:71)
    # Static shape buckets for the image axis: NLVR2 needs 2, retrieval 2..10
    # (worker.py:256-284). Each bucket compiles once.
    image_buckets: Sequence[int] = (1, 2, 4, 8, 10)
    # Row buckets used ONLY by run_many's chunking (the queue-backlog
    # batched path). The image buckets top out at 10 for retrieval
    # semantics, which caps batched MFU near 0.5%; throughput-sized chunks
    # keep the MXU fed — one batch-32 forward is ~0.8 TFLOP of real work
    # per dispatch. The intermediate 16 keeps mid-size batches (11-31 rows)
    # off the 32-row padding cliff. None/() → chunk at max(image_buckets)
    # (the round-3 behavior). Read by this class's own methods alone
    # (all_row_buckets, max_batch_rows): `self.` is no spelling VMT122 knows.
    throughput_buckets: Sequence[int] | None = (16, 32)  # vmtlint: disable=VMT122
    compute_dtype: str = "bfloat16"  # MXU-native compute precision
    # Param STORAGE dtype for serving (init_params / checkpoint restore /
    # mesh placement all cast to it). "bfloat16" halves every weight read —
    # at serving batch sizes the forward is weight-read-bound (see
    # engine/flops.py roofline), so this is the serving-latency knob — and
    # halves the boot upload. "int8" halves it AGAIN: floating matrix
    # leaves are stored as per-channel symmetric {"int8", "scale"} pairs
    # (quant.py) and dequantized inside the jitted forward right before
    # each matmul, so HBM reads stay int8. Training is unaffected: the
    # trainer owns its own f32 master tree, and checkpoints on disk stay
    # f32 — quantization happens at the serving cast seam only.
    param_dtype: str = "float32"
    # Run the nine per-task decode heads as ONE batched program (stacked
    # weight slabs + in-program gather by task id, engine/runtime.py)
    # instead of nine sequential small matmuls. Mixed-task chunks stop
    # fragmenting into per-head dispatches; numerics match the per-head
    # path to LayerNorm rounding (~1e-6 f32). Off → the round-3 per-head
    # path, which the parity tests pin against.
    fused_task_heads: bool = True
    # Default ON: serving runs the flash co-attention kernel, compiled by
    # Mosaic. A compiler refusal fails the boot with the compiler's message
    # (no automatic XLA fallback — turning these off is the remedy, and an
    # explicit one). Off-TPU the kernels only run when the model config
    # says ``pallas_interpret``; CPU tests pin whichever path they mean.
    use_pallas_coattention: bool = True
    use_pallas_self_attention: bool = True  # 128-aligned streams only
    # Region-count threshold for sequence-parallel ring attention on the
    # visual stream (parallel/ring.py): buckets at or above it route
    # v-stream self-attention through the mesh's "sp" axis (MeshConfig.sp
    # > 1), below it the dense path wins (ppermute latency beats the HBM
    # saving at demo scale — 101 regions). Static per compiled bucket.
    ring_min_regions: int = 256
    # Text/label assets. None → the committed defaults in assets/ (real
    # file-loading code paths; swap the files for the genuine bert-base-
    # uncased vocab / reference label pickles to get score parity).
    vocab_path: str | None = None
    labels_root: str | None = None
    # AOT executable cache (engine/aotcache.py): serialized compiled
    # programs keyed by COMPILE_SURFACE.json record keys + a compatibility
    # fingerprint, stored next to the checkpoint. Warm boots deserialize
    # instead of trace+compile; misses compile and backfill. None → off.
    # serve/app.py defaults it next to the checkpoint when one is given.
    aot_cache_dir: str | None = None
    # Compile shape buckets concurrently at warmup — XLA compilation is C++
    # and releases the GIL, so 5 buckets warm in ~the longest single compile.
    parallel_warmup: bool = True
    # Device-side input cache (LRU entries): store-backed images are
    # content-stable, so their encoded region tensors are constants — pin
    # them in HBM after the first request instead of re-uploading ~0.4 MB/
    # image (bf16) per query over the host↔TPU link. 0 disables. Keys are
    # explicit (engine.prepare cache_keys) — never inferred from synthetic
    # path defaults. Entries are single image ROWS (max_regions ×
    # v_feature_size ≈ 0.41 MB bf16 / 0.83 MB f32 at serving size), shared
    # across buckets; eviction is entry-count LRU, so 64 entries ≈ 26 MB
    # bf16 (53 MB f32) against the v5e's 16 GB HBM.
    device_input_cache_entries: int = 64

    def bucket_for(self, n_images: int) -> int:
        for b in self.image_buckets:
            if n_images <= b:
                return b
        raise ValueError(f"no shape bucket holds {n_images} images")

    def all_row_buckets(self) -> list:
        """Every compiled row count serving can dispatch: the image buckets
        (run()) plus the throughput buckets (run_many), sorted. The single
        source for warmup coverage and chunk-fitting."""
        return sorted({*self.image_buckets,
                       *(self.throughput_buckets or ())})

    def row_bucket_for(self, n_rows: int) -> int:
        """Smallest compiled row count that fits a run_many chunk (batched
        rows are independent single-image requests, so the image-axis
        semantics of bucket_for don't constrain them)."""
        if n_rows < 1:
            raise ValueError(f"row count must be >=1, got {n_rows}")
        for b in self.all_row_buckets():
            if n_rows <= b:
                return b
        raise ValueError(f"no row bucket holds {n_rows} rows")

    def max_batch_rows(self) -> int:
        """Largest compiled row count — run_many's chunk size and the
        natural drain depth for a backlogged worker."""
        return max(max(self.image_buckets),
                   *(self.throughput_buckets or (0,)))


@dataclasses.dataclass(frozen=True)
class DetectorConfig:
    """Faster R-CNN region-feature extractor (detect/model.py).

    Defaults mirror the reference's X-152-32x8d-FPN geometry
    (maskrcnn_benchmark driven from reference worker.py:59-89): ResNeXt
    bottleneck stages (3, 8, 36, 3) with 32 groups × width 8, a 256-channel
    FPN, class-agnostic proposals, fc6 2048-d region features, 1601 VG
    classes. ``tiny()`` scales the same topology down for CPU tests.
    The serving default remains precomputed features (BASELINE.json);
    live extraction is the sanctioned stretch for novel uploads.
    """

    # --- backbone (ResNeXt) ---
    stem_channels: int = 64
    stage_blocks: Sequence[int] = (3, 8, 36, 3)  # X-152
    groups: int = 32
    width_per_group: int = 8
    stage_channels: Sequence[int] = (256, 512, 1024, 2048)
    # --- FPN ---
    fpn_channels: int = 256
    # --- RPN ---
    anchor_sizes: Sequence[int] = (32, 64, 128, 256, 512)  # per level P2..P6
    aspect_ratios: Sequence[float] = (0.5, 1.0, 2.0)
    rpn_pre_nms_top_n: int = 1000
    rpn_post_nms_top_n: int = 300
    rpn_nms_thresh: float = 0.7
    # --- ROI box head ---
    roi_resolution: int = 7
    roi_sampling: int = 2
    representation_size: int = 2048  # fc6/fc7 width → the ViLBERT v_feature
    num_classes: int = 1601  # VG classes incl. background col 0
    # --- input canvas (static shapes for XLA) ---
    canvas: int = 1344  # fits short-side-800/long-side-1333 preprocessing

    def tiny(self, **overrides) -> "DetectorConfig":
        small = dict(
            stem_channels=8, stage_blocks=(1, 1, 1, 1), groups=2,
            width_per_group=4, stage_channels=(16, 32, 64, 128),
            fpn_channels=16, rpn_pre_nms_top_n=64, rpn_post_nms_top_n=32,
            roi_resolution=3, roi_sampling=2, representation_size=32,
            num_classes=7, canvas=64,
        )
        small.update(overrides)
        return dataclasses.replace(self, **small)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout. The reference has no intra-model parallelism
    (SURVEY.md §2.3); here DP×TP over ICI is first-class."""

    dp: int = -1  # -1: all remaining devices
    tp: int = 1
    # Sequence-parallel axis size (ring attention over the visual stream,
    # parallel/ring.py). 1 = no sp axis; >1 adds an "sp" mesh axis and
    # engine/trainer route long region sets through the ring when they
    # clear EngineConfig.ring_min_regions.
    sp: int = 1
    axis_names: Sequence[str] = ("dp", "tp")


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Web/queue tier (replaces Django settings + demo/constants.py +
    sender/worker pika constants)."""

    queue_name: str = "vilbert_multitask_queue"  # wire-compatible (sender.py:18)
    queue_db_path: str = "serve_state/queue.sqlite3"
    results_db_path: str = "serve_state/results.sqlite3"
    media_root: str = "media"
    refer_expr_dir: str = "refer_expressions_task"  # worker.py:600
    http_host: str = "127.0.0.1"
    http_port: int = 8400
    ws_port: int = 8401
    # Poison bound on *charged* attempts (claims less releases; fixes
    # worker.py:650-655). ``queue_max_deliveries`` below bounds every claim.
    max_delivery_attempts: int = 3
    # Shared secret for the /worker/* endpoints (remote workers, serve/remote.py).
    # None → open, matching the reference broker's default-credentials posture
    # (sender.py:12-15); set it when workers cross host boundaries.
    worker_token: str | None = None
    # Shared secret for the ADMIN WRITE surface (POST /admin/*). The
    # reference's Django admin is login-gated (demo/admin.py); here edits
    # mutate the persistent task catalog, so when set, writes require
    # ``Authorization: Bearer <token>`` (admin.html prompts for it).
    # None → open — acceptable only on the loopback default bind.
    admin_token: str | None = None
    # --- resilience/ knobs (see ARCHITECTURE.md "Resilience") ---
    # Admission control at the HTTP door: shed with 429 + Retry-After when
    # pending+inflight depth crosses the threshold (0 disables the signal;
    # the oldest pending job's age is AdmissionController's own bound).
    admission_max_queue_depth: int = 512
    admission_retry_after_s: float = 2.0
    # --- replica pool (serve/pool.py) ---
    # Engine replicas behind the queue/scheduler seam: separate devices or
    # mesh shards on hardware, CPU threads in dryrun. 1 keeps the
    # single-engine data path but still health-gates it through the pool.
    pool_replicas: int = 1
    # How long checkout() waits for a ready replica before raising
    # NoReadyReplica (jobs stay queued; the durable queue absorbs brief
    # all-replicas-busy or rolling-swap windows).
    pool_checkout_timeout_s: float = 30.0
    # Dispatches a single replica may hold concurrently. 1 = strictly
    # serial per replica (scaling comes from replica count alone).
    pool_max_inflight_per_replica: int = 1
    # Per-replica dispatch breaker: stricter than the engine's own funnel
    # breaker — a replica that keeps failing leaves the rotation
    # (ready→degraded) after this many failures in the window, and is
    # probed again (half-open checkout) after the reset timeout.
    pool_breaker_failure_threshold: int = 3
    pool_breaker_window_s: float = 30.0
    pool_breaker_reset_timeout_s: float = 5.0
    # Rolling checkpoint swap: max seconds to wait for a draining replica's
    # in-flight dispatches to finish before swapping params anyway.
    pool_swap_drain_timeout_s: float = 30.0
    # Total deliveries (claims) a job gets before the queue dead-letters
    # it as poison — counts every redelivery, including visibility-timeout
    # and release()-based failover redeliveries that charge no *attempt*.
    queue_max_deliveries: int = 3
    # --- continuous-batching scheduler (serve/scheduler.py; its fixed
    # sizes are constants at the top of that module) ---
    # Max READY (claimed + prepped, undispatched) jobs. Doubles as intake
    # backpressure AND the admission signal: ready jobs stay 'inflight' in
    # the durable queue, so they keep counting against the
    # AdmissionController's pending+inflight depth at the HTTP door.
    sched_ready_depth: int = 64
    # Adaptive batching window bounds: the scheduler lingers up to the
    # current window for co-arriving jobs before firing a partial batch;
    # the window stretches (x2 up to max) after full buckets and shrinks
    # (/2 down to min) after partial ones, so an idle system fires nearly
    # immediately and a backlogged one packs bigger batches.
    sched_window_min_s: float = 0.002
    sched_window_max_s: float = 0.05
    # --- obs/ live-health knobs (see ARCHITECTURE.md "SLOs & flight
    # recorder") ---
    # Background sampler: snapshot cadence of the in-process time-series
    # store.
    sampler_cadence_s: float = 1.0
    # Multi-window burn-rate evaluation: PAGE/WARN need the burn over the
    # threshold on BOTH windows (fast = "happening now", slow =
    # "sustained"). The burn thresholds are SloEvaluator's, the targets
    # serve/app.py's.
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 600.0
    # Flight recorder: bundle directory (under serve_state by default so
    # a soak tmpdir sweeps it), rotation cap, and the per-event re-trigger
    # floor.
    recorder_dir: str = "serve_state/postmortem"
    recorder_max_bundles: int = 16
    recorder_min_interval_s: float = 30.0
    # Fleet observability spine (obs/fleet.py): every process's sampler
    # tick flushes instrument snapshots, timeseries deltas, spans, and a
    # heartbeat into a shared WAL sqlite db (next to the queue db when
    # unset), so any process can answer ?scope=fleet queries for the
    # whole fleet. A peer whose heartbeat is older than the staleness
    # bound is treated as dead (SIGKILL leaves no tombstone). The cost
    # attribution records and the tail-sampled trace store
    # (obs/attrib.py, obs/tracestore.py) live on the same db.
    fleet_db_path: str | None = None
    fleet_heartbeat_stale_s: float = 15.0
    # Tenant-weighted fairness in the EDF scheduler: select_batch grants
    # per-tenant row budgets by weighted deficit (DRR) ABOVE deadline
    # ordering, so one hot tenant cannot starve the rest. Weights are
    # relative shares; tenants absent from the map weigh 1.0, and None
    # means every tenant is equal.
    tenant_weights: Mapping[str, float] | None = None
    # --- closed-loop autoscaler (serve/autoscale.py; ROADMAP item 1) ---
    # Target-tracking on queue-wait p95 and SLO burn rate, riding the obs
    # sampler cadence. Breach above target*band_high for breach_ticks
    # consecutive ticks scales OUT (pool.add_replica); slack below
    # target*band_low AND burn below threshold for slack_ticks ticks
    # scales IN (pool.retire_replica, never below min). Scale-out is
    # additionally gated on pool health: any open replica breaker or a
    # poison/dead-letter rate above max_poison_rate_per_s reads as
    # "unhealthy, don't scale", not "overloaded, add replicas".
    autoscale_enabled: bool = False
    autoscale_min_replicas: int = 1
    autoscale_max_replicas: int = 4
    autoscale_target_queue_wait_p95_ms: float = 500.0
    autoscale_burn_threshold: float = 1.0
    autoscale_band_high: float = 1.2
    autoscale_band_low: float = 0.5
    autoscale_breach_ticks: int = 3
    autoscale_slack_ticks: int = 12
    autoscale_cooldown_out_s: float = 30.0
    autoscale_cooldown_in_s: float = 60.0
    autoscale_max_poison_rate_per_s: float = 0.5
    autoscale_window_s: float = 30.0
    autoscale_decision_history: int = 128



LINEAR_ATTENTION, FULL_ATTENTION = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """A causal decoder of ``model_type: olmo_hybrid`` (gated linear
    attention in ``k`` layers of ``k + 1``): the source ``config.json``'s
    keys under their own names. The layers are ``models/olmo_hybrid.py``
    (``model_type`` names the module: ``engine/generate.py`` finds it so)."""

    model_type: str = "olmo_hybrid"
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    layer_types: Sequence[str] = (
        (LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,)) * 8
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    # Kernel choice (not the source's): the Pallas kernels on, which are
    # the chunked scan of ops/gated_delta.py and the decode attention of
    # ops/paged_attention.py; ``pallas_interpret`` is the CPU tests'
    # explicit choice, never inferred from the backend.
    use_pallas_scan: bool = True
    pallas_interpret: bool = False

    def __post_init__(self):
        types = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        if self.model_type != "olmo_hybrid":
            raise ValueError(f"model_type {self.model_type!r} is not "
                             "olmo_hybrid")
        if len(types) != self.num_hidden_layers:
            raise ValueError("layer_types names another depth than "
                             "num_hidden_layers")
        if FULL_ATTENTION not in types:
            raise ValueError("a pattern without full_attention has no "
                             "period")
        period = types.index(FULL_ATTENTION) + 1
        one = (LINEAR_ATTENTION,) * (period - 1) + (FULL_ATTENTION,)
        if len(types) % period or types != one * (len(types) // period):
            raise ValueError("layer_types must repeat one period of linear "
                             "layers closed by a full one")
        if (self.hidden_act != "silu" or self.attention_bias
                or self.tie_word_embeddings):
            raise ValueError("only silu, no attention bias, untied head")
        if self.num_attention_heads != self.num_key_value_heads:
            raise ValueError("grouped key/value heads are not implemented")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("linear key and value head counts must agree")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")

    @property
    def period(self) -> int:
        """Layers a period: the linear ones and the full one closing it."""
        return self.layer_types.index(FULL_ATTENTION) + 1

    @property
    def periods(self) -> int:
        return self.num_hidden_layers // self.period

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: q~ | k~ | v~."""
        return self.linear_num_key_heads * (2 * self.linear_key_head_dim
                                            + self.linear_value_head_dim)

    def tiny(self) -> "OlmoHybridConfig":
        """The size of the CPU tests: 2 periods of 64-wide layers."""
        return dataclasses.replace(
            self, vocab_size=512, hidden_size=64, intermediate_size=96,
            num_hidden_layers=8, num_attention_heads=4,
            num_key_value_heads=4, max_position_embeddings=512,
            layer_types=((LINEAR_ATTENTION,) * 3 + (FULL_ATTENTION,)) * 2,
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=16,
            use_pallas_scan=False)

SLIDING_ATTENTION = "sliding_attention"
_LAGUNA_ROPE = (
    (FULL_ATTENTION, (
        ("rope_theta", 500000.0), ("rope_type", "yarn"), ("factor", 128.0),
        ("original_max_position_embeddings", 8192), ("beta_slow", 1.0),
        ("beta_fast", 32.0), ("attention_factor", 1.4852030263919618),
        ("partial_rotary_factor", 0.5))),
    (SLIDING_ATTENTION, (
        ("rope_type", "default"), ("rope_theta", 10000.0),
        ("partial_rotary_factor", 1.0))),
)


def _frozen(value):
    """Dicts and lists of a source ``config.json`` as nested tuples, so the
    frozen dataclass that holds them stays hashable."""
    if isinstance(value, dict):
        return tuple((k, _frozen(v)) for k, v in value.items())
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """A causal decoder of ``model_type: laguna``: sparse experts behind a
    256-wide router, and grouped attention whose layers are windowed three
    in four, with another count of query heads than the full ones. The
    source ``config.json``'s keys under their own names; the layers are
    ``models/laguna.py``.

    Two keys are this repo's, the *cut* a chip's share of a deployment is
    (``benchmark/configs/laguna-s-2.1-5l-ep2.json``): ``experts_held`` =
    (first, count) of the routed experts whose weights live here (None:
    all; the router stays ``num_experts`` wide and a token's top
    ``num_experts_per_tok`` is taken over all of them); and ``vocab_size``
    itself, which counts the rows of the embedding and the head held here
    (ids are drawn from that slice)."""

    model_type: str = "laguna"
    vocab_size: int = 100352
    hidden_size: int = 3072
    intermediate_size: int = 12288
    num_hidden_layers: int = 48
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 1048576
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    num_experts: int = 256
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Sequence[int] = (0,)
    tie_word_embeddings: bool = False
    gating: str = "per-head"
    sliding_window: int = 512
    rope_parameters: Sequence = _LAGUNA_ROPE
    layer_types: Sequence[str] = (
        (FULL_ATTENTION,) + (SLIDING_ATTENTION,) * 3) * 12
    mlp_layer_types: Sequence[str] = ("dense",) + ("sparse",) * 47
    gating_types: Sequence[str] = ("per_head",) * 48
    num_attention_heads_per_layer: Sequence[int] = (48, 72, 72, 72) * 12
    moe_apply_router_weight_on_input: bool = False
    moe_routed_scaling_factor: float = 2.5
    moe_router_logit_softcapping: float = 0.0
    # The cut (see above).
    experts_held: Sequence[int] | None = None
    # Kernel choice (not the source's): the grouped expert product of
    # ops/moe.py and the decode attention of ops/paged_attention.py as
    # Pallas kernels; ``pallas_interpret`` is the CPU tests' explicit
    # choice, never inferred from the backend.
    use_pallas: bool = True
    pallas_interpret: bool = False

    def __post_init__(self):
        for name in ("layer_types", "mlp_layer_types", "gating_types",
                     "num_attention_heads_per_layer", "mlp_only_layers",
                     "rope_parameters"):
            object.__setattr__(self, name, _frozen(getattr(self, name)))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(v) for v in self.experts_held))
        n = self.num_hidden_layers
        for name in ("layer_types", "mlp_layer_types", "gating_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"{name} names another depth than "
                                 "num_hidden_layers")
        if self.model_type != "laguna":
            raise ValueError(f"model_type {self.model_type!r} is not laguna")
        if set(self.layer_types) - {FULL_ATTENTION, SLIDING_ATTENTION}:
            raise ValueError("layer_types: only full_attention and "
                             "sliding_attention are implemented")
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("mlp_layer_types: only dense and sparse")
        dense = tuple(i for i, t in enumerate(self.mlp_layer_types)
                      if t == "dense")
        if (self.decoder_sparse_step != 1
                or dense != tuple(i for i in self.mlp_only_layers if i < n)):
            raise ValueError("mlp_layer_types must be dense exactly at "
                             "mlp_only_layers (decoder_sparse_step 1)")
        if (self.gating != "per-head"
                or set(self.gating_types) != {"per_head"}):
            raise ValueError("only the per-head output gate is implemented")
        if (self.hidden_act != "silu" or self.attention_bias
                or self.tie_word_embeddings):
            raise ValueError("only silu, no attention bias, untied head")
        if (not self.norm_topk_prob or self.moe_apply_router_weight_on_input
                or self.moe_router_logit_softcapping):
            raise ValueError("the router is implemented with norm_topk_prob, "
                             "its weight on the expert's output and no "
                             "logit soft-capping")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("every layer's query heads must divide into "
                             "the key/value heads")
        rope = self.rope
        if set(rope) != {FULL_ATTENTION, SLIDING_ATTENTION} or any(
                p["rope_type"] not in ("default", "yarn")
                for p in rope.values()):
            raise ValueError("rope_parameters: one entry a layer type, of "
                             "rope_type default or yarn")
        if any(self.head_dim * float(p.get("partial_rotary_factor", 1.0)) % 2
               for p in rope.values()):
            raise ValueError("the rotated part of a head must be even")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError("experts_held = (first, count) must lie inside "
                             "the router's num_experts")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts")

    @property
    def rope(self) -> dict:
        """``rope_parameters`` as the source has it: {layer type: {key:
        value}}."""
        return {t: dict(p) for t, p in self.rope_parameters}

    @property
    def held(self) -> tuple:
        """(first, count) of the routed experts whose weights live here."""
        return (0, self.num_experts) if self.experts_held is None \
            else self.experts_held

    @property
    def full_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == FULL_ATTENTION)

    @property
    def sliding_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == SLIDING_ATTENTION)

    @property
    def sparse_layers(self) -> tuple:
        return tuple(i for i, t in enumerate(self.mlp_layer_types)
                     if t == "sparse")

    def cut(self, layers: int) -> "LagunaConfig":
        """The first ``layers`` layers of the pattern."""
        return dataclasses.replace(
            self, num_hidden_layers=layers,
            layer_types=self.layer_types[:layers],
            mlp_layer_types=self.mlp_layer_types[:layers],
            gating_types=self.gating_types[:layers],
            num_attention_heads_per_layer=(
                self.num_attention_heads_per_layer[:layers]))

    def tiny(self) -> "LagunaConfig":
        """The size of the CPU tests: 5 layers in the published pattern, 64
        wide, 16 experts of which the first 8 are held, 4 a token; window
        16; a context long enough that YaRN's ramp is crossed."""
        rope = self.rope
        rope[FULL_ATTENTION].update(original_max_position_embeddings=64,
                                    factor=8.0, attention_factor=None)
        return dataclasses.replace(
            self.cut(5), vocab_size=384, hidden_size=64, intermediate_size=96,
            num_key_value_heads=2, head_dim=16, num_attention_heads=4,
            num_attention_heads_per_layer=(4, 6, 6, 6, 4),
            max_position_embeddings=512, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=32,
            shared_expert_intermediate_size=32, sliding_window=16,
            rope_parameters=rope, experts_held=(0, 8), use_pallas=False)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """A decoder-hybrid-decoder of ``model_type: phi4flash`` (arXiv:
    2507.06607): a *self-decoder* whose layers alternate a Mamba mixer
    (selective scan) with differential attention (windowed; the last one
    full, and paged), then a *cross-decoder* whose layers alternate a gated
    memory unit, which reads the scan output of the self-decoder's last
    Mamba layer, with differential cross-attention over the keys and values
    of the self-decoder's full layer. The source ``config.json``'s keys
    under their own names; the Mamba mixer's sizes, which it does not give,
    are fields with the family's defaults (Mamba-1). The layers are
    ``models/phi4flash.py``."""

    model_type: str = "phi4flash"
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    hidden_act: str = "silu"
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    mb_per_layer: int = 2
    sliding_window: int = 512
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    # Assumed (the source module's defaults; its ``config.json`` has no key).
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | str = "auto"
    # Kernel choice (not the source's): the selective scan of
    # ops/selective_scan.py and both attentions of ops/paged_attention.py as
    # Pallas kernels; ``pallas_interpret`` is the CPU tests' explicit
    # choice, never inferred from the backend.
    use_pallas: bool = True
    pallas_interpret: bool = False

    def __post_init__(self):
        if self.model_type != "phi4flash":
            raise ValueError(f"model_type {self.model_type!r} is not "
                             "phi4flash")
        n = self.num_hidden_layers
        if self.mb_per_layer != 2:
            raise ValueError("only mb_per_layer 2 (every second layer a "
                             "Mamba mixer) is implemented")
        if n % 4 or n < 8:
            raise ValueError("num_hidden_layers must be a multiple of 4, at "
                             "least 8: the half-way split needs a Mamba and "
                             "a full layer to close the self-decoder, a "
                             "window pair before them and whole pairs after")
        if (self.hidden_act != "silu" or self.mlp_bias or self.lm_head_bias
                or not self.tie_word_embeddings):
            raise ValueError("only silu, no MLP or head bias, tied head")
        if self.embd_pdrop or self.resid_pdrop:
            raise ValueError("dropout is not implemented (serving)")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into the heads")
        if (self.num_attention_heads % 2 or self.num_key_value_heads % 2
                or self.num_attention_heads % self.num_key_value_heads):
            raise ValueError("differential attention pairs heads: even "
                             "counts, query heads a multiple of the "
                             "key/value heads")
        if self.mamba_dt_rank != "auto" and int(self.mamba_dt_rank) < 1:
            raise ValueError("mamba_dt_rank is 'auto' or a whole number")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return (-(-self.hidden_size // 16) if self.mamba_dt_rank == "auto"
                else int(self.mamba_dt_rank))

    @property
    def layer_kinds(self) -> tuple:
        """What each layer's mixer is: ``mamba``, ``window`` (differential
        attention over the last ``sliding_window`` positions), ``full``
        (differential attention over the sequence: the one paged layer),
        ``gmu`` (gated memory unit), ``cross`` (differential
        cross-attention over the full layer's keys and values)."""
        half = self.num_hidden_layers // 2
        kinds = []
        for l in range(self.num_hidden_layers):
            if l < half:
                kinds.append("mamba" if l % 2 == 0 else "window")
            elif l == half:
                kinds.append("mamba")
            elif l == half + 1:
                kinds.append("full")
            else:
                kinds.append("gmu" if l % 2 == 0 else "cross")
        return tuple(kinds)

    def layers_of(self, *kinds: str) -> tuple:
        return tuple(l for l, k in enumerate(self.layer_kinds) if k in kinds)

    @property
    def self_decoder_layers(self) -> int:
        """Layers a prompt token runs: up to the full layer, with it."""
        return self.num_hidden_layers // 2 + 2

    def tiny(self) -> "Phi4FlashConfig":
        """The size of the CPU tests: 8 layers (Mamba and window twice, the
        memory's Mamba, the full layer, a GMU, a cross layer), 64 wide, 8 /
        4 heads of 8, state 4, window 16."""
        return dataclasses.replace(
            self, vocab_size=384, hidden_size=64, intermediate_size=96,
            num_hidden_layers=8, num_attention_heads=8,
            num_key_value_heads=4, max_position_embeddings=512,
            sliding_window=16, mamba_d_state=4, use_pallas=False)


@dataclasses.dataclass(frozen=True)
class GenerateConfig:
    """The text-generation engine (engine/generate.py). ``model`` None: the
    app serves ViLBERT and refuses the ``generate`` task. One ``ServeApp``
    holds one model: a generate app serves no ViLBERT task."""

    model: OlmoHybridConfig | LagunaConfig | Phi4FlashConfig | None = None
    param_dtype: str = "bfloat16"
    # Compiled shapes: tokens a prefill chunk (multiples of the page size
    # and of the scan's 64), sequences a decode step.
    prefill_buckets: Sequence[int] = (256, 1024, 2048)
    decode_buckets: Sequence[int] = (8, 16, 24, 32)
    # The sequence-state manager (engine/seqstate.py): sequences resident
    # at once, and the key/value pool in pages of ``page_size`` tokens.
    slots: int = 32
    kv_pages: int = 256
    page_size: int = 256
    # None: what the slots and pages hold when all are in use.
    state_bytes_budget: int | None = None
    max_logit_ids: int = 16
    # Pages of the pool a decode attention step reads at once (``kv_pages``
    # must be a multiple).
    decode_attention_pages: int = 32

    def prefill_bucket_for(self, tokens: int) -> int:
        for b in sorted(self.prefill_buckets):
            if tokens <= b:
                return b
        return max(self.prefill_buckets)

    def decode_bucket_for(self, sequences: int) -> int:
        for b in sorted(self.decode_buckets):
            if sequences <= b:
                return b
        raise ValueError(f"no decode bucket holds {sequences} sequences")

    def problem_with(self, prompt_ids, max_new_tokens, logit_ids) -> str:
        """Why a generate request cannot be served ('' if it can): the one
        rule the HTTP door and the engine both apply."""
        model = self.model
        for name, ids in (("prompt_ids", prompt_ids),
                          ("logit_ids", logit_ids)):
            if not isinstance(ids, (list, tuple)) or not all(
                    type(i) is int for i in ids):
                return f"{name} must be a list of whole numbers"
            if ids and not (0 <= min(ids) and max(ids) < model.vocab_size):
                return f"{name} must lie in [0, {model.vocab_size})"
        if type(max_new_tokens) is not int or max_new_tokens < 1:
            return "max_new_tokens must be a whole number of at least 1"
        if not prompt_ids:
            return "prompt_ids must hold at least one token"
        total = len(prompt_ids) + max_new_tokens
        if total > model.max_position_embeddings:
            return (f"prompt ({len(prompt_ids)}) + max_new_tokens "
                    f"({max_new_tokens}) exceed the context of "
                    f"{model.max_position_embeddings}")
        if -(-total // self.page_size) > self.kv_pages:
            return (f"the request needs {-(-total // self.page_size)} "
                    f"key/value pages; the pool has {self.kv_pages}")
        if len(logit_ids) > self.max_logit_ids:
            return f"at most {self.max_logit_ids} logit_ids"
        return ""


@dataclasses.dataclass(frozen=True)
class FrameworkConfig:
    model: ViLBertConfig = dataclasses.field(default_factory=ViLBertConfig)
    engine: EngineConfig = dataclasses.field(default_factory=EngineConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    generate: GenerateConfig = dataclasses.field(
        default_factory=GenerateConfig)


def config_fingerprint(cfg: FrameworkConfig) -> str:
    """Short stable hash of the full config tree — the "which exact
    configuration was this process running" field for `vmt_build_info`
    and flight-recorder bundles. Same config → same fingerprint across
    processes (sorted-key JSON over the dataclass dict)."""
    import hashlib

    blob = json.dumps(dataclasses.asdict(cfg), sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def add_backend_args(parser) -> None:
    """The shared --tiny/--cpu CLI knobs (serving binary, evals harness,
    onboarding CLI): one definition so a new backend knob can't silently
    diverge between entry points."""
    parser.add_argument("--tiny", action="store_true",
                        help="tiny model config (rehearsal/tests; must "
                             "match any checkpoint being loaded)")
    parser.add_argument("--cpu", action="store_true",
                        help="pin the CPU backend (f32, XLA attention)")


def apply_backend_args(cfg: FrameworkConfig, args) -> FrameworkConfig:
    """Apply add_backend_args selections. With --cpu this must run before
    any jax backend init: it pins jax_platforms in-process."""
    if getattr(args, "cpu", False):
        import jax

        jax.config.update("jax_platforms", "cpu")
        cfg = dataclasses.replace(cfg, engine=dataclasses.replace(
            cfg.engine, compute_dtype="float32",
            use_pallas_coattention=False, use_pallas_self_attention=False))
    if getattr(args, "tiny", False):
        cfg = dataclasses.replace(cfg, model=cfg.model.tiny())
    return cfg


def require_tpu(what: str) -> None:
    """Fail fast unless JAX's default backend is a TPU. The serving binary
    and the chip smoke call this before any other JAX work: with no chip
    JAX would otherwise carry on on the CPU and every number after that
    would describe the wrong machine. CPU is an explicit choice made by
    the caller (``--cpu``, ``--cpu-rehearsal``), never a fallback."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU but JAX's default backend is "
            f"{backend!r} ({jax.devices()[0].device_kind}); pass the "
            f"explicit CPU flag to run off-chip")
