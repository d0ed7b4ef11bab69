"""Process-wide observability: span tracing, instruments, exporters.

Three pillars (see ARCHITECTURE.md "Observability"):

- ``obs.span("engine.forward", task_id=...)`` — monotonic-clocked spans
  with thread-local parenting and cross-queue trace-id resumption
  (:mod:`vilbert_multitask_tpu.obs.trace`);
- ``obs.REGISTRY`` — counters / gauges / log-bucket histograms, plus the
  one shared :func:`percentile` used by serve and the soak
  (:mod:`vilbert_multitask_tpu.obs.instruments`);
- Prometheus text exposition, Chrome-trace JSON, and ``jax.profiler``
  toggles (:mod:`vilbert_multitask_tpu.obs.export`).

Importing the package wires the default tracer's observer to feed every
completed span into the ``vmt_span_ms{name,task}`` histogram, which is
what ``GET /metrics?format=prometheus`` serves as per-task stage
latencies, and its CPU seconds into ``vmt_span_cpu_ms{name}``.
"""

from __future__ import annotations

import time

from vilbert_multitask_tpu.obs.trace import (
    Span,
    Tracer,
    current_trace_id,
    default_tracer,
    new_trace_id,
    span,
    trace_scope,
)
from vilbert_multitask_tpu.obs.instruments import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    REGISTRY,
    log_buckets,
    percentile,
)
from vilbert_multitask_tpu.obs.export import (
    OPENMETRICS_CONTENT_TYPE,
    PROMETHEUS_CONTENT_TYPE,
    chrome_trace,
    dump_trace,
    render_openmetrics,
    render_prometheus,
    start_profile,
    stop_profile,
)
from vilbert_multitask_tpu.obs.attrib import (
    STAGES as COST_STAGES,
    CostAttributor,
    JobCost,
    get_attributor,
    job_batch,
    job_begin,
    job_charge,
    job_finish,
    set_attributor,
)
from vilbert_multitask_tpu.obs.tracestore import TraceStore
from vilbert_multitask_tpu.obs.timeseries import (
    GIL_PROBE_THREAD_NAME,
    GilProbe,
    SAMPLER_THREAD_NAME,
    Sampler,
    TimeSeriesStore,
    WINDOW_RESERVOIR,
)
from vilbert_multitask_tpu.obs.recorder import (
    RECORDER_THREAD_NAME,
    FlightRecorder,
    active_recorder,
    clear_recorder,
    install_recorder,
    record_event,
    record_spike,
)
from vilbert_multitask_tpu.obs.watchdog import (
    THREAD_ALIVE_GAUGE,
    ThreadWatchdog,
    crash_guard,
    watchdog,
)
from vilbert_multitask_tpu.obs.slo import (
    STATE_OK,
    STATE_PAGE,
    STATE_WARN,
    Slo,
    SloEvaluator,
    availability_slo,
    latency_slo,
    slack_floor_slo,
)
from vilbert_multitask_tpu.obs.identity import (
    WorkerIdentity,
    mint_identity,
    process_identity,
    reset_process_identity,
)
from vilbert_multitask_tpu.obs.fleet import (
    FleetSpine,
    default_spine_path,
)

__all__ = [
    "Span", "Tracer", "current_trace_id", "default_tracer", "new_trace_id",
    "span", "trace_scope",
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY",
    "log_buckets", "percentile",
    "OPENMETRICS_CONTENT_TYPE", "PROMETHEUS_CONTENT_TYPE", "chrome_trace",
    "dump_trace", "render_openmetrics", "render_prometheus",
    "start_profile", "stop_profile",
    "COST_STAGES", "CostAttributor", "JobCost", "TraceStore",
    "get_attributor", "job_batch", "job_begin", "job_charge", "job_finish",
    "set_attributor",
    "SHED_COUNTER", "RETRY_COUNTER", "BREAKER_GAUGE", "DEADLINE_SLACK",
    "BATCH_FILL", "SCHED_WAIT", "QUEUE_WAIT", "BATCHES_DISPATCHED",
    "REPLICA_STATE", "FAILOVER_COUNTER", "POISON_COUNTER",
    "RESULT_CACHE_HITS", "RESULT_CACHE_MISSES",
    "RESULT_CACHE_INVALIDATIONS", "COALESCED_SUBMITS", "TENANT_DEFICIT",
    "SCHED_READY_JOBS", "INTAKE_EMPTY_POLLS", "INTAKE_BACKPRESSURE_POLLS",
    "INTAKE_CLAIMS_SIGNALLED", "INTAKE_CLAIMS_UNSIGNALLED",
    "FEATURE_STORE_HITS", "FEATURE_STORE_MISSES", "FEATURE_STORE_READ_BYTES",
    "FEATURE_STORE_LOAD_SECONDS", "INPUT_CACHE_HITS", "INPUT_CACHE_MISSES",
    "INPUT_CACHE_INSERTS", "INTAKE_ROWS_RESIDENT", "INTAKE_ROWS_READ",
    "INTAKE_ROWS_LATE",
    "SPAN_CPU_HISTOGRAM", "READY_WAIT", "COMPLETION_WAIT",
    "DISPATCH_STARVED", "DISPATCH_BLOCKED", "PROCESS_CPU_SECONDS",
    "GIL_PROBE_THREAD_NAME", "GilProbe", "WINDOW_RESERVOIR",
    "SAMPLER_THREAD_NAME", "Sampler", "TimeSeriesStore",
    "RECORDER_THREAD_NAME", "FlightRecorder", "active_recorder",
    "clear_recorder", "install_recorder", "record_event", "record_spike",
    "THREAD_ALIVE_GAUGE", "ThreadWatchdog", "crash_guard", "watchdog",
    "STATE_OK", "STATE_PAGE", "STATE_WARN", "Slo", "SloEvaluator",
    "availability_slo", "latency_slo", "slack_floor_slo",
    "WorkerIdentity", "mint_identity", "process_identity",
    "reset_process_identity",
    "FleetSpine", "default_spine_path",
]

# Both span histograms hold a benchmark window's spans (a reader divides
# one's sum by the other's over the same spans).
SPAN_HISTOGRAM = REGISTRY.histogram(
    "vmt_span_ms",
    "Span durations by span name and task (ms).",
    labelnames=("name", "task"),
    reservoir=WINDOW_RESERVOIR,
)
SPAN_CPU_HISTOGRAM = REGISTRY.histogram(
    "vmt_span_cpu_ms",
    "The thread's CPU time inside a span, by span name (ms); the span's "
    "wall time less this is time off the core. Spans recorded after the "
    "fact have none.",
    labelnames=("name",),
    reservoir=WINDOW_RESERVOIR,
)
# The host process as a whole: its CPU, read when collected.
PROCESS_CPU_SECONDS = REGISTRY.read_counter(
    "vmt_process_cpu_seconds_total",
    "CPU seconds of this process, all threads (time.process_time, read "
    "when collected).",
    time.process_time,
)

# Resilience instruments (resilience/ policy plane). Defined here so the
# policy module stays import-light and every exporter sees them.
SHED_COUNTER = REGISTRY.counter(
    "vmt_shed_total",
    "Requests/jobs shed before doing work, by reason "
    "(queue_depth, queue_age, deadline).",
    labelnames=("reason",),
)
RETRY_COUNTER = REGISTRY.counter(
    "vmt_retries_total",
    "Retry attempts actually slept for, by call site.",
    labelnames=("site",),
)
BREAKER_GAUGE = REGISTRY.gauge(
    "vmt_breaker_state",
    "Circuit-breaker state: 0 closed, 1 half-open, 2 open.",
    labelnames=("breaker",),
)
DEADLINE_SLACK = REGISTRY.histogram(
    "vmt_deadline_slack_ms",
    "Remaining deadline budget when the worker picked the job up (ms).",
    labelnames=("task",),
)

# Continuous-batching scheduler instruments (serve/scheduler.py).
BATCH_FILL = REGISTRY.histogram(
    "vmt_batch_fill",
    "Dispatched-chunk occupancy as a fraction of its row bucket (1.0 = "
    "the bucket was full; lower = padded rows burned).",
    labelnames=("bucket",),
    buckets=tuple(i / 16 for i in range(1, 17)),
)
SCHED_WAIT = REGISTRY.histogram(
    "vmt_sched_wait_ms",
    "Claim to dispatch (ms): the claimed job's preparation on an intake "
    "thread plus its wait in the ready-queue before its batch fired.",
)
# The scheduler's hand-overs, and its dispatch stage's time between spans.
READY_WAIT = REGISTRY.histogram(
    "vmt_ready_wait_ms",
    "Time a prepared job waited in the ready-queue, from its parking to "
    "its dispatch or admission (ms).",
    reservoir=WINDOW_RESERVOIR,
)
COMPLETION_WAIT = REGISTRY.histogram(
    "vmt_completion_wait_ms",
    "Time a result waited in the completion queue, from its put to the "
    "completion thread taking it (ms).",
    reservoir=WINDOW_RESERVOIR,
)
DISPATCH_STARVED = REGISTRY.counter(
    "vmt_dispatch_starved_seconds_total",
    "Seconds the scheduler's batch-selecting loop waited with no ready "
    "job (in a replica pool, batches then run on an executor thread).",
)
DISPATCH_BLOCKED = REGISTRY.counter(
    "vmt_dispatch_blocked_seconds_total",
    "Seconds the thread that runs a batch blocked handing a result to "
    "a full completion queue.",
)
QUEUE_WAIT = REGISTRY.histogram(
    "vmt_queue_wait_ms",
    "Publish-to-claim latency (ms): POST / stamp to worker claim, the "
    "queueing delay Metrics.record's intake-anchored e2e cannot see. "
    "The tenant label is the deficit scheduler's user-facing effect: a "
    "tenant throttled below its weighted share queues longer, visibly.",
    labelnames=("task", "tenant"),
)
BATCHES_DISPATCHED = REGISTRY.counter(
    "vmt_batches_dispatched_total",
    "Device chunks dispatched by the continuous-batching scheduler.",
)
SCHED_READY_JOBS = REGISTRY.histogram(
    "vmt_sched_ready_jobs",
    "Jobs parked in the scheduler's ready-queue at the moment the window "
    "policy fired (1 = the batcher had nothing to pack the job with).",
    buckets=(1, 2, 3, 4, 6, 8, 12, 16, 24, 32, 48, 64),
)
INTAKE_EMPTY_POLLS = REGISTRY.counter(
    "vmt_intake_empty_polls_total",
    "Intake claims that came back empty: the timed fallback claim of one "
    "idle thread at a time, and claims another thread got ahead of.",
)
INTAKE_CLAIMS_SIGNALLED = REGISTRY.counter(
    "vmt_intake_claims_signalled_total",
    "Intake claims that returned a job on a thread the queue's signal had "
    "just woken (a publish, nack or release in this process).",
)
INTAKE_CLAIMS_UNSIGNALLED = REGISTRY.counter(
    "vmt_intake_claims_unsignalled_total",
    "Intake claims that returned a job after a timed wake or straight "
    "after the thread's last job.",
)
INTAKE_BACKPRESSURE_POLLS = REGISTRY.counter(
    "vmt_intake_backpressure_polls_total",
    "Intake poll-interval sleeps taken because the ready-queue was at "
    "sched_ready_depth (claim run-ahead bounded, not an empty queue).",
)

# Feature path (features/store.py host LRU, engine/runtime.py device slab).
# What has to be told apart has a name of its own, not a label: readers sum
# a counter's label sets.
FEATURE_STORE_HITS = REGISTRY.counter(
    "vmt_feature_store_hits_total",
    "FeatureStore reads answered from the host LRU.",
)
FEATURE_STORE_MISSES = REGISTRY.counter(
    "vmt_feature_store_misses_total",
    "FeatureStore reads that loaded the feature file.",
)
FEATURE_STORE_READ_BYTES = REGISTRY.counter(
    "vmt_feature_store_read_bytes_total",
    "Bytes of the feature files loaded on FeatureStore misses.",
)
FEATURE_STORE_LOAD_SECONDS = REGISTRY.counter(
    "vmt_feature_store_load_seconds_total",
    "Seconds from open to RegionFeatures on FeatureStore misses.",
)
INPUT_CACHE_HITS = REGISTRY.counter(
    "vmt_input_cache_hits_total",
    "Image rows resolved to a resident device-slab slot (no upload).",
)
INPUT_CACHE_MISSES = REGISTRY.counter(
    "vmt_input_cache_misses_total",
    "Keyed image rows that were not slab-resident (each one inserts).",
)
INPUT_CACHE_INSERTS = REGISTRY.counter(
    "vmt_input_cache_inserts_total",
    "Rows written into the device slab: keyed misses plus keyless "
    "scratch rows (each a functional update of the whole slab).",
)
# An image row of prepare_from_store, by what the intake did for it. Apart
# from the two pairs above: a resident row never reaches the store (neither
# a hit nor a miss of its host LRU) and is a hit of the slab at the pack.
INTAKE_ROWS_RESIDENT = REGISTRY.counter(
    "vmt_intake_rows_resident_total",
    "Image rows the intake found on the device by their file identity: "
    "not read, not encoded, no host tensor carried to the pack.",
)
INTAKE_ROWS_READ = REGISTRY.counter(
    "vmt_intake_rows_read_total",
    "Image rows the intake read from the feature store and encoded.",
)
INTAKE_ROWS_LATE = REGISTRY.counter(
    "vmt_intake_rows_late_total",
    "Rows the intake called resident that the pack no longer found in "
    "the slab: read and encoded late, on the dispatch thread.",
)

# Replica-pool instruments (serve/pool.py).
REPLICA_STATE = REGISTRY.gauge(
    "vmt_replica_state",
    "Replica health state: 0 booting, 1 warming, 2 ready, 3 degraded, "
    "4 draining, 5 dead.",
    labelnames=("replica",),
)
FAILOVER_COUNTER = REGISTRY.counter(
    "vmt_failovers_total",
    "In-flight jobs released back to the queue because their replica "
    "died or tripped its breaker mid-dispatch.",
    labelnames=("replica",),
)
POISON_COUNTER = REGISTRY.counter(
    "vmt_poison_jobs_total",
    "Jobs dead-lettered by the queue after exhausting queue_max_deliveries "
    "total deliveries (poison-job quarantine).",
)

# Duplicate-traffic tier instruments (serve/resultcache.py + scheduler).
RESULT_CACHE_HITS = REGISTRY.counter(
    "vmt_result_cache_hits_total",
    "Submits answered from the durable result cache — no queue publish, "
    "no TPU forward.",
)
RESULT_CACHE_MISSES = REGISTRY.counter(
    "vmt_result_cache_misses_total",
    "Submits that missed the result cache and published a real job "
    "(the submit became the singleflight leader).",
)
RESULT_CACHE_INVALIDATIONS = REGISTRY.counter(
    "vmt_result_cache_invalidations_total",
    "Cache rows dropped because a rolling swap changed the config "
    "fingerprint / model generation.",
)
COALESCED_SUBMITS = REGISTRY.counter(
    "vmt_coalesced_submits_total",
    "Submits attached as followers to an identical in-flight job "
    "(singleflight): they pay one shared forward instead of N.",
)
TENANT_DEFICIT = REGISTRY.gauge(
    "vmt_tenant_deficit",
    "Weighted-deficit scheduler credit per tenant (rows); persistently "
    "negative means the tenant is consuming above its weighted share.",
    labelnames=("tenant",),
)


def _observe_span(s: Span) -> None:
    SPAN_HISTOGRAM.observe(
        s.dur_s * 1e3, name=s.name, task=str(s.attrs.get("task_id", "")))
    if s.cpu_s is not None:
        SPAN_CPU_HISTOGRAM.observe(s.cpu_s * 1e3, name=s.name)


default_tracer().set_observer(_observe_span)
