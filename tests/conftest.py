"""Test harness: force an 8-device virtual CPU mesh so sharding tests run
anywhere (the standard JAX fake-backend trick; see SURVEY.md §4).

Tests are CPU work: the platform is pinned to CPU *before any backend init*
(``jax.config.update`` works post-import as long as ``jax.devices()`` hasn't
been called yet; XLA_FLAGS is read at first backend init). The persistent
compilation cache is pointed at a per-session scratch directory, also before
jax is imported, so a test run neither reads programs a previous run left in
the checkout's ``.jax_cache`` nor writes any there (engine/cachedir.py).
"""

import atexit
import os
import shutil
import tempfile

if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _cache_dir = tempfile.mkdtemp(prefix="vmt_test_jax_cache_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

jax.config.update("jax_threefry_partitionable", True)

import pytest  # noqa: E402

# ------------------------------------------------------- fast/slow profiles
# The full suite is compile-bound (~40-75 min on this 1-core box) — slow
# enough that nobody runs it mid-edit, which is how regressions slip in
# (VERDICT r4 weak-5). The compile-heaviest tests (>= ~20 s measured call
# time, mostly mesh-sharded trainer loops and multi-bucket warmups) carry
# the ``slow`` marker so
#
#     pytest -m "not slow"     # fast profile, <10 min — the edit loop
#     pytest                   # full suite — round boundaries / CI
#
# Centralized here (not per-file decorators) so the list is one reviewable
# block; the collection hook FAILS if an entry stops matching a collected
# test, so a rename can't silently un-slow anything. Parametrized tests
# match on the base name.
SLOW_TESTS = {
    # trainer loops (optimizer steps × jit compiles, some mesh-sharded)
    "test_multitask_smoke_trains_all_heads",
    "test_checkpoint_resume_is_bit_exact",
    "test_mesh_checkpoint_resume_is_bit_exact",
    "test_mesh_sharded_training_loop",
    "test_cli_main_synthetic_smoke",
    "test_pretrain_jsonl_captions",
    "test_loss_decreases_on_fixed_batch",
    "test_retrieval_jsonl_group_layout",
    "test_trainer_aborts_on_divergence",
    "test_pretrain_head_trains",
    "test_checkpoint_retention",
    "test_eval_hook_scores_on_serving_path",
    # train-step unit suites that grad-compile the full model
    "test_dryrun_multichip_entry",
    "test_sharded_train_step_on_mesh",
    "test_loss_decreases_over_steps",
    "test_remat_matches_plain_gradients",
    # engine/serving paths that compile several buckets or a mesh twin
    "test_mesh_sharded_run_many_matches_single_device",
    "test_mesh_sharded_engine_matches_single_device",
    "test_transfer_dtype_follows_compute_dtype",
    "test_bf16_param_storage_decode_parity",
    "test_int8_param_storage_decode_parity",
    "test_fused_heads_match_per_head_decode_on_mixed_chunk",
    "test_device_input_cache_lru_eviction",
    "test_input_cache_stats_counts",
    "test_parallel_warmup_compiles_all_buckets",
    "test_serveapp_serves_through_mesh",
    "test_cpu_rehearsal_passes_on_a_mesh",
    "test_throughput_bucket_chunking",
    # end-to-end flows with their own engines/converters
    "test_onboard_end_to_end",
    "test_fallback_store_feeds_vilbert_forward",
    "test_model_runs_sequence_parallel_and_matches_dense",
    "test_golden_scores_are_falsifiable",
    "test_golden_scores_exact",
    "test_full_serving_config_parity",  # also marked inline (280M params)
    # XLA cost analyses over compiled forwards
    "test_flops_estimate_vs_xla_cost_analysis",
}


_COLLECT_ERRORS = []


def pytest_collectreport(report):
    if report.failed:
        _COLLECT_ERRORS.append(report.nodeid)


def pytest_collection_modifyitems(config, items):
    seen = set()
    for item in items:
        base = item.name.split("[")[0]
        if base in SLOW_TESTS:
            seen.add(base)
            item.add_marker(pytest.mark.slow)
    # Only enforce inventory on full, error-free collections: a -k/path-
    # filtered run legitimately collects a subset, and a file that failed
    # to collect already reports its own error — asserting here would bury
    # that real failure under a bogus "renamed?" INTERNALERROR.
    if (not _COLLECT_ERRORS
            and config.args in ([], ["tests"], ["tests/"])
            and len(items) > 150):
        missing = SLOW_TESTS - seen
        assert not missing, (
            f"SLOW_TESTS entries match no collected test (renamed?): "
            f"{sorted(missing)}")


# ------------------------------------------------- transfer-guard sanitizer
# Dynamic twin of vmtlint's VMT101 (host-transfer-in-jit): the engine and
# model unit tests run under ``jax.transfer_guard("disallow")``, so any
# IMPLICIT host↔device transfer — a numpy array silently re-uploaded per
# call, a Python scalar materialized mid-eager-forward — fails the test
# instead of becoming round 2's 23.7 s p50. Explicit transfers
# (``jax.device_put``, ``jnp.asarray``, ``np.asarray(device_array)``) stay
# legal under "disallow"; that is exactly the contract the engine code is
# held to. Session fixtures (the shared ``engine``) are built before the
# function-scoped guard activates, so one-time boot transfers are exempt —
# engines constructed inside a test body run fully guarded.
TRANSFER_GUARDED_MODULES = {"test_engine", "test_model_shapes"}


@pytest.fixture(autouse=True)
def _no_implicit_transfers(request):
    if request.module.__name__.rpartition(".")[2] \
            not in TRANSFER_GUARDED_MODULES:
        yield
        return
    with jax.transfer_guard("disallow"):
        yield


# ------------------------------------------------------ obs thread hygiene
@pytest.fixture(autouse=True)
def _no_leaked_project_threads():
    """Every thread a test spawns must be accounted for when it ends:
    the sampler and flight-recorder writer joined (stop()/close()
    contract — leaking either keeps sampling freed state under every
    later test), any other non-daemon thread joined, and any *named*
    daemon thread registered with the obs watchdog (a crash-guarded
    loop announces itself; an anonymous stdlib helper gets a pass).

    One thread may be born mid-test and not be the test's: the writer of a
    flight recorder that a wider-scoped fixture installed before the test
    began (a module's ``ServeApp``) starts lazily at the first trigger, and
    a sampler tick can fire one at any time (an SLO page on a loaded
    host). That app's ``stop()`` joins it. A recorder installed *during*
    the test is still the test's to close."""
    import threading

    from vilbert_multitask_tpu import obs

    before = {id(t) for t in threading.enumerate()}
    recorder_before = obs.active_recorder()
    yield

    # Default/stdlib naming schemes: unnamed threads, pool workers, and
    # asyncio helpers — not project loops, not watchdog material.
    stdlib_names = ("MainThread", "Thread-", "ThreadPoolExecutor",
                    "asyncio_", "Dummy-")
    wd = obs.watchdog()
    leaked = []
    for t in threading.enumerate():
        if id(t) in before or not t.is_alive():
            continue
        if (recorder_before is not None
                and obs.active_recorder() is recorder_before
                and getattr(recorder_before, "_thread", None) is t):
            continue
        if t.name in (obs.SAMPLER_THREAD_NAME, obs.GIL_PROBE_THREAD_NAME,
                      obs.RECORDER_THREAD_NAME):
            leaked.append(f"{t.name} (stop()/close() must join it)")
        elif not t.daemon:
            leaked.append(f"{t.name} (non-daemon thread never joined)")
        elif not t.name.startswith(stdlib_names) \
                and not wd.is_known_thread(t.name):
            leaked.append(f"{t.name} (named daemon thread unknown to "
                          f"the watchdog registry — run its loop under "
                          f"obs.crash_guard or join it)")
    assert not leaked, (
        f"project threads leaked by this test: {leaked}")


@pytest.fixture(scope="session")
def tiny_config():
    from vilbert_multitask_tpu.config import ViLBertConfig

    return ViLBertConfig().tiny()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


# --------------------------------------------------------- serving fixtures
@pytest.fixture(scope="session")
def tiny_framework_cfg(tmp_path_factory):
    from vilbert_multitask_tpu.config import (
        EngineConfig,
        FrameworkConfig,
        ServingConfig,
        ViLBertConfig,
    )

    root = tmp_path_factory.mktemp("serve_state")
    return FrameworkConfig(
        model=ViLBertConfig().tiny(),
        engine=EngineConfig(
            max_text_len=12, max_regions=9, num_features=8,
            image_buckets=(1, 2, 4, 8), compute_dtype="float32",
            # Keep the serving fixtures on the image buckets alone: the
            # default 16/32-row throughput buckets would add two more
            # compiles to every batching test. Their behavior has a
            # dedicated test (test_batching.py::test_throughput_bucket_chunking).
            throughput_buckets=None,
            # XLA attention here: these fixtures exercise the serving tiers,
            # not the kernel, and interpret-mode Pallas makes CPU forwards
            # ~10x slower. Kernel coverage lives in test_pallas_coattention.
            use_pallas_coattention=False, use_pallas_self_attention=False,
        ),
        serving=ServingConfig(
            queue_db_path=str(root / "queue.sqlite3"),
            results_db_path=str(root / "results.sqlite3"),
            media_root=str(root / "media"),
            http_port=0,
        ),
    )


@pytest.fixture(scope="session")
def features_dir(tmp_path_factory, tiny_framework_cfg):
    import numpy as np

    from vilbert_multitask_tpu.features.pipeline import RegionFeatures
    from vilbert_multitask_tpu.features.store import save_reference_npy

    d = tmp_path_factory.mktemp("features")
    nrng = np.random.default_rng(0)
    dim = tiny_framework_cfg.model.v_feature_size
    for name in ("img_a", "img_b"):
        boxes = np.array([[10, 10, 60, 60], [30, 20, 90, 80],
                          [5, 40, 50, 95]], np.float32)
        region = RegionFeatures(
            features=nrng.normal(size=(3, dim)).astype(np.float32),
            boxes=boxes, image_width=100, image_height=100)
        save_reference_npy(str(d / f"{name}.npy"), region, name)
    return str(d)


@pytest.fixture(scope="session")
def engine(tiny_framework_cfg, features_dir):
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.features.store import FeatureStore

    return InferenceEngine(tiny_framework_cfg,
                           feature_store=FeatureStore(features_dir))


@pytest.fixture()
def stack(tiny_framework_cfg, engine, tmp_path):
    import dataclasses

    from vilbert_multitask_tpu.serve import (
        DurableQueue,
        PushHub,
        ResultStore,
        ServeWorker,
    )

    s = dataclasses.replace(
        tiny_framework_cfg.serving,
        queue_db_path=str(tmp_path / "q.sqlite3"),
        results_db_path=str(tmp_path / "r.sqlite3"),
        media_root=str(tmp_path / "media"),
    )
    hub = PushHub()
    q = DurableQueue(s.queue_db_path,
                     max_delivery_attempts=s.max_delivery_attempts)
    store = ResultStore(s.results_db_path)
    worker = ServeWorker(engine, q, store, hub, s)
    return s, hub, q, store, worker
