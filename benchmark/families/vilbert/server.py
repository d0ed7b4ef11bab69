"""The system under test, booted as a deployment boots it: a real
``ServeApp`` (HTTP + websocket + durable queue + scheduler + engine) on
ephemeral ports with a fresh state directory, the cell's configuration, and
the weights the benchmark made from the seed. This is the only file of the
benchmark that imports the program."""

from __future__ import annotations

import dataclasses
import json
import os
import time


def framework_config(config: dict, state_dir: str, labels_root: str,
                     vocab_path: str, rehearsal: bool):
    from vilbert_multitask_tpu.config import (
        EngineConfig,
        FrameworkConfig,
        ServingConfig,
        ViLBertConfig,
    )

    model = dict(config["model"])
    for key in ("v_biattention_id", "t_biattention_id"):
        model[key] = tuple(model[key])
    engine = dict(config["engine"])
    for key in ("image_buckets", "throughput_buckets"):
        engine[key] = tuple(engine[key])
    if rehearsal:
        # The CPU rehearsal says so itself: kernels in the interpreter.
        model["pallas_interpret"] = True
    serving = dataclasses.replace(
        ServingConfig(),
        queue_db_path=os.path.join(state_dir, "queue.sqlite3"),
        results_db_path=os.path.join(state_dir, "results.sqlite3"),
        media_root=os.path.join(state_dir, "media"),
        http_port=0, ws_port=0)
    return FrameworkConfig(
        model=ViLBertConfig(**model),
        engine=EngineConfig(vocab_path=vocab_path, labels_root=labels_root,
                            **engine),
        serving=serving)


def write_label_maps(root: str, model: dict) -> None:
    """Answer vocabularies of the benchmark's own: label ``i`` is the text
    ``"i"``, so a frame's answer names the index it stands for."""
    os.makedirs(root, exist_ok=True)
    for name, key in (("vqa", "num_labels"), ("gqa", "gqa_num_labels")):
        with open(os.path.join(root, f"{name}_label2ans.json"), "w",
                  encoding="utf-8") as f:
            json.dump([str(i) for i in range(model[key])], f)


PRELOAD_TASK = 1  # a one-image family: one insert and a one-row forward


def boot(cfg, params, feature_root: str, row_buckets: list,
         gallery: list) -> tuple:
    """(app, phase seconds). Builds the engine as ``ServeApp`` itself does
    for a one-chip host, but around the benchmark's weights, warms only the
    row buckets this cell's traffic dispatches (and the one-row program the
    preload uses), then fills the device cache with the ``gallery``."""
    from vilbert_multitask_tpu.engine import aotcache, cachedir
    from vilbert_multitask_tpu.engine.runtime import InferenceEngine
    from vilbert_multitask_tpu.features.store import FeatureStore
    from vilbert_multitask_tpu.serve.app import ServeApp

    phases = {}
    t0 = time.monotonic()
    cfg = dataclasses.replace(cfg, engine=dataclasses.replace(
        cfg.engine, aot_cache_dir=cachedir.default_aot_cache_dir()))
    aot = aotcache.AotCache(
        cfg.engine.aot_cache_dir,
        aotcache.compile_fingerprint(cfg, mesh=None,
                                     heads=cfg.engine.fused_task_heads))
    aot.prefetch()
    engine = InferenceEngine(cfg, params=params,
                             feature_store=FeatureStore(feature_root),
                             replica_id="r0", aot_cache=aot)
    app = ServeApp(cfg, engine=[engine], feature_root=feature_root)
    phases["engine_init_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    app.boot_info["phase"] = "warming"
    # One bucket at a time: a warm-up thread holds the device cache it
    # packed against while its program compiles, and seven of those at once
    # filled the chip (PERF.md section 5).
    for bucket in sorted({1, *row_buckets}):
        app.engine.warmup(buckets=[bucket])
    phases["warmup_s"] = time.monotonic() - t0
    # The gallery a deployment holds on the device, put there by the
    # program's own read, insert and forward (``predict``), one image a
    # call: every insert makes a new copy of the whole device cache, and
    # the copies of one call's inserts are alive together.
    t0 = time.monotonic()
    for k, name in enumerate(gallery):
        engine.predict(PRELOAD_TASK, f"preload gallery image {k}", [name])
    phases["preload_s"] = time.monotonic() - t0
    app.boot_info["phase"] = "booting"
    app.start()
    return app, phases
