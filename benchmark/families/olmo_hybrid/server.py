"""The system under test, booted as a deployment boots it: a real
``ServeApp`` (HTTP + websocket + durable queue + scheduler) around a
``GenerateEngine`` holding the benchmark's weights, on ephemeral ports with
a fresh state directory, every prefill and decode bucket warmed. With
``tests/broken_run.py`` and ``families/vilbert/server.py`` the only files
of the benchmark that import the program's serving classes."""

from __future__ import annotations

import dataclasses
import os
import time


def framework_config(model: dict, engine: dict, state_dir: str,
                     rehearsal: bool):
    from vilbert_multitask_tpu.config import (
        FrameworkConfig,
        GenerateConfig,
        OlmoHybridConfig,
        ServingConfig,
    )

    model = dict(model)
    model.pop("model_type", None)
    model.pop("rope_parameters", None)   # rope_theta null: no rotary
    if rehearsal:
        # The CPU rehearsal says so itself: the kernel in the interpreter.
        model["pallas_interpret"] = True
    engine = dict(engine)
    for key in ("prefill_buckets", "decode_buckets"):
        engine[key] = tuple(engine[key])
    serving = dataclasses.replace(
        ServingConfig(),
        queue_db_path=os.path.join(state_dir, "queue.sqlite3"),
        results_db_path=os.path.join(state_dir, "results.sqlite3"),
        media_root=os.path.join(state_dir, "media"),
        http_port=0, ws_port=0)
    return FrameworkConfig(
        generate=GenerateConfig(model=OlmoHybridConfig(**model), **engine),
        serving=serving)


class _App:
    """What the generic code asks of an application (``http_port``,
    ``ws.bound_port``, ``stop()``); stopping also frees the sequence state,
    so that the reference has the device's memory beside the weights."""

    def __init__(self, app, engine):
        self.app, self.generate_engine = app, engine
        self.ws = app.ws

    @property
    def http_port(self):
        return self.app.http_port

    def stop(self) -> None:
        self.app.stop()
        self.generate_engine.close()


def boot(cfg, params) -> tuple:
    from vilbert_multitask_tpu.engine import aotcache, cachedir
    from vilbert_multitask_tpu.engine.generate import (
        GenerateEngine,
        generate_fingerprint,
    )
    from vilbert_multitask_tpu.serve.app import ServeApp

    phases = {}
    t0 = time.monotonic()
    cfg = dataclasses.replace(cfg, engine=dataclasses.replace(
        cfg.engine, aot_cache_dir=cachedir.default_aot_cache_dir()))
    aot = aotcache.AotCache(cfg.engine.aot_cache_dir,
                            generate_fingerprint(cfg))
    aot.prefetch()
    engine = GenerateEngine(cfg, params=params, replica_id="r0",
                            aot_cache=aot)
    app = ServeApp(cfg, engine=[engine])
    phases["engine_init_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    app.warm()
    phases["warmup_s"] = time.monotonic() - t0
    app.start()
    return _App(app, engine), phases
