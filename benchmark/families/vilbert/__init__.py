"""The ``vilbert`` family: what a cell of the 12-in-1 ViLBERT needs of its
architecture, as ``benchmark/families/__init__.py`` lists it. Each function
hands on to the file of this package that does the work:

  assets, check_traffic   ``catalog.py`` (region-feature files), the vocabulary
  schedule                ``traffic.py`` (sessions of task, images, question)
  weights                 ``harness/weights.py`` over ``reference/<name>.py``'s shapes
  boot                    ``server.py`` (``ServeApp``, warm-up by row bucket,
                          the gallery put into the device cache)
  units_since, flops_per_unit, unwritten_bytes
                          image rows dispatched, ``flops.py``, the device
                          cache's rows nothing was written to
  sample, run_reference, compare, frame_of
                          ``check.py`` over ``reference/<name>.py`` and
                          ``reference/inputs.py``
"""

from __future__ import annotations

import importlib
import os
import time

from ...harness.spec import BENCH_DIR
from . import catalog, check, flops, traffic

VOCAB = os.path.join(BENCH_DIR, "assets", "vocab.txt")

sample = check.sample
compare = check.compare
frame_of = check.frame_of


def _reference(config: dict):
    return importlib.import_module(
        f"benchmark.reference.{config['reference']}")


def check_traffic(config: dict, traffic_file: dict) -> None:
    """Raises where a traffic file cannot be run under a configuration."""
    engine = config["engine"]
    buckets = {*engine["image_buckets"], *engine["throughput_buckets"]}
    if not set(traffic_file["row_buckets"]) <= buckets:
        raise ValueError(f"row_buckets {traffic_file['row_buckets']} are not "
                         f"all among the engine's {sorted(buckets)}")
    widest = max(int(kind["images"]) for kind in traffic_file["deck"])
    if widest > check.REFERENCE_ROWS:
        raise ValueError(f"a request of {widest} images is wider than the "
                         f"check's one program ({check.REFERENCE_ROWS})")


def assets(config: dict, traffic_file: dict, cache_dir: str) -> tuple:
    """(what has to be on disk before the schedule can be made, phase
    seconds): the image catalog's directory and the vocabulary."""
    t = time.monotonic()
    feature_root = catalog.ensure(
        traffic_file, config["model"]["v_feature_size"], cache_dir)
    return ({"feature_root": feature_root, "vocab": VOCAB},
            {"feature_store_s": time.monotonic() - t})


def schedule(traffic_file: dict, seed: int, seconds: float,
             assets: dict) -> dict:
    return traffic.schedule(traffic_file, seed, seconds,
                            traffic.load_words(assets["vocab"]))


def weights(config: dict, seed: int) -> tuple:
    """(the served tree on the device, its parameter count): float32, the
    type both configurations store, one jitted draw for the whole tree."""
    if config["engine"]["param_dtype"] != "float32":
        raise SystemExit("the vilbert family makes float32 weights; "
                         f"{config['name']} stores "
                         f"{config['engine']['param_dtype']}")
    # Imported here: it imports JAX, which ``run.py`` touches only after
    # the generator child is started.
    from ...harness import weights as tree_weights

    shapes = _reference(config).param_shapes(config["model"])
    return tree_weights.make(shapes, seed), tree_weights.count(shapes)


def boot(config: dict, traffic_file: dict, params, assets: dict,
         state_dir: str, rehearsal: bool) -> tuple:
    """(the running ``ServeApp``, phase seconds)."""
    from . import server

    labels_root = os.path.join(state_dir, "labels")
    server.write_label_maps(labels_root, config["model"])
    cfg = server.framework_config(config, state_dir, labels_root,
                                  assets["vocab"], rehearsal)
    return server.boot(cfg, params, assets["feature_root"],
                       traffic_file["row_buckets"],
                       traffic.gallery(traffic_file))


def units_since(app, since: float) -> float:
    """Image rows the scheduler dispatched since ``since`` (monotonic): each
    sample of ``vmt_batch_fill`` is a share of its bucket's rows."""
    from vilbert_multitask_tpu import obs

    fill = obs.BATCH_FILL
    rows = 0.0
    for labels in fill.series_counts():
        shares = fill.window_samples(time.monotonic() - since,
                                     bucket=labels[0])
        rows += sum(shares) * float(labels[0])
    return rows


def flops_per_unit(config: dict) -> int:
    return flops.forward_flops_per_row(config["model"], config["engine"])


def unwritten_bytes(app, config: dict) -> tuple:
    """(bytes the device holds reserved and unwritten, what to say of
    them): the device cache's rows that nothing was ever written to."""
    engine, model = config["engine"], config["model"]
    # One cached image: features as they are shipped (two bytes each under
    # a 16-bit compute type), float32 boxes, an int32 mask.
    wide = 2 if engine["compute_dtype"] in ("bfloat16", "float16") else 4
    row_bytes = engine["max_regions"] * (model["v_feature_size"] * wide + 24)
    entries = engine["device_input_cache_entries"]
    written = app.engine.input_cache_stats["entries"]
    return ((entries - written) * row_bytes,
            {"cache_entries": entries, "cache_entries_written": written})


def run_reference(config: dict, params, picked: list, assets: dict,
                  lower=None) -> list:
    return check.run_reference(config, _reference(config), params, picked,
                               assets["feature_root"], assets["vocab"],
                               lower=lower)
