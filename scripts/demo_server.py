"""Self-contained demo server: tiny model + synthetic assets + full web UI.

Boots the complete serving stack (engine, queue, worker, HTTP, websocket,
browser frontend) on CPU with a tiny random-weight model and generated demo
images/features, so the end-to-end product — image grid, task gating,
submit, terminal stream, per-task result rendering — can be driven in a
browser with zero external assets:

    python scripts/demo_server.py            # http://127.0.0.1:8400/

The real deployment is ``python -m vilbert_multitask_tpu.serve.app`` with a
converted checkpoint, the bert vocab, and real precomputed features.
"""

import os
import sys
import threading

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
from PIL import Image, ImageDraw

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from vilbert_multitask_tpu.config import (  # noqa: E402
    EngineConfig,
    FrameworkConfig,
    ServingConfig,
    ViLBertConfig,
)
from vilbert_multitask_tpu.features.pipeline import RegionFeatures  # noqa: E402
from vilbert_multitask_tpu.features.store import save_reference_npy  # noqa: E402
from vilbert_multitask_tpu.serve.app import ServeApp  # noqa: E402

ROOT = os.environ.get("VMT_DEMO_ROOT", "/tmp/vmt_demo")


def make_assets() -> None:
    os.makedirs(f"{ROOT}/media/demo", exist_ok=True)
    os.makedirs(f"{ROOT}/features", exist_ok=True)
    rng = np.random.default_rng(0)
    colors = [(180, 60, 60), (60, 140, 200), (90, 170, 90), (200, 170, 60)]
    for i, name in enumerate(["img_a", "img_b", "img_c", "img_d"]):
        img = Image.new("RGB", (320, 240), colors[i])
        d = ImageDraw.Draw(img)
        d.rectangle([40 + 30 * i, 40, 150 + 30 * i, 150],
                    outline=(255, 255, 255), width=4)
        d.text((10, 10), name, fill=(255, 255, 255))
        img.save(f"{ROOT}/media/demo/{name}.jpg")
        boxes = np.array([[30, 30, 120, 120], [100, 60, 220, 180],
                          [20, 100, 160, 230], [150, 20, 300, 140],
                          [60, 60, 200, 200]], np.float32)
        region = RegionFeatures(
            features=rng.normal(size=(5, 32)).astype(np.float32),
            boxes=boxes, image_width=320, image_height=240)
        save_reference_npy(f"{ROOT}/features/{name}.npy", region, name)


def main() -> None:
    make_assets()
    cfg = FrameworkConfig(
        model=ViLBertConfig().tiny(),
        engine=EngineConfig(max_text_len=16, max_regions=9, num_features=8,
                            image_buckets=(1, 2, 4),
                            compute_dtype="float32",
                            # CPU demo: XLA attention (the Pallas kernels
                            # compile only for a TPU).
                            use_pallas_coattention=False,
                            use_pallas_self_attention=False),
        serving=ServingConfig(
            queue_db_path=f"{ROOT}/queue.sqlite3",
            results_db_path=f"{ROOT}/results.sqlite3",
            media_root=f"{ROOT}/media",
            http_port=int(os.environ.get("VMT_DEMO_PORT", "8400")),
            ws_port=int(os.environ.get("VMT_DEMO_WS_PORT", "8401"))),
    )
    app = ServeApp(cfg, feature_root=f"{ROOT}/features")
    print("compiling shape buckets...")
    app.engine.warmup(buckets=(1, 2))
    app.start()
    print(f"READY http://127.0.0.1:{app.http_port}/  "
          f"ws={app.ws.bound_port}  (tiny random weights — answers are "
          f"structural, not meaningful)", flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        app.stop()


if __name__ == "__main__":
    main()
