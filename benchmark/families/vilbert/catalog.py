"""The image catalog: region-feature files in the reference ``.npy`` schema
(what the offline detector emits), made once per checkout from the traffic
file's ``catalog_seed`` and reused while a manifest of names and sizes
matches. ``g####`` are the gallery a deployment has preloaded; ``u#####``
are users' uploads, new to the server when first asked about."""

from __future__ import annotations

import json
import os

import numpy as np

BOXES = 100
IMAGE_W, IMAGE_H = 640, 480


def _names(traffic: dict) -> list:
    return ([f"g{i:04d}" for i in range(int(traffic["gallery_images"]))]
            + [f"u{i:05d}" for i in range(int(traffic["upload_pool"]))])


def _write_image(path: str, name: str, rng, feature_size: int) -> None:
    x1 = rng.random(BOXES) * (IMAGE_W - 32)
    y1 = rng.random(BOXES) * (IMAGE_H - 32)
    boxes = np.stack([x1, y1,
                      np.minimum(x1 + 16 + rng.random(BOXES) * IMAGE_W / 4,
                                 IMAGE_W),
                      np.minimum(y1 + 16 + rng.random(BOXES) * IMAGE_H / 4,
                                 IMAGE_H)], axis=1).astype(np.float32)
    # fc6 region features come out of a ReLU: non-negative, mostly small.
    features = np.maximum(
        rng.standard_normal((BOXES, feature_size), np.float32), 0.0)
    np.save(path, {
        "image_id": name, "features": features, "bbox": boxes,
        "num_boxes": BOXES, "image_width": IMAGE_W, "image_height": IMAGE_H,
        "objects": np.zeros((0,), np.int64),
        "cls_prob": np.zeros((0, 0), np.float32)})


def ensure(traffic: dict, feature_size: int, cache_dir: str) -> str:
    """The catalog's directory, written now if it is not there whole."""
    seed = int(traffic["catalog_seed"])
    root = os.path.join(cache_dir, "features",
                        f"seed{seed}-w{feature_size}")
    manifest_path = os.path.join(root, "manifest.json")
    names = _names(traffic)
    try:
        with open(manifest_path, encoding="utf-8") as f:
            manifest = json.load(f)
    except (OSError, ValueError):
        manifest = {}
    os.makedirs(root, exist_ok=True)
    written = []
    for index, name in enumerate(names):
        path = os.path.join(root, name + ".npy")
        size = manifest.get(name)
        if size is not None and os.path.exists(path) \
                and os.path.getsize(path) == size:
            continue
        _write_image(path, name,
                     np.random.default_rng([seed, feature_size, index]),
                     feature_size)
        manifest[name] = os.path.getsize(path)
        written.append(path)
    if written:
        with open(manifest_path, "w", encoding="utf-8") as f:
            json.dump(manifest, f)
        # Gigabytes of new files: have the kernel write them back now, in
        # the set-up of the checkout's first run, and not under the windows
        # of this run and the next.
        for path in written:
            fd = os.open(path, os.O_RDONLY)
            try:
                os.fsync(fd)
            finally:
                os.close(fd)
    return root


def feature_path(root: str, image_name: str) -> str:
    return os.path.join(root, image_name.split(".")[0] + ".npy")
