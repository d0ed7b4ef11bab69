"""Precomputed region-feature store.

Per BASELINE.json, the GPU Faster R-CNN in the serving loop (reference
worker.py:59-223) is replaced by a precomputed-feature loader. Two formats:

1. The reference ``.npy`` schema — a pickled dict per image with keys
   ``image_id, features[N,2048], bbox[N,4], num_boxes, objects, cls_prob,
   image_width, image_height`` (written at reference worker.py:209-216) —
   so feature dumps produced by the reference tooling drop straight in.
2. A packed little-endian binary format (``.vlfr``) with a fixed header,
   designed for mmap-friendly zero-copy reads; the C++ fast loader in
   ``native/feature_store.cpp`` reads it without the pickle machinery.

The store is keyed the way the reference keys features: by image-file
basename without extension (worker.py:210-211).
"""

from __future__ import annotations

import os
import struct
import time
from collections import OrderedDict
from typing import Dict, Iterable

import numpy as np

from vilbert_multitask_tpu import obs
from vilbert_multitask_tpu.features.pipeline import RegionFeatures

_VLFR_MAGIC = b"VLFR\x01"


def load_reference_npy(path: str) -> RegionFeatures:
    """Read one image's features in the reference ``.npy`` dict schema."""
    raw = np.load(path, allow_pickle=True).item()
    cls_prob = np.asarray(raw.get("cls_prob", ()), np.float32)
    return RegionFeatures(
        features=np.asarray(raw["features"], np.float32),
        boxes=np.asarray(raw["bbox"], np.float32),
        image_width=int(raw["image_width"]),
        image_height=int(raw["image_height"]),
        num_boxes=int(raw.get("num_boxes", len(raw["features"]))),
        cls_prob=cls_prob if cls_prob.size else None,
    )


def save_reference_npy(path: str, region: RegionFeatures, image_id: str,
                       objects: np.ndarray | None = None,
                       cls_prob: np.ndarray | None = None) -> None:
    """Write the reference schema (what the offline extractor emits)."""
    info = {
        "image_id": image_id,
        "features": np.asarray(region.features, np.float32),
        "bbox": np.asarray(region.boxes, np.float32),
        "num_boxes": int(region.num_boxes),
        "image_width": int(region.image_width),
        "image_height": int(region.image_height),
        "objects": objects if objects is not None else np.zeros((0,), np.int64),
        "cls_prob": (cls_prob if cls_prob is not None
                     else region.cls_prob if region.cls_prob is not None
                     else np.zeros((0, 0), np.float32)),
    }
    np.save(path, info)


def save_vlfr(path: str, region: RegionFeatures) -> None:
    """Packed binary: header(magic, n, d, w, h) + f32 features + f32 boxes.

    The format carries the SERVING fields only — ``cls_prob`` (the MRM
    pretraining target) is dropped; a pretraining run against a .vlfr
    store falls back to uniform targets, so warn when it's discarded here.
    """
    if region.cls_prob is not None:
        import logging

        logging.getLogger(__name__).warning(
            ".vlfr stores no cls_prob: %s loses the detector class "
            "distribution — MRM pretraining against this store will use "
            "uniform targets (keep the .npy for pretraining data)", path)
    feats = np.ascontiguousarray(region.features, dtype="<f4")
    boxes = np.ascontiguousarray(region.boxes, dtype="<f4")
    n, d = feats.shape
    with open(path, "wb") as f:
        f.write(_VLFR_MAGIC)
        f.write(struct.pack("<IIII", n, d, int(region.image_width),
                            int(region.image_height)))
        f.write(feats.tobytes())
        f.write(boxes.tobytes())


def load_vlfr(path: str) -> RegionFeatures:
    with open(path, "rb") as f:
        magic = f.read(5)
        if magic != _VLFR_MAGIC:
            raise ValueError(f"{path}: not a VLFR file")
        n, d, w, h = struct.unpack("<IIII", f.read(16))
        feats = np.frombuffer(f.read(n * d * 4), dtype="<f4").reshape(n, d)
        boxes = np.frombuffer(f.read(n * 4 * 4), dtype="<f4").reshape(n, 4)
    return RegionFeatures(features=feats.copy(), boxes=boxes.copy(),
                          image_width=w, image_height=h, num_boxes=n)


def image_key(image_path: str) -> str:
    """Image path → store key (basename sans extension, worker.py:210-211)."""
    return os.path.basename(image_path).split(".")[0]


def stat_identity(path: str, st: os.stat_result) -> str:
    """The identity string of a file from a ``stat`` already taken."""
    return f"{path}:{st.st_mtime_ns}:{st.st_size}"


def file_identity(path: str) -> str:
    """Content-stable cache identity for a file: path + mtime + size."""
    return stat_identity(path, os.stat(path))


class FeatureStore:
    """Directory-backed feature store with an LRU cache.

    Fixes a reference inefficiency while keeping its contract: the reference
    re-reads label pickles and feature data per request (SURVEY.md §2.4);
    here repeated images hit the in-memory LRU.
    """

    def __init__(self, root: str, max_cached: int = 256):
        self.root = root
        self.max_cached = max_cached
        self._cache: "OrderedDict[str, RegionFeatures]" = OrderedDict()
        # Probe (and if needed build) the native reader at construction —
        # boot-time cost, so the first request never pays the g++ build —
        # but only when this store actually holds .vlfr files.
        self._native_ok = False
        if self._has_vlfr():
            from vilbert_multitask_tpu import native

            self._native_ok = native.available()

    def _has_vlfr(self) -> bool:
        try:
            with os.scandir(self.root) as it:
                return any(e.name.endswith(".vlfr") for e in it)
        except OSError:
            return False

    def _locate(self, image_path: str) -> tuple[str, str]:
        """(feature file, its content identity) for an image: ONE ``stat``
        where the reference ``.npy`` is there (a second for a ``.vlfr``
        store), which both finds the file and dates it. Every system call
        is a hand-over of the interpreter lock in a busy server, and the
        intake makes this call for every image of every request."""
        key = image_key(image_path)
        for ext in (".npy", ".vlfr"):
            path = os.path.join(self.root, key + ext)
            try:
                return path, stat_identity(path, os.stat(path))
            except (OSError, ValueError):  # what os.path.exists calls absent
                continue
        raise FileNotFoundError(
            f"no feature file for key '{key}' under {self.root} (.npy/.vlfr)"
        )

    def identity(self, image_path: str) -> str:
        """Content-stable identity for this image's features: resolved file
        path + mtime + size. Cache layers (the host LRU here, the engine's
        device input cache) key on this so a replaced/edited feature file
        is a cache MISS, never silently served stale. One ``stat`` and no
        read: the engine's intake asks it of every image FIRST, asks its
        device cache for that identity, and calls :meth:`fetch` only for
        what the device does not hold (engine.prepare_from_store)."""
        return self._locate(image_path)[1]

    def fetch(self, image_path: str) -> tuple[RegionFeatures, str]:
        """(features, content identity) — the identity is captured BEFORE
        the read, so a file replaced mid-request can at worst bind an OLD
        key to NEW content (which the next request's fresh stat misses and
        re-reads), never a new key to stale content.

        The engine binds a device row to THIS key, whatever identity its
        intake probed with a moment earlier; the same holds for the late
        read of a row that left the device between intake and pack."""
        path, key = self._locate(image_path)
        if key in self._cache:
            self._cache.move_to_end(key)
            obs.FEATURE_STORE_HITS.inc()
            return self._cache[key], key
        t_load = time.perf_counter()
        if path.endswith(".npy"):
            region = load_reference_npy(path)
        elif self._native_ok:
            from vilbert_multitask_tpu import native

            region = native.read_vlfr(path)
        else:
            region = load_vlfr(path)
        obs.FEATURE_STORE_MISSES.inc()
        obs.FEATURE_STORE_LOAD_SECONDS.inc(time.perf_counter() - t_load)
        obs.FEATURE_STORE_READ_BYTES.inc(os.path.getsize(path))
        self._cache[key] = region
        if len(self._cache) > self.max_cached:
            self._cache.popitem(last=False)
        return region, key

    def get(self, image_path: str) -> RegionFeatures:
        return self.fetch(image_path)[0]

    def get_batch(self, image_paths: Iterable[str]) -> list[RegionFeatures]:
        return [self.get(p) for p in image_paths]
