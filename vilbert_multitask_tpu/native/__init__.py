"""ctypes bindings for the native C++ runtime components (native/*.cpp).

Reference capability: the C++/CUDA layer the reference drives through
``maskrcnn_benchmark`` (NMS kernel + box selection, reference
worker.py:51,123-176) and fast feature IO. The library is built from
``native/vmt_native.cpp`` by ``make`` (mtime-aware: a no-op when the binary
is current) on first use in every process — the ``.so`` is untracked, and a
binary of unknown provenance is never loaded without make's say-so. Every
entry point has a pure JAX/numpy twin (ops/nms.py, features/store.py), so
the framework degrades gracefully when no toolchain is present —
``available()`` gates the fast path.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libvmt_native.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _build() -> bool:
    try:
        subprocess.run(["make", "-s"], cwd=_NATIVE_DIR, check=True,
                       capture_output=True, timeout=120)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if not _build():
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _load_failed = True
            return None
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        lib.vmt_nms.argtypes = [f32p, f32p, ctypes.c_int, ctypes.c_float, u8p]
        lib.vmt_nms.restype = ctypes.c_int
        lib.vmt_select_top_regions.argtypes = [
            f32p, f32p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_float, ctypes.c_float, ctypes.c_int, i32p, f32p, i32p,
            f32p,
        ]
        lib.vmt_select_top_regions.restype = ctypes.c_int
        lib.vmt_vlfr_header.argtypes = [ctypes.c_char_p] + [
            ctypes.POINTER(ctypes.c_int32)] * 4
        lib.vmt_vlfr_header.restype = ctypes.c_int
        lib.vmt_vlfr_read.argtypes = [ctypes.c_char_p, f32p, f32p]
        lib.vmt_vlfr_read.restype = ctypes.c_int
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def nms(boxes: np.ndarray, scores: np.ndarray,
        iou_threshold: float = 0.5) -> np.ndarray:
    """Greedy NMS → (N,) bool keep mask; ops/nms.py:nms_mask semantics."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable (no compiler?)")
    boxes = np.ascontiguousarray(boxes, np.float32)
    scores = np.ascontiguousarray(scores, np.float32)
    keep = np.zeros((boxes.shape[0],), np.uint8)
    lib.vmt_nms(boxes, scores, boxes.shape[0], iou_threshold, keep)
    return keep.astype(bool)


def select_top_regions(
    boxes: np.ndarray,
    class_scores: np.ndarray,
    num_keep: int = 100,
    iou_threshold: float = 0.5,
    conf_threshold: float = 0.0,
    background: bool = False,
) -> Tuple[np.ndarray, int, np.ndarray, np.ndarray, np.ndarray]:
    """Native twin of ops/nms.py:select_top_regions (same return layout)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable (no compiler?)")
    boxes = np.ascontiguousarray(boxes, np.float32)
    class_scores = np.ascontiguousarray(class_scores, np.float32)
    n, c = class_scores.shape
    keep_indices = np.zeros((num_keep,), np.int32)
    max_conf = np.zeros((n,), np.float32)
    objects = np.zeros((num_keep,), np.int32)
    cls_prob = np.zeros((num_keep,), np.float32)
    num_valid = lib.vmt_select_top_regions(
        boxes, class_scores, n, c, num_keep, iou_threshold, conf_threshold,
        int(background), keep_indices, max_conf, objects, cls_prob,
    )
    return keep_indices, num_valid, max_conf, objects, cls_prob


def read_vlfr(path: str):
    """Fast .vlfr loader (features/store.py format) → RegionFeatures."""
    from vilbert_multitask_tpu.features.pipeline import RegionFeatures

    lib = _load()
    if lib is None:
        raise RuntimeError("native library unavailable (no compiler?)")
    n = ctypes.c_int32()
    d = ctypes.c_int32()
    w = ctypes.c_int32()
    h = ctypes.c_int32()
    rc = lib.vmt_vlfr_header(path.encode(), ctypes.byref(n), ctypes.byref(d),
                             ctypes.byref(w), ctypes.byref(h))
    if rc != 0:
        raise IOError(f"vmt_vlfr_header({path}) failed with {rc}")
    feats = np.empty((n.value, d.value), np.float32)
    boxes = np.empty((n.value, 4), np.float32)
    rc = lib.vmt_vlfr_read(path.encode(), feats, boxes)
    if rc != 0:
        raise IOError(f"vmt_vlfr_read({path}) failed with {rc}")
    return RegionFeatures(features=feats, boxes=boxes, image_width=w.value,
                          image_height=h.value, num_boxes=n.value)
