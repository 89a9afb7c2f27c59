"""Native (C++) host data-path kernels, loaded via ctypes.

Compiled on first use with the system toolchain into
``<checkout>/.native_build/`` (or ``DINOV3_TPU_NATIVE_DIR``) — inside the
checkout, never the home directory, so a copy built from what git
commits finds or builds everything next to itself. All callers fall
back to the numpy implementations when the toolchain or the build is
unavailable (said loudly: a WARNING, and ``describe()`` names which arm
this process got), so the framework never *requires* the native path —
it is a throughput optimization for the host side of the input pipeline
(the device side is XLA/Pallas, see dinov3_tpu/ops).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

logger = logging.getLogger("dinov3")

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "normalize.cpp")
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False
_STATUS = "not loaded yet"


def _cache_dir() -> str:
    return os.environ.get(
        "DINOV3_TPU_NATIVE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".native_build"),
    )


def _build() -> str | None:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    out_dir = _cache_dir()
    os.makedirs(out_dir, exist_ok=True)
    global _STATUS
    so_path = os.path.join(out_dir, f"dinov3_native_{tag}.so")
    if os.path.exists(so_path):
        _STATUS = f"loaded the already-built {so_path}"
        return so_path
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC",
        "-o", so_path + ".tmp", _SRC,
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        _STATUS = f"build failed ({e}); numpy fallbacks in use"
        logger.warning("native build unavailable (%s); using numpy "
                       "fallbacks", e)
        return None
    os.replace(so_path + ".tmp", so_path)
    _STATUS = f"built {so_path} with g++"
    logger.info("built native kernels: %s", so_path)
    return so_path


def _load() -> ctypes.CDLL | None:
    global _LIB, _TRIED, _STATUS
    if _LIB is not None or _TRIED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if os.environ.get("DINOV3_TPU_NO_NATIVE"):
            _STATUS = "switched off by DINOV3_TPU_NO_NATIVE; numpy fallbacks"
            return None
        so = _build()
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        f32p = ctypes.POINTER(ctypes.c_float)
        lib.normalize_u8_to_f32.argtypes = [
            u8p, f32p, ctypes.c_int64, f32p, f32p,
        ]
        lib.normalize_u8_to_f32_hflip.argtypes = [
            u8p, f32p, ctypes.c_int64, ctypes.c_int64, f32p, f32p,
        ]
        lib.stack_crops_f32.argtypes = [
            ctypes.POINTER(f32p), f32p, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.color_jitter_f32.argtypes = [
            f32p, ctypes.c_int64, ctypes.POINTER(ctypes.c_int32),
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
        ]
        _LIB = lib
        return _LIB


def native_available() -> bool:
    return _load() is not None


def describe() -> str:
    """Which arm this process runs: built here, found built, or the
    numpy fallbacks (and why) — triggers the load."""
    _load()
    return _STATUS


def _scale_bias(mean, std):
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    scale = (1.0 / (255.0 * std)).astype(np.float32)
    bias = (-mean / std).astype(np.float32)
    return scale, bias


def normalize_image(
    arr_u8: np.ndarray, mean, std, hflip: bool = False
) -> np.ndarray | None:
    """[H, W, 3] uint8 -> normalized float32; None if native unavailable."""
    lib = _load()
    if lib is None:
        return None
    arr_u8 = np.ascontiguousarray(arr_u8)
    if arr_u8.dtype != np.uint8 or arr_u8.ndim != 3 or arr_u8.shape[2] != 3:
        return None
    h, w, _ = arr_u8.shape
    out = np.empty((h, w, 3), np.float32)
    scale, bias = _scale_bias(mean, std)
    f32p = ctypes.POINTER(ctypes.c_float)
    u8p = arr_u8.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
    if hflip:
        lib.normalize_u8_to_f32_hflip(
            u8p, out.ctypes.data_as(f32p), h, w,
            scale.ctypes.data_as(f32p), bias.ctypes.data_as(f32p),
        )
    else:
        lib.normalize_u8_to_f32(
            u8p, out.ctypes.data_as(f32p), h * w,
            scale.ctypes.data_as(f32p), bias.ctypes.data_as(f32p),
        )
    return out


def stack_crops(arrays: list[np.ndarray]) -> np.ndarray | None:
    """Stack same-shape fp32 arrays along a new axis 0 with one native
    memcpy loop; None if native unavailable or shapes/dtypes unsuitable."""
    lib = _load()
    if lib is None or not arrays:
        return None
    first = arrays[0]
    if first.dtype != np.float32:
        return None
    item = int(first.size)
    for a in arrays:
        if a.shape != first.shape or a.dtype != np.float32:
            return None
    contig = [np.ascontiguousarray(a) for a in arrays]
    out = np.empty((len(contig),) + first.shape, np.float32)
    f32p = ctypes.POINTER(ctypes.c_float)
    ptrs = (f32p * len(contig))(
        *[a.ctypes.data_as(f32p) for a in contig]
    )
    lib.stack_crops_f32(ptrs, out.ctypes.data_as(f32p), len(contig), item)
    return out


def color_jitter(
    arr_f32: np.ndarray,
    order,
    brightness: float | None,
    contrast: float | None,
    saturation: float | None,
    hue: float | None,
) -> np.ndarray | None:
    """In-place fused brightness/contrast/saturation/hue on a [H, W, 3]
    float32 array in [0, 255]; None if native unavailable. ``order`` is a
    permutation of 0..3; None factors skip that op."""
    lib = _load()
    if lib is None:
        return None
    if arr_f32.dtype != np.float32 or arr_f32.ndim != 3 \
            or arr_f32.shape[2] != 3 or not arr_f32.flags.c_contiguous:
        return None
    order_arr = np.asarray(order, np.int32)
    f32p = ctypes.POINTER(ctypes.c_float)
    lib.color_jitter_f32(
        arr_f32.ctypes.data_as(f32p),
        arr_f32.shape[0] * arr_f32.shape[1],
        order_arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        -1.0 if brightness is None else float(brightness),
        -1.0 if contrast is None else float(contrast),
        -1.0 if saturation is None else float(saturation),
        2.0 if hue is None else float(hue),  # outside [-0.5, 0.5] = skip
    )
    return arr_f32
