"""ctypes binding for the native host-ETL library (native/etl.cpp).

The library is OPTIONAL: `available()` is False when the shared object
is missing and no C++ toolchain can build it, and every consumer
(normalizers, fetchers) falls back to its numpy path — the same
degrade-gracefully contract the reference uses for its optional cuDNN
helper jar (ConvolutionLayer.java:66-77 reflective load)."""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdl4jtpu_etl.so")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _build(force: bool = False) -> bool:
    src = os.path.join(_NATIVE_DIR, "etl.cpp")
    if not os.path.exists(src):
        return False
    try:
        cmd = ["make", "-C", _NATIVE_DIR, os.path.basename(_LIB_PATH)] \
            + (["-B"] if force else [])
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return os.path.exists(_LIB_PATH)
    except (subprocess.SubprocessError, OSError) as e:
        log.info("native ETL build unavailable (%s); using numpy paths", e)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not os.path.exists(_LIB_PATH) and not _build():
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        # AttributeError here means a stale/foreign .so — fall back.
        if lib.etl_abi_version() != 2:
            # stale checkout artifact: rebuild in place and reload once
            # (silently dropping to numpy would be a large quiet ETL
            # regression on every install that predates the ABI bump)
            log.info("native ETL ABI mismatch; rebuilding")
            if not _build(force=True):
                log.warning("native ETL rebuild failed; using numpy paths")
                return None
            lib = ctypes.CDLL(_LIB_PATH)
            if lib.etl_abi_version() != 2:
                log.warning("native ETL still ABI-mismatched after "
                            "rebuild; using numpy paths")
                return None
        f32p = ctypes.POINTER(ctypes.c_float)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.u8_to_f32_scaled.argtypes = [u8p, f32p, ctypes.c_int64,
                                         ctypes.c_float, ctypes.c_float,
                                         ctypes.c_float]
        lib.f32_standardize.argtypes = [f32p, ctypes.c_int64,
                                        ctypes.c_int64, f32p, f32p]
        lib.parse_csv_floats.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                         ctypes.c_char, f32p,
                                         ctypes.c_int64]
        lib.parse_csv_floats.restype = ctypes.c_int64
        lib.one_hot_f32.argtypes = [i32p, f32p, ctypes.c_int64,
                                    ctypes.c_int64]
        lib.gather_rows_f32.argtypes = [f32p, i32p, f32p, ctypes.c_int64,
                                        ctypes.c_int64]
        lib.u8_resize_bilinear_hwc.argtypes = [
            u8p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, u8p,
            ctypes.c_int64, ctypes.c_int64]
        lib.etl_set_omp_threads.argtypes = [ctypes.c_int]
        _lib = lib
    except (OSError, AttributeError) as e:
        log.info("native ETL load failed (%s); using numpy paths", e)
    return _lib


def available() -> bool:
    return _load() is not None


def _fptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def u8_to_f32_scaled(src: np.ndarray, max_pixel: float = 255.0,
                     min_range: float = 0.0,
                     max_range: float = 1.0) -> np.ndarray:
    """uint8 → scaled float32 (ImagePreProcessingScaler hot path)."""
    lib = _load()
    src = np.ascontiguousarray(src, np.uint8)
    if lib is None:
        x = src.astype(np.float32) / max_pixel
        return x * (max_range - min_range) + min_range
    out = np.empty(src.shape, np.float32)
    lib.u8_to_f32_scaled(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), _fptr(out),
        src.size, max_pixel, min_range, max_range)
    return out


def standardize(data: np.ndarray, mean: np.ndarray,
                std: np.ndarray) -> np.ndarray:
    """(x - mean)/std over the trailing feature axis, native when
    possible (NormalizerStandardize hot path). Returns a new array."""
    lib = _load()
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    c = np.asarray(data).shape[-1]
    if mean.shape != (c,) or std.shape != (c,):
        # the numpy path would raise a broadcast error; the native kernel
        # would read out of bounds — reject loudly either way.
        raise ValueError(f"standardize: feature axis {c} != stats length "
                         f"{mean.shape[0]}")
    if lib is None:
        return ((np.asarray(data) - mean) / std).astype(np.float32)
    out = np.array(data, np.float32, order="C")  # exactly one owned copy
    lib.f32_standardize(_fptr(out), out.size // c, c, _fptr(mean),
                        _fptr(std))
    return out


def parse_csv_floats(text: bytes | str, delimiter: str = ",",
                     max_out: Optional[int] = None) -> np.ndarray:
    """Parse all floats out of a CSV chunk (CSVRecordReader fast path)."""
    lib = _load()
    if isinstance(text, str):
        text = text.encode()
    if lib is None:
        # strtof-equivalent: parse the longest numeric PREFIX of each
        # token ('7.5abc' → 7.5), treat spaces as separators, skip tokens
        # with no numeric prefix — exactly what the native kernel does.
        import re
        num = re.compile(
            rb"[+-]?(?:\d+\.?\d*|\.\d+)(?:[eE][+-]?\d+)?")
        out = []
        for chunk in re.split(rb"[\n\r \t]|" + re.escape(delimiter.encode()),
                              text):
            m = num.match(chunk)
            if m:
                out.append(float(m.group(0)))
        return np.array(out, np.float32)
    cap = max_out if max_out is not None else len(text) // 2 + 1
    out = np.empty(cap, np.float32)
    n = lib.parse_csv_floats(text, len(text), delimiter.encode(),
                             _fptr(out), cap)
    return out[:n]


def gather_rows(table: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """out[i] = table[idx[i]] (host-side batch assembly for
    embedding-style lookups). Indices must be in range."""
    lib = _load()
    table = np.ascontiguousarray(table, np.float32)
    idx = np.ascontiguousarray(idx, np.int32)
    if idx.ndim != 1 or table.ndim != 2:
        raise ValueError("gather_rows needs 1-D idx over a 2-D table")
    if idx.size and (idx.min() < 0 or idx.max() >= table.shape[0]):
        raise IndexError("gather_rows index out of range")
    if lib is None:
        return table[idx]
    out = np.empty((idx.shape[0], table.shape[1]), np.float32)
    lib.gather_rows_f32(
        _fptr(table), idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        _fptr(out), idx.shape[0], table.shape[1])
    return out


def set_omp_threads(n: int) -> None:
    """Cap the CALLING thread's OpenMP team for the native kernels. Pool
    workers that parallelize at the image level pass 1 to avoid nesting
    two parallelism layers (per-thread OpenMP ICV, so each worker sets
    its own)."""
    lib = _load()
    if lib is not None:
        lib.etl_set_omp_threads(int(n))


def resize_bilinear(img: np.ndarray, out_h: int, out_w: int) -> np.ndarray:
    """HWC uint8 bilinear resize with half-pixel centers (the
    ImageRecordReader scale step; matches OpenCV INTER_LINEAR, which
    DataVec's NativeImageLoader uses)."""
    lib = _load()
    img = np.ascontiguousarray(img, np.uint8)
    if img.ndim != 3:
        raise ValueError(f"resize_bilinear needs [H,W,C], got {img.shape}")
    h, w, c = img.shape
    if (h, w) == (out_h, out_w):
        return img
    if lib is None:
        # numpy fallback: same half-pixel-center sampling
        fy = np.clip((np.arange(out_h) + 0.5) * (h / out_h) - 0.5, 0, None)
        fx = np.clip((np.arange(out_w) + 0.5) * (w / out_w) - 0.5, 0, None)
        y0 = np.minimum(fy.astype(np.int64), h - 1)
        x0 = np.minimum(fx.astype(np.int64), w - 1)
        y1 = np.minimum(y0 + 1, h - 1)
        x1 = np.minimum(x0 + 1, w - 1)
        wy = (fy - y0)[:, None, None]
        wx = (fx - x0)[None, :, None]
        f = img.astype(np.float32)
        top = f[y0][:, x0] * (1 - wx) + f[y0][:, x1] * wx
        bot = f[y1][:, x0] * (1 - wx) + f[y1][:, x1] * wx
        return (top * (1 - wy) + bot * wy + 0.5).astype(np.uint8)
    out = np.empty((out_h, out_w, c), np.uint8)
    u8 = ctypes.POINTER(ctypes.c_uint8)
    lib.u8_resize_bilinear_hwc(img.ctypes.data_as(u8), h, w, c,
                               out.ctypes.data_as(u8), out_h, out_w)
    return out


def one_hot(labels: np.ndarray, classes: int) -> np.ndarray:
    """1-D int labels → [n, classes] one-hot; out-of-range labels
    (negative or >= classes) produce all-zero rows on BOTH paths."""
    lib = _load()
    labels = np.ascontiguousarray(labels, np.int32)
    if labels.ndim != 1:
        raise ValueError(f"one_hot needs 1-D labels, got {labels.shape}")
    if lib is None:
        out = np.zeros((labels.shape[0], classes), np.float32)
        valid = (labels >= 0) & (labels < classes)
        out[np.nonzero(valid)[0], labels[valid]] = 1.0
        return out
    out = np.empty((labels.shape[0], classes), np.float32)
    lib.one_hot_f32(
        labels.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), _fptr(out),
        labels.shape[0], classes)
    return out
