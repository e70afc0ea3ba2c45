"""ctypes binding for the native int8 GEMM (native/quant_gemm.cpp).

XLA's CPU backend has no int8 dot emitter (an s8 dot_general
materializes an s32 weight copy and runs slower than fp32 — measured in
docs/design.md "Quantized serving"), so the CPU arm of the quantized
serving path routes the hot matmul through this library's AVX512-VNNI
kernel. Same degrade-gracefully contract as native_etl: `available()`
is False when the .so is missing and cannot be built, and `int8_gemm`
falls back to a numpy int32 matmul — correct everywhere, fast where the
hardware allows. Dispatch between this path, Pallas, and plain XLA is
decided by a measured probe in ops/pallas_kernels.quant_matmul (the
LRN-style honesty rule), never assumed.
"""
from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import sys
from typing import Optional

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libdl4jtpu_quant.so")
_ABI = 3
_lib: Optional[ctypes.CDLL] = None
_tried = False
_ffi_registered = False
FFI_TARGET = "dl4jtpu_int8_gemm"


def _build(force: bool = False) -> bool:
    src = os.path.join(_NATIVE_DIR, "quant_gemm.cpp")
    if not os.path.exists(src):
        return False
    try:
        # PYTHON: the Makefile asks THIS interpreter's jax for the XLA
        # FFI headers the handler is compiled against.
        cmd = ["make", "-C", _NATIVE_DIR, os.path.basename(_LIB_PATH),
               f"PYTHON={sys.executable}"] + (["-B"] if force else [])
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        return os.path.exists(_LIB_PATH)
    except (subprocess.SubprocessError, OSError) as e:
        log.warning("native quant build unavailable (%s): %s", e,
                    (getattr(e, "stderr", None) or b"")[-500:])
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    i8p = ctypes.POINTER(ctypes.c_int8)
    i32p = ctypes.POINTER(ctypes.c_int32)
    lib.int8_gemm.argtypes = [i8p, i8p, i32p, ctypes.c_int64,
                              ctypes.c_int64, ctypes.c_int64]
    lib.int8_gemm_vnni_available.restype = ctypes.c_int32
    lib.quant_abi_version.restype = ctypes.c_int32
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if not os.path.exists(_LIB_PATH) and not _build():
        return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
        # AttributeError here means a stale/foreign .so — rebuild once
        # (the etl loader's protocol; silent numpy fallback would be a
        # quiet serving-throughput regression).
        if lib.quant_abi_version() != _ABI:
            log.info("native quant ABI mismatch; rebuilding")
            if not _build(force=True):
                return None
            lib = ctypes.CDLL(_LIB_PATH)
            if lib.quant_abi_version() != _ABI:
                log.warning("native quant still ABI-mismatched after "
                            "rebuild; numpy fallback")
                return None
        _lib = _bind(lib)
    except (OSError, AttributeError) as e:
        log.info("native quant load failed (%s); numpy fallback", e)
    return _lib


def available() -> bool:
    return _load() is not None


def ffi_register() -> None:
    """Register the library's XLA typed-FFI handler as the CPU
    custom-call target `dl4jtpu_int8_gemm` (once per process): a
    registered custom call hands the kernel raw XLA buffer pointers
    in-process. Raises when the library is not available — callers
    gate on `available()` first."""
    global _ffi_registered
    if _ffi_registered:
        return
    lib = _load()
    if lib is None:
        raise RuntimeError(
            "native int8 GEMM library unavailable (native/"
            "libdl4jtpu_quant.so missing and could not be built)")
    import jax
    jax.ffi.register_ffi_target(
        FFI_TARGET, jax.ffi.pycapsule(lib.dl4jtpu_int8_gemm_ffi),
        platform="cpu")
    _ffi_registered = True


def vnni() -> bool:
    """True when the loaded library will actually run the VNNI kernel
    (compiled in AND the CPU supports it), so a measurement can record
    which hardware path it ran."""
    lib = _load()
    return bool(lib is not None and lib.int8_gemm_vnni_available())


def int8_gemm(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """out[b, n] = sum_k x[b, k] * w[n, k] in exact int32 arithmetic.

    `x` is s8 [B, K]; `w` is s8 [N, K] (weights stored transposed so
    each output channel is a unit-stride row — the layout quantize_tree
    produces). The host-side entry to the same kernel the quant_matmul
    native arm reaches through its XLA custom call."""
    lib = _load()
    x = np.ascontiguousarray(x, np.int8)
    w = np.ascontiguousarray(w, np.int8)
    if x.ndim != 2 or w.ndim != 2 or x.shape[1] != w.shape[1]:
        raise ValueError(
            f"int8_gemm needs [B,K] x [N,K], got {x.shape} x {w.shape}")
    if lib is None:
        return x.astype(np.int32) @ w.astype(np.int32).T
    out = np.empty((x.shape[0], w.shape[0]), np.int32)
    i8p = ctypes.POINTER(ctypes.c_int8)
    lib.int8_gemm(x.ctypes.data_as(i8p), w.ctypes.data_as(i8p),
                  out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                  x.shape[0], x.shape[1], w.shape[0])
    return out
