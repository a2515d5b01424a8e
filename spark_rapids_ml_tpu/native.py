#
# Native host-staging bindings — loads native/staging.cpp (the analog of
# the reference's native memory layer: `_concat_and_free`/reserved-memory
# staging utils.py:358-522 and numpy_allocator.py's C hooks) via ctypes,
# building the shared library on first use with the host's g++ — and
# again whenever the one on disk was not built from this source on this
# machine (`_build_key`).  Every entry point has a numpy fallback, so the
# package works without a compiler (`status()` says which path runs);
# the native path parallelizes the pad/cast/pack/densify loops that feed
# `jax.device_put`.
#
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

from .utils import get_logger
from .telemetry.locks import named_lock

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO_ROOT, "native", "staging.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
_LIB_PATH = os.path.join(_BUILD_DIR, "libstaging.so")
_KEY_PATH = _LIB_PATH + ".key"

_lock = named_lock("native_build")
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_fallback_reason = ""


_BUILD_TIMEOUT_S = 300


class NativeBuildTimeout(RuntimeError):
    """The native staging build's compiler hung past the timeout.  Unlike
    a missing g++ (an expected environment, silently falls back to numpy),
    a HUNG compiler is a real fault worth surfacing loudly — and the bare
    `TimeoutExpired` loses the command line and any partial stderr, which
    is exactly what's needed to debug it."""


_CXXFLAGS = (
    "-O3", "-march=native", "-fopenmp", "-shared", "-fPIC", "-std=c++17",
)


def _read(path: str, mode: str = "rb"):
    try:
        with open(path, mode) as f:
            return f.read()
    except OSError:
        return b"" if "b" in mode else ""


def _build_key() -> str:
    """What a usable libstaging.so was built from and where: the source,
    the flags, this host's CPU feature flags (`-march=native` bakes them
    in) and this boot of this machine.  A library copied in beside the
    tree from another machine — build outputs are git-ignored but tools
    that copy the directory carry them — never matches, so it is rebuilt
    rather than dlopen'ed."""
    import hashlib

    cpu_flags = next(
        (
            ln for ln in _read("/proc/cpuinfo", "r").splitlines()
            if ln.startswith(("flags", "Features"))
        ),
        "",
    )
    h = hashlib.sha256()
    for part in (
        _read(_SRC),
        " ".join(_CXXFLAGS).encode(),
        cpu_flags.encode(),
        _read("/proc/sys/kernel/random/boot_id"),
    ):
        h.update(part)
        h.update(b"\0")
    return h.hexdigest()


def _build() -> bool:
    global _load_failed, _fallback_reason
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # compile to a process-unique temp path and rename into place so
    # concurrent builders never dlopen a half-written library
    tmp_path = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXXFLAGS, _SRC, "-o", tmp_path]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=_BUILD_TIMEOUT_S
        )
    except subprocess.TimeoutExpired as e:
        stderr = e.stderr or b""
        if isinstance(stderr, bytes):
            stderr = stderr.decode(errors="replace")
        # latch the failure like every other build/load path: without
        # this, each subsequent staging call re-runs the full hung
        # compile and pays the timeout again
        _load_failed = True
        raise NativeBuildTimeout(
            f"native staging build timed out after {_BUILD_TIMEOUT_S}s: "
            f"`{' '.join(cmd)}`"
            + (f"; partial stderr: {stderr[-500:]}" if stderr else "")
        ) from e
    except OSError as e:  # g++ missing
        _fallback_reason = f"build unavailable ({e})"
        get_logger("spark_rapids_ml_tpu.native").warning(
            f"native staging {_fallback_reason}; using numpy fallback"
        )
        return False
    if proc.returncode != 0:
        _fallback_reason = f"build failed: {proc.stderr[-200:].strip()}"
        get_logger("spark_rapids_ml_tpu.native").warning(
            f"native staging build failed; using numpy fallback:\n{proc.stderr[-500:]}"
        )
        return False
    os.replace(tmp_path, _LIB_PATH)
    with open(_KEY_PATH, "w") as f:
        f.write(_build_key())
    return True


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed, _fallback_reason
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if (
            not os.path.exists(_LIB_PATH)
            or _read(_KEY_PATH, "r") != _build_key()
        ):
            if not _build():
                _load_failed = True
                return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            _fallback_reason = f"load failed ({e})"
            get_logger("spark_rapids_ml_tpu.native").warning(
                f"native staging {_fallback_reason}; using numpy fallback"
            )
            _load_failed = True
            return None
        i64, f32p, f64p = ctypes.c_int64, ctypes.POINTER(ctypes.c_float), \
            ctypes.POINTER(ctypes.c_double)
        pp = ctypes.POINTER(ctypes.c_void_p)
        for name, argtypes in {
            "pad_cast_f64_f32": [f64p, i64, i64, i64, f32p],
            "pad_copy_f32": [f32p, i64, i64, i64, f32p],
            "pad_copy_f64": [f64p, i64, i64, i64, f64p],
            "pad_cast_f32_f64": [f32p, i64, i64, i64, f64p],
            "pack_rows_f64_f32": [pp, i64, i64, i64, f32p],
            "pack_rows_f32_f32": [pp, i64, i64, i64, f32p],
            "pack_rows_f64_f64": [pp, i64, i64, i64, f64p],
            "gather_strided_f64_f32": [f64p, i64, i64, i64, i64, f32p],
            "gather_strided_f32_f32": [f32p, i64, i64, i64, i64, f32p],
            "gather_strided_f64_f64": [f64p, i64, i64, i64, i64, f64p],
            "gather_strided_f32_f64": [f32p, i64, i64, i64, i64, f64p],
            "csr_densify_f32": [ctypes.POINTER(i64),
                                ctypes.POINTER(ctypes.c_int32), f32p, i64,
                                i64, i64, f32p],
            "csr_densify_f64_f32": [ctypes.POINTER(i64),
                                    ctypes.POINTER(ctypes.c_int32), f64p,
                                    i64, i64, i64, f32p],
        }.items():
            getattr(lib, name).argtypes = argtypes
            getattr(lib, name).restype = None
        lib.staging_num_threads.restype = ctypes.c_int
        _lib = lib
        get_logger("spark_rapids_ml_tpu.native").info(
            f"native staging library loaded ({lib.staging_num_threads()} threads)"
        )
    return _lib


def status() -> str:
    """One line for an operator: whether host staging runs the native
    kernels or numpy, and why — a missing compiler is otherwise found
    later as a slow `stage`."""
    lib = _load()
    if lib is None:
        return f"numpy ({_fallback_reason or 'native library unavailable'})"
    return f"native ({lib.staging_num_threads()} threads)"


def available() -> bool:
    return _load() is not None


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


# Below ~64MB the numpy copy is already fast; skip ctypes overhead.
_MIN_NATIVE_BYTES = 1 << 26


# set True in tests to exercise the native kernels regardless of size and
# thread-count gates
_FORCE_NATIVE = False

# pack_rows wins even single-threaded; this only amortizes the ctypes setup
_MIN_PACK_ROWS = 16384


def _parallel_lib():
    """The library, but only when OpenMP has real parallelism: numpy's
    SIMD copy/cast loops already saturate a single core, so the bandwidth-
    bound pad/densify paths only win multi-threaded."""
    lib = _load()
    if lib is not None and (_FORCE_NATIVE or lib.staging_num_threads() > 1):
        return lib
    return None


def pad_cast(arr: np.ndarray, n_pad: int, dtype: np.dtype) -> np.ndarray:
    """Zero-padded, dtype-cast, C-contiguous copy of a 2-D array — the
    staging step of mesh.shard_rows, parallelized when large."""
    dtype = np.dtype(dtype)
    n, d = arr.shape
    lib = _parallel_lib() if arr.nbytes >= _MIN_NATIVE_BYTES else None
    pair = (str(arr.dtype), str(dtype))
    fn = None
    if lib is not None and arr.flags.c_contiguous:
        fn = {
            ("float64", "float32"): ("pad_cast_f64_f32", ctypes.c_double),
            ("float32", "float32"): ("pad_copy_f32", ctypes.c_float),
            ("float64", "float64"): ("pad_copy_f64", ctypes.c_double),
            ("float32", "float64"): ("pad_cast_f32_f64", ctypes.c_float),
        }.get(pair)
    if fn is not None:
        out = np.empty((n_pad, d), dtype)
        name, src_ct = fn
        dst_ct = ctypes.c_float if dtype == np.float32 else ctypes.c_double
        getattr(lib, name)(_ptr(arr, src_ct), n, d, n_pad, _ptr(out, dst_ct))
        return out
    out = np.zeros((n_pad, d), dtype)
    out[:n] = arr
    return out


def gather_rows_strided(
    arr: np.ndarray, start: int, step: int, count: int, dtype: np.dtype,
    out: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Contiguous, dtype-cast copy of rows `arr[start + i*step]` for
    i in [0, count) — the fused interleave-permutation slice of the
    pipelined staging engine (mesh.RowStager round-robin layout),
    parallelized when large.  `step=1` is the plain contiguous chunk
    slice (still fusing the cast), so the engine has ONE producer
    primitive for both layouts.  `out`, a C-contiguous (count, ...)
    array of `dtype`, receives the rows where given (the engine's reused
    piece buffers); a new array otherwise."""
    dtype = np.dtype(dtype)
    d = int(np.prod(arr.shape[1:], dtype=np.int64)) if arr.ndim > 1 else 1
    out_bytes = count * d * dtype.itemsize
    lib = (
        _parallel_lib()
        if (out_bytes >= _MIN_NATIVE_BYTES or _FORCE_NATIVE)
        else None
    )
    if (
        lib is not None and arr.ndim == 2 and arr.flags.c_contiguous
        and count > 0
    ):
        name = {
            ("float64", "float32"): "gather_strided_f64_f32",
            ("float32", "float32"): "gather_strided_f32_f32",
            ("float64", "float64"): "gather_strided_f64_f64",
            ("float32", "float64"): "gather_strided_f32_f64",
        }.get((str(arr.dtype), str(dtype)))
        if name is not None:
            src_ct = (
                ctypes.c_double if arr.dtype == np.float64 else ctypes.c_float
            )
            dst_ct = (
                ctypes.c_float if dtype == np.float32 else ctypes.c_double
            )
            if out is None:
                out = np.empty((count, d), dtype)
            getattr(lib, name)(
                _ptr(arr, src_ct), start, step, count, d, _ptr(out, dst_ct)
            )
            return out
    stop = start + count * step
    if out is None:
        return np.ascontiguousarray(arr[start:stop:step], dtype=dtype)
    np.copyto(out, arr[start:stop:step], casting="unsafe")
    return out


def pack_rows(rows: np.ndarray, n_pad: int, dtype: np.dtype) -> np.ndarray:
    """Pack an object array of n per-row vectors into a padded (n_pad, d)
    matrix — the np.stack replacement for array-valued feature columns."""
    dtype = np.dtype(dtype)
    n = len(rows)
    first = np.asarray(rows[0])
    d = first.shape[0]
    # wins even single-threaded (np.stack pays per-row Python overhead),
    # so gate only on the row count amortizing the ctypes setup
    lib = _load() if (n >= _MIN_PACK_ROWS or _FORCE_NATIVE) else None
    if lib is not None and dtype in (np.float32, np.float64):
        name = {
            ("float64", "float32"): "pack_rows_f64_f32",
            ("float32", "float32"): "pack_rows_f32_f32",
            ("float64", "float64"): "pack_rows_f64_f64",
        }.get((str(first.dtype), str(dtype)))
        if name is not None:
            ptrs = (ctypes.c_void_p * n)()
            ok = True
            for i in range(n):
                r = rows[i]
                if (
                    not isinstance(r, np.ndarray)
                    or r.dtype != first.dtype
                    or r.shape != (d,)
                    or not r.flags.c_contiguous
                ):
                    ok = False
                    break
                ptrs[i] = r.ctypes.data
            if ok:
                out = np.empty((n_pad, d), dtype)
                dst_ct = (
                    ctypes.c_float if dtype == np.float32 else ctypes.c_double
                )
                getattr(lib, name)(
                    ctypes.cast(ptrs, ctypes.POINTER(ctypes.c_void_p)),
                    n, d, n_pad, _ptr(out, dst_ct),
                )
                return out
    stacked = np.ascontiguousarray(
        np.stack([np.asarray(v, dtype=dtype) for v in rows])
    )
    if n_pad == n:
        return stacked
    out = np.zeros((n_pad, d), dtype)
    out[:n] = stacked
    return out


def densify_csr(csr, n_pad: int, dtype: np.dtype) -> np.ndarray:
    """CSR -> padded dense (n_pad, d) block (the per-block densify of the
    TPU sparse strategy), parallelized over rows."""
    dtype = np.dtype(dtype)
    n, d = csr.shape
    lib = (
        _parallel_lib()
        if (n * d * dtype.itemsize >= _MIN_NATIVE_BYTES or _FORCE_NATIVE)
        else None
    )
    if lib is not None and dtype == np.float32:
        if not csr.has_canonical_format:
            # the native kernel assigns (last write wins); scipy's toarray
            # SUMS duplicate entries — canonicalize to match
            csr.sum_duplicates()
        indptr = np.ascontiguousarray(csr.indptr, dtype=np.int64)
        indices = np.ascontiguousarray(csr.indices, dtype=np.int32)
        data = np.ascontiguousarray(csr.data)
        name = {
            "float32": "csr_densify_f32",
            "float64": "csr_densify_f64_f32",
        }.get(str(data.dtype))
        if name is not None:
            out = np.empty((n_pad, d), np.float32)
            getattr(lib, name)(
                _ptr(indptr, ctypes.c_int64),
                _ptr(indices, ctypes.c_int32),
                _ptr(data, ctypes.c_float if data.dtype == np.float32
                     else ctypes.c_double),
                n, d, n_pad, _ptr(out, ctypes.c_float),
            )
            return out
    dense = csr.toarray()
    if n_pad == n:
        return np.ascontiguousarray(dense.astype(dtype, copy=False))
    out = np.zeros((n_pad, d), dtype)
    out[:n] = dense
    return out


__all__ = [
    "NativeBuildTimeout", "available", "status", "pad_cast", "pack_rows",
    "densify_csr", "gather_rows_strided",
]
