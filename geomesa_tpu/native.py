"""ctypes loader for the host-side native runtime (native/geomesa_native.cpp).

The TPU compute path is JAX/XLA; this module accelerates the *host* runtime
around it — morton interleave at ingest, z-range cover at plan time, Java
string hashing for BIN export, and searchsorted window resolution. Every
function has a NumPy fallback (used when the library is absent or when
``GEOMESA_NATIVE=0``), so behavior is identical either way; parity is
enforced by tests/test_native.py.

The shared library is built from the tree's source with ``g++ -O3 -shared``
the first time it is needed (single attempt per process). Its file name
carries a hash of the source, so a library built from other source, such as
a stale build lying in a copied checkout, is never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_NATIVE_DIR = os.path.join(os.path.dirname(_HERE), "native")
_SRC_PATH = os.path.join(_NATIVE_DIR, "geomesa_native.cpp")

_lock = threading.Lock()
_lib: "Optional[ctypes.CDLL]" = None
_tried = False

_u64p = np.ctypeslib.ndpointer(np.uint64, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
_i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
_u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_f64p = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")


def _so_path() -> Optional[str]:
    """Where the library built from the current source lives (None when
    the source is absent)."""
    try:
        with open(_SRC_PATH, "rb") as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
    except OSError:
        return None
    return os.path.join(_NATIVE_DIR, f"libgeomesa_native-{digest}.so")


def _build(so_path: str) -> bool:
    """Compile the shared library to ``so_path``. Returns success."""
    # build to a temp name and rename: concurrent first-callers (sidecar +
    # CLI, pytest workers) must never dlopen a half-written .so
    tmp = f"{so_path}.{os.getpid()}.tmp"
    base = ["g++", "-O3", "-fPIC", "-std=c++17", "-shared", "-o", tmp, _SRC_PATH]
    for cmd in (base[:1] + ["-fopenmp"] + base[1:], base):  # openmp optional
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=120)
            os.replace(tmp, so_path)
            return True
        except Exception:
            try:
                os.unlink(tmp)
            except OSError:
                pass
    return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    c64, c32 = ctypes.c_int64, ctypes.c_int32
    cu64 = ctypes.c_uint64
    lib.gm_abi_version.restype = c32
    lib.gm_interleave2.argtypes = [_u64p, _u64p, _u64p, c64]
    lib.gm_deinterleave2.argtypes = [_u64p, _u64p, _u64p, c64]
    lib.gm_interleave3.argtypes = [_u64p, _u64p, _u64p, _u64p, c64]
    lib.gm_deinterleave3.argtypes = [_u64p, _u64p, _u64p, _u64p, c64]
    lib.gm_zcover.argtypes = [_u64p, _u64p, c32, c32, c64, _u64p, _u64p, c64]
    lib.gm_zcover.restype = c64
    lib.gm_java_hash_utf16.argtypes = [_u16p, _i64p, c64, _i32p]
    lib.gm_windows_u64.argtypes = [_u64p, c64, _u64p, _u64p, c64, _i64p, _i64p]
    lib.gm_bin_windows.argtypes = [
        _i32p, _u64p, c64, _i32p, c64, cu64, cu64, _i64p, _i64p,
    ]
    lib.gm_bin_windows.restype = c64
    lib.gm_z2_encode.argtypes = [_f64p, _f64p, c64, _u64p]
    lib.gm_z3_encode.argtypes = [_f64p, _f64p, _i64p, ctypes.c_double, c64, _u64p]
    lib.gm_fid_hash64.argtypes = [_u8p, c64, c64, _u64p]
    lib.gm_time_split.argtypes = [
        _i64p, c64, c64, c32,
        _i32p, ctypes.c_void_p, ctypes.c_void_p,
    ]
    lib.gm_pack_idx.argtypes = [
        _u64p, c64, c32, c32, c32, ctypes.c_void_p,
        ctypes.c_void_p, c32, c64, _u64p,
    ]
    lib.gm_unpack_idx.argtypes = [
        _u64p, c64, c32, c32, c32, c32, c64,
        ctypes.c_void_p, ctypes.c_void_p, _u64p, ctypes.c_void_p,
    ]
    lib.gm_off_from_bin.argtypes = [_i64p, _i32p, c64, c64, _i64p]
    lib.gm_sort_u64.argtypes = [_u64p, c64]
    _u32p = np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS")
    lib.gm_u32_to_s.argtypes = [_u32p, _u8p, c64]
    lib.gm_u32_to_s.restype = c32
    lib.gm_s_to_u32.argtypes = [_u8p, _u32p, c64]
    lib.gm_s_to_u32.restype = c32
    lib.gm_num_threads.restype = c32
    return lib


def lib() -> "Optional[ctypes.CDLL]":
    """The loaded library, or None (disabled / unbuildable)."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    if _tried or os.environ.get("GEOMESA_NATIVE", "1") == "0":
        return _lib
    with _lock:
        if _tried or _lib is not None:
            return _lib
        _tried = True
        so_path = _so_path()
        if so_path is None:
            return None
        if not os.path.exists(so_path) and not _build(so_path):
            return None
        try:
            candidate = ctypes.CDLL(so_path)
            if candidate.gm_abi_version() == 4:
                _lib = _bind(candidate)
        except (OSError, AttributeError):
            _lib = None
    return _lib


def available() -> bool:
    return lib() is not None


# ---------------------------------------------------------------------------
# Wrappers (native when available, identical NumPy fallback otherwise)
# ---------------------------------------------------------------------------

def interleave2(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    L = lib()
    x = np.ascontiguousarray(x, np.uint64)
    y = np.ascontiguousarray(y, np.uint64)
    if L is None:
        from geomesa_tpu.curves import zorder

        return zorder._interleave2_np(x, y)
    out = np.empty(len(x), np.uint64)
    L.gm_interleave2(x, y, out, len(x))
    return out


def deinterleave2(z: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    L = lib()
    z = np.ascontiguousarray(z, np.uint64)
    if L is None:
        from geomesa_tpu.curves import zorder

        return zorder._deinterleave2_np(z)
    x = np.empty(len(z), np.uint64)
    y = np.empty(len(z), np.uint64)
    L.gm_deinterleave2(z, x, y, len(z))
    return x, y


def interleave3(x: np.ndarray, y: np.ndarray, t: np.ndarray) -> np.ndarray:
    L = lib()
    x = np.ascontiguousarray(x, np.uint64)
    y = np.ascontiguousarray(y, np.uint64)
    t = np.ascontiguousarray(t, np.uint64)
    if L is None:
        from geomesa_tpu.curves import zorder

        return zorder._interleave3_np(x, y, t)
    out = np.empty(len(x), np.uint64)
    L.gm_interleave3(x, y, t, out, len(x))
    return out


def deinterleave3(z: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    L = lib()
    z = np.ascontiguousarray(z, np.uint64)
    if L is None:
        from geomesa_tpu.curves import zorder

        return zorder._deinterleave3_np(z)
    x = np.empty(len(z), np.uint64)
    y = np.empty(len(z), np.uint64)
    t = np.empty(len(z), np.uint64)
    L.gm_deinterleave3(z, x, y, t, len(z))
    return x, y, t


def zcover(
    lo: Sequence[int], hi: Sequence[int], bits: int, dims: int,
    max_ranges: int = 2000,
) -> Tuple[np.ndarray, np.ndarray]:
    """Native z-range cover: merged, sorted, inclusive ranges as two int64
    arrays (lows, highs). Falls back to Python."""
    from geomesa_tpu.curves.cover import zcover as py_zcover

    L = lib()
    if L is None:
        return py_zcover(lo, hi, bits, dims, max_ranges)
    qlo = np.ascontiguousarray(list(lo), np.uint64)
    qhi = np.ascontiguousarray(list(hi), np.uint64)
    cap = max_ranges + 16
    out_lo = np.empty(cap, np.uint64)
    out_hi = np.empty(cap, np.uint64)
    n = L.gm_zcover(qlo, qhi, bits, dims, max_ranges, out_lo, out_hi, cap)
    if n < 0:
        # invalid args (-2: Python raises the descriptive error) or
        # capacity overflow (-1): resolve through the fallback either way
        return py_zcover(lo, hi, bits, dims, max_ranges)
    # z-values use at most 63 bits (bits * dims <= 63): the cast is exact
    return out_lo[:n].astype(np.int64), out_hi[:n].astype(np.int64)


def java_hash(values: Sequence[str]) -> np.ndarray:
    """Java String.hashCode for a batch of strings (int32)."""
    L = lib()
    if L is None:
        from geomesa_tpu.io.bin_format import java_string_hash

        return np.array([java_string_hash(str(v)) for v in values], np.int32)
    units_parts: List[np.ndarray] = []
    offsets = np.zeros(len(values) + 1, np.int64)
    for i, v in enumerate(values):
        b = str(v).encode("utf-16-be", "surrogatepass")
        u = np.frombuffer(b, dtype=">u2").astype(np.uint16)
        units_parts.append(u)
        offsets[i + 1] = offsets[i] + len(u)
    units = (
        np.concatenate(units_parts) if units_parts else np.zeros(0, np.uint16)
    )
    units = np.ascontiguousarray(units)
    out = np.empty(len(values), np.int32)
    L.gm_java_hash_utf16(units, offsets, len(values), out)
    return out


def windows_u64(
    keys: np.ndarray, lo: np.ndarray, hi: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Batched [lo, hi] -> (start, end) windows over one sorted u64 column."""
    keys = np.ascontiguousarray(keys, np.uint64)
    lo = np.ascontiguousarray(lo, np.uint64)
    hi = np.ascontiguousarray(hi, np.uint64)
    L = lib()
    if L is None:
        return (
            np.searchsorted(keys, lo, side="left").astype(np.int64),
            np.searchsorted(keys, hi, side="right").astype(np.int64),
        )
    k = len(lo)
    starts = np.empty(k, np.int64)
    ends = np.empty(k, np.int64)
    L.gm_windows_u64(keys, len(keys), lo, hi, k, starts, ends)
    return starts, ends


def bin_windows(
    bins_col: np.ndarray, z_col: np.ndarray, bins: np.ndarray,
    zlo: int, zhi: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Per-time-bin [zlo, zhi] windows over (bin, z)-sorted columns.

    Returns (starts, ends) of only the non-empty windows. Falls back to the
    NumPy loop when the library is absent."""
    bins_col = np.ascontiguousarray(bins_col, np.int32)
    z_col = np.ascontiguousarray(z_col, np.uint64)
    bins = np.ascontiguousarray(bins, np.int32)
    L = lib()
    if L is None:
        starts, ends = [], []
        for b in bins.tolist():
            s = int(np.searchsorted(bins_col, b, side="left"))
            e = int(np.searchsorted(bins_col, b, side="right"))
            if e <= s:
                continue
            seg = z_col[s:e]
            s2 = s + int(np.searchsorted(seg, np.uint64(zlo), side="left"))
            e2 = s + int(np.searchsorted(seg, np.uint64(zhi), side="right"))
            if e2 > s2:
                starts.append(s2)
                ends.append(e2)
        return np.asarray(starts, np.int64), np.asarray(ends, np.int64)
    n = len(bins)
    starts = np.empty(n, np.int64)
    ends = np.empty(n, np.int64)
    m = L.gm_bin_windows(
        bins_col, z_col, len(bins_col), bins, n,
        np.uint64(zlo), np.uint64(zhi), starts, ends,
    )
    return starts[:m], ends[:m]


def z2_encode(x: np.ndarray, y: np.ndarray) -> Optional[np.ndarray]:
    """Fused normalize+interleave z2 encode; None -> numpy fallback path."""
    L = lib()
    if L is None:
        return None
    x = np.ascontiguousarray(x, np.float64)
    y = np.ascontiguousarray(y, np.float64)
    out = np.empty(len(x), np.uint64)
    L.gm_z2_encode(x, y, len(x), out)
    return out


def z3_encode(
    x: np.ndarray, y: np.ndarray, off_ms: np.ndarray, off_max: float
) -> Optional[np.ndarray]:
    """Fused normalize+interleave z3 encode; None -> numpy fallback path."""
    L = lib()
    if L is None:
        return None
    x = np.ascontiguousarray(x, np.float64)
    y = np.ascontiguousarray(y, np.float64)
    off_ms = np.ascontiguousarray(off_ms, np.int64)
    out = np.empty(len(x), np.uint64)
    L.gm_z3_encode(x, y, off_ms, float(off_max), len(x), out)
    return out


def fid_hash64(a: np.ndarray) -> Optional[np.ndarray]:
    """Single-pass feature-id hash over a U/S string column; None ->
    numpy fallback (packsort.fid_hash64 python path, bit-identical)."""
    L = lib()
    if L is None:
        return None
    a = np.ascontiguousarray(a)
    u8 = a.view(np.uint8)
    out = np.empty(len(a), np.uint64)
    L.gm_fid_hash64(u8, len(a), a.dtype.itemsize, out)
    return out


def off_from_bin(t: np.ndarray, bins: np.ndarray, period_ms: int):
    """offset_ms = t - bin*period fused; None -> numpy fallback path."""
    L = lib()
    if L is None:
        return None
    t = np.ascontiguousarray(t, np.int64)
    bins = np.ascontiguousarray(bins, np.int32)
    out = np.empty(len(t), np.int64)
    L.gm_off_from_bin(t, bins, int(period_ms), len(t), out)
    return out


def time_split(
    t: np.ndarray, period_ms: int, scale: int,
    want_off_ms: bool = True, want_scaled: bool = False,
):
    """epoch_ms -> (bin i32, off_ms i64 | None, off_scaled i32 | None) in one
    native pass; None -> numpy fallback path."""
    L = lib()
    if L is None:
        return None
    t = np.ascontiguousarray(t, np.int64)
    n = len(t)
    b = np.empty(n, np.int32)
    off = np.empty(n, np.int64) if want_off_ms else None
    sc = np.empty(n, np.int32) if want_scaled else None
    L.gm_time_split(
        t, n, int(period_ms), int(scale), b,
        off.ctypes.data if off is not None else None,
        sc.ctypes.data if sc is not None else None,
    )
    return b, off, sc


def u32_to_s(cp: np.ndarray) -> "Optional[np.ndarray]":
    """Fused UCS4->bytes narrowing with ASCII check. ``cp`` is the flat
    uint32 code-point view of a 'U' array; returns the uint8 buffer, or
    None when unavailable / non-ASCII (caller keeps the unicode layout)."""
    L = lib()
    if L is None:
        return None
    cp = np.ascontiguousarray(cp, np.uint32)
    out = np.empty(cp.size, np.uint8)
    if not L.gm_u32_to_s(cp.reshape(-1), out, cp.size):
        return None
    return out


def s_to_u32(by: np.ndarray) -> "Optional[np.ndarray]":
    """Fused bytes->UCS4 widening with ASCII check (export mirror)."""
    L = lib()
    if L is None:
        return None
    by = np.ascontiguousarray(by, np.uint8)
    out = np.empty(by.size, np.uint32)
    if not L.gm_s_to_u32(by.reshape(-1), out, by.size):
        return None
    return out
