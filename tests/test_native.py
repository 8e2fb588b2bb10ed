"""Native runtime parity: every C++ entry point must agree bit-for-bit with
the NumPy/Python fallbacks (geomesa_tpu/native.py contract)."""

import numpy as np
import pytest

from geomesa_tpu import native
from geomesa_tpu.curves import zorder
from geomesa_tpu.curves.cover import zcover
from geomesa_tpu.io.bin_format import java_string_hash

needs_native = pytest.mark.skipif(
    not native.available(), reason="native library not built"
)


def test_native_builds():
    # the toolchain is part of the supported environment: the library must
    # build here even though the framework degrades gracefully without it
    assert native.available()


@needs_native
def test_interleave2_parity():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 1 << 31, 10_000).astype(np.uint64)
    y = rng.integers(0, 1 << 31, 10_000).astype(np.uint64)
    np.testing.assert_array_equal(native.interleave2(x, y), zorder.interleave2(x, y))
    z = native.interleave2(x, y)
    nx, ny = native.deinterleave2(z)
    np.testing.assert_array_equal(nx, x)
    np.testing.assert_array_equal(ny, y)


@needs_native
def test_interleave3_parity():
    rng = np.random.default_rng(2)
    x = rng.integers(0, 1 << 21, 10_000).astype(np.uint64)
    y = rng.integers(0, 1 << 21, 10_000).astype(np.uint64)
    t = rng.integers(0, 1 << 21, 10_000).astype(np.uint64)
    np.testing.assert_array_equal(
        native.interleave3(x, y, t), zorder.interleave3(x, y, t)
    )
    z = native.interleave3(x, y, t)
    nx, ny, nt = native.deinterleave3(z)
    np.testing.assert_array_equal(nx, x)
    np.testing.assert_array_equal(ny, y)
    np.testing.assert_array_equal(nt, t)


@needs_native
@pytest.mark.parametrize("dims,bits", [(2, 31), (3, 21), (2, 12), (3, 8)])
def test_zcover_parity(dims, bits):
    rng = np.random.default_rng(dims * 100 + bits)
    top = (1 << bits) - 1
    for budget in (16, 200, 2000):
        for _ in range(20):
            lo = rng.integers(0, top, dims)
            hi = [int(v + rng.integers(0, top - v + 1)) for v in lo]
            want = zcover(list(lo), hi, bits, dims, budget)
            got = native.zcover(list(lo), hi, bits, dims, budget)
            for g, w in zip(got, want):
                assert g.dtype == w.dtype == np.int64
                np.testing.assert_array_equal(g, w)


@needs_native
def test_zcover_point_box():
    want = zcover([5, 5], [5, 5], 8, 2, 2000)
    got = native.zcover([5, 5], [5, 5], 8, 2, 2000)
    np.testing.assert_array_equal(got, want)
    (lo,), (hi,) = got
    assert lo == hi


@needs_native
def test_java_hash_parity():
    vals = ["", "a", "track-123", "ünïcødé", "🚀astral", "x" * 500]
    got = native.java_hash(vals)
    want = np.array([java_string_hash(v) for v in vals], np.int32)
    np.testing.assert_array_equal(got, want)


@needs_native
def test_windows_u64_parity():
    rng = np.random.default_rng(7)
    keys = np.sort(rng.integers(0, 1 << 60, 5000).astype(np.uint64))
    lo = rng.integers(0, 1 << 60, 64).astype(np.uint64)
    hi = lo + rng.integers(0, 1 << 40, 64).astype(np.uint64)
    s, e = native.windows_u64(keys, lo, hi)
    np.testing.assert_array_equal(s, np.searchsorted(keys, lo, side="left"))
    np.testing.assert_array_equal(e, np.searchsorted(keys, hi, side="right"))


@needs_native
def test_bin_windows_parity():
    rng = np.random.default_rng(9)
    n = 4000
    bins_col = np.sort(rng.integers(100, 120, n).astype(np.int32))
    z_col = np.empty(n, np.uint64)
    # z sorted within each bin segment (the table's (bin, z) lexsort)
    for b in np.unique(bins_col):
        seg = bins_col == b
        z_col[seg] = np.sort(rng.integers(0, 1 << 50, int(seg.sum())).astype(np.uint64))
    bins = np.array([99, 103, 107, 119, 121], np.int32)
    zlo, zhi = 1 << 10, 1 << 49

    s, e = native.bin_windows(bins_col, z_col, bins, zlo, zhi)
    # oracle: the original python loop
    ws, we = [], []
    for b in bins.tolist():
        s0 = int(np.searchsorted(bins_col, b, side="left"))
        e0 = int(np.searchsorted(bins_col, b, side="right"))
        if e0 <= s0:
            continue
        seg = z_col[s0:e0]
        s2 = s0 + int(np.searchsorted(seg, np.uint64(zlo), side="left"))
        e2 = s0 + int(np.searchsorted(seg, np.uint64(zhi), side="right"))
        if e2 > s2:
            ws.append(s2)
            we.append(e2)
    np.testing.assert_array_equal(s, np.asarray(ws, np.int64))
    np.testing.assert_array_equal(e, np.asarray(we, np.int64))


def test_fallback_when_disabled(monkeypatch):
    """GEOMESA_NATIVE=0 must route everything through the NumPy paths."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    assert not native.available()
    x = np.array([3, 9], np.uint64)
    y = np.array([5, 2], np.uint64)
    np.testing.assert_array_equal(native.interleave2(x, y), zorder.interleave2(x, y))
    np.testing.assert_array_equal(
        native.zcover([0, 0], [3, 3], 4, 2), zcover([0, 0], [3, 3], 4, 2)
    )
    got = native.java_hash(["abc"])
    assert got[0] == java_string_hash("abc")
