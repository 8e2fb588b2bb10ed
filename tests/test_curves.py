"""Curve kernels vs brute-force oracles.

Analog of the reference's Z3Test / XZ3SFCTest / BinnedTimeTest (SURVEY.md §4.1),
but property-style against slow bit-loop oracles.
"""

import numpy as np
import pytest

from geomesa_tpu.curves.binned_time import BinnedTime, TimePeriod, WEEK_MS
from geomesa_tpu.curves.cover import zcover, ZRange
from geomesa_tpu.curves.xz import XZ2SFC, XZ3SFC
from geomesa_tpu.curves.zorder import (
    Z2SFC,
    Z3SFC,
    NormalizedDimension,
    deinterleave2,
    deinterleave3,
    device_interleave,
    interleave2,
    interleave3,
    join_u64,
    split_u64,
)


def slow_interleave(dims, bits):
    """Bit-loop oracle matching the documented layout."""
    d = len(dims)
    z = 0
    for i in range(bits):
        for k in range(d):
            z |= ((int(dims[k]) >> i) & 1) << (d * i + (d - 1 - k))
    return z


def test_interleave2_matches_oracle(rng):
    xs = rng.integers(0, 1 << 31, size=200, dtype=np.uint64)
    ys = rng.integers(0, 1 << 31, size=200, dtype=np.uint64)
    z = interleave2(xs, ys)
    for i in range(0, 200, 17):
        assert int(z[i]) == slow_interleave([xs[i], ys[i]], 31)
    xi, yi = deinterleave2(z)
    np.testing.assert_array_equal(xi, xs)
    np.testing.assert_array_equal(yi, ys)


def test_interleave3_matches_oracle(rng):
    xs = rng.integers(0, 1 << 21, size=200, dtype=np.uint64)
    ys = rng.integers(0, 1 << 21, size=200, dtype=np.uint64)
    ts = rng.integers(0, 1 << 21, size=200, dtype=np.uint64)
    z = interleave3(xs, ys, ts)
    for i in range(0, 200, 17):
        assert int(z[i]) == slow_interleave([xs[i], ys[i], ts[i]], 21)
    xi, yi, ti = deinterleave3(z)
    np.testing.assert_array_equal(xi, xs)
    np.testing.assert_array_equal(yi, ys)
    np.testing.assert_array_equal(ti, ts)


def test_device_interleave_matches_host(rng):
    import jax

    xs = rng.integers(0, 1 << 21, size=64, dtype=np.uint64)
    ys = rng.integers(0, 1 << 21, size=64, dtype=np.uint64)
    ts = rng.integers(0, 1 << 21, size=64, dtype=np.uint64)
    host_z = interleave3(xs, ys, ts)
    hi, lo = jax.jit(lambda a, b, c: device_interleave([a, b, c], 21))(
        xs.astype(np.int32), ys.astype(np.int32), ts.astype(np.int32)
    )
    dev_z = join_u64(np.asarray(hi), np.asarray(lo))
    np.testing.assert_array_equal(dev_z, host_z)
    # and the split/join helpers roundtrip
    h2, l2 = split_u64(host_z)
    np.testing.assert_array_equal(join_u64(h2, l2), host_z)


def test_normalized_dimension_roundtrip():
    dim = NormalizedDimension(-180.0, 180.0, 21)
    xs = np.linspace(-180, 180, 1000)
    idx = dim.normalize(xs)
    back = dim.denormalize(idx)
    res = 360.0 / (1 << 21)
    assert np.max(np.abs(back - xs)) <= res
    assert dim.normalize(np.array([-180.0]))[0] == 0
    assert dim.normalize(np.array([180.0]))[0] == (1 << 21) - 1
    assert dim.normalize(np.array([1e9]))[0] == (1 << 21) - 1  # clipped


def _cover_is_exact(lo, hi, bits, dims, max_ranges=10_000):
    """Oracle: every cell's z is in ranges iff the cell is in the box."""
    ranges = zcover(lo, hi, bits=bits, dims=dims, max_ranges=max_ranges)
    # Build membership set.
    covered = set()
    for lo_, hi_ in zip(*(a.tolist() for a in ranges)):
        covered.update(range(lo_, hi_ + 1))
    size = 1 << bits
    for z in range(1 << (bits * dims)):
        coords = []
        for k in range(dims):
            c = 0
            for i in range(bits):
                c |= ((z >> (dims * i + (dims - 1 - k))) & 1) << i
            coords.append(c)
        inside = all(lo[k] <= coords[k] <= hi[k] for k in range(dims))
        assert (z in covered) == inside, f"z={z} coords={coords}"


def test_zcover_exact_small_2d():
    _cover_is_exact((1, 2), (5, 6), bits=3, dims=2)
    _cover_is_exact((0, 0), (7, 7), bits=3, dims=2)
    _cover_is_exact((3, 3), (3, 3), bits=3, dims=2)


def test_zcover_exact_small_3d():
    _cover_is_exact((1, 0, 2), (2, 3, 3), bits=2, dims=3)


def test_zcover_budget_overcovers_but_contains():
    lo, hi = (1, 2), (6, 5)
    exact = zcover(lo, hi, bits=3, dims=2, max_ranges=10_000)
    budget = zcover(lo, hi, bits=3, dims=2, max_ranges=4)
    assert len(budget[0]) <= 6
    exact_set = set()
    for lo_, hi_ in zip(*(a.tolist() for a in exact)):
        exact_set.update(range(lo_, hi_ + 1))
    budget_set = set()
    for lo_, hi_ in zip(*(a.tolist() for a in budget)):
        budget_set.update(range(lo_, hi_ + 1))
    assert exact_set <= budget_set  # never loses a match


def test_z2_ranges_contain_points(rng):
    sfc = Z2SFC()
    bbox = (-10.0, 35.0, 5.0, 42.0)
    xs = rng.uniform(bbox[0], bbox[2], 500)
    ys = rng.uniform(bbox[1], bbox[3], 500)
    zs = sfc.index(xs, ys)
    lows, his = (a.astype(np.uint64) for a in sfc.ranges(*bbox))
    for z in zs:
        i = np.searchsorted(lows, z, side="right") - 1
        assert i >= 0 and z <= his[i], f"point z {z} not covered"


def test_z3_ranges_contain_points(rng):
    sfc = Z3SFC(TimePeriod.WEEK)
    xs = rng.uniform(-74.1, -73.9, 300)
    ys = rng.uniform(40.6, 40.9, 300)
    ts = rng.uniform(1e8, 5e8, 300)  # offsets within the week
    zs = sfc.index(xs, ys, ts)
    lows, his = (
        a.astype(np.uint64)
        for a in sfc.ranges((-74.1, -73.9), (40.6, 40.9), (1e8, 5e8))
    )
    for z in zs:
        i = np.searchsorted(lows, z, side="right") - 1
        assert i >= 0 and z <= his[i]


def test_binned_time_roundtrip(rng):
    for period in TimePeriod:
        bt = BinnedTime(period)
        ts = rng.integers(0, 1_700_000_000_000, size=1000, dtype=np.int64)
        b, off = bt.to_bin_and_offset(ts)
        start = bt.bin_start_ms(b)
        np.testing.assert_array_equal(start + off, ts)
        assert np.all(off >= 0)
        assert np.all(off <= bt.max_offset_ms)


def test_binned_time_week_matches_division():
    bt = BinnedTime(TimePeriod.WEEK)
    b, off = bt.to_bin_and_offset(np.array([WEEK_MS * 100 + 1234], dtype=np.int64))
    assert b[0] == 100 and off[0] == 1234


def test_xz2_index_and_ranges(rng):
    sfc = XZ2SFC(g=8)
    # random small boxes
    n = 300
    x0 = rng.uniform(-170, 160, n)
    y0 = rng.uniform(-80, 70, n)
    w = rng.uniform(0.001, 5.0, n)
    h = rng.uniform(0.001, 5.0, n)
    codes = sfc.index(x0, y0, x0 + w, y0 + h)
    assert np.all(codes >= 0)
    query = (-20.0, -20.0, 30.0, 25.0)
    ranges = sfc.ranges(*query)
    lows = np.array([r.lo for r in ranges])
    his = np.array([r.hi for r in ranges])
    # every element that intersects the query must be covered
    inter = (x0 <= query[2]) & (x0 + w >= query[0]) & (y0 <= query[3]) & (y0 + h >= query[1])
    for c, isect in zip(codes, inter):
        i = np.searchsorted(lows, c, side="right") - 1
        covered = i >= 0 and c <= his[i]
        if isect:
            assert covered, f"intersecting element code {c} not covered"


def test_xz3_index_and_ranges(rng):
    sfc = XZ3SFC(TimePeriod.WEEK, g=6)
    n = 200
    x0 = rng.uniform(-170, 160, n)
    y0 = rng.uniform(-80, 70, n)
    t0 = rng.uniform(0, WEEK_MS * 0.9, n)
    w = rng.uniform(0.001, 2.0, n)
    dt = rng.uniform(1.0, WEEK_MS * 0.05, n)
    codes = sfc.index(x0, y0, t0, x0 + w, y0 + w, t0 + dt)
    query_x, query_y, query_t = (-20.0, 30.0), (-20.0, 25.0), (0.0, WEEK_MS * 0.5)
    ranges = sfc.ranges(query_x, query_y, query_t)
    lows = np.array([r.lo for r in ranges])
    his = np.array([r.hi for r in ranges])
    inter = (
        (x0 <= query_x[1]) & (x0 + w >= query_x[0])
        & (y0 <= query_y[1]) & (y0 + w >= query_y[0])
        & (t0 <= query_t[1]) & (t0 + dt >= query_t[0])
    )
    for c, isect in zip(codes, inter):
        i = np.searchsorted(lows, c, side="right") - 1
        covered = i >= 0 and c <= his[i]
        if isect:
            assert covered
