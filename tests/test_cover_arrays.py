"""Cover ranges as int64 arrays, from the native cover to the window
needles, against the list-of-tuples path they replaced.

The oracle is that path: the cover's list of ``ZRange``, per-geometry
tuples merged by a tuple façade over ``_merge_cap``, edge-bin tuple lists,
and needles shifted tuple by tuple. Ranges, edge sets, coverage, needles and
resolved windows must be equal, array for array.
"""

import numpy as np
import pytest

from geomesa_tpu import config, native
from geomesa_tpu.curves.cover import ZRange
from geomesa_tpu.filter import ir, parse_ecql
from geomesa_tpu.index import keyspace as ks
from geomesa_tpu.schema.feature_type import FeatureType

FT = FeatureType.from_spec("t", "dtg:Date,*geom:Point;geomesa.z3.interval='week'")
RAISED = 32768

SPACE = {
    "one_box": "BBOX(geom, -100, 30, -80, 45)",
    "two_boxes": "(BBOX(geom, -118, 26, -105, 35) OR BBOX(geom, -85, 40, -72, 49))",
    "antimeridian": "(BBOX(geom, 175, -10, 180, 10) OR BBOX(geom, -180, -10, -175, 10))",
}
#: weeks start on Thursdays: one interval inside one week, one across six
TIME = {
    "one_bin": "dtg DURING 2020-01-03T05:00:00Z/2020-01-06T12:00:00Z",
    "many_bins": "dtg DURING 2020-01-03T05:00:00Z/2020-02-05T17:30:00Z",
}
Z3_QUERIES = {
    "one_box.many_bins": f"{SPACE['one_box']} AND {TIME['many_bins']}",
    "one_box.one_bin": f"{SPACE['one_box']} AND {TIME['one_bin']}",
    "two_boxes.many_bins": f"{SPACE['two_boxes']} AND {TIME['many_bins']}",
    "antimeridian.one_bin": f"{SPACE['antimeridian']} AND {TIME['one_bin']}",
    "no_geometry.many_bins": TIME["many_bins"],
}
#: (shift, dtype) of the stored key column: unquantized, and two quantized;
#: at shift 40 some disjoint ranges of the raised cover become adjacent
SHIFTS = [(0, np.uint64), (13, np.uint64), (40, np.uint32)]


# ---------------------------------------------------------------------------
# the replaced list-of-tuples path
# ---------------------------------------------------------------------------

def _as_list(cover):
    """The cover as the list of ``ZRange`` the native cover used to return."""
    lo, hi = cover
    return [ZRange(int(a), int(b)) for a, b in zip(lo, hi)]


def _merge_zranges(ranges, cap):
    if not ranges:
        return []
    los = np.asarray([r[0] for r in ranges], np.int64)
    his = np.asarray([r[1] for r in ranges], np.int64)
    mlo, mhi = ks._merge_cap(los, his, cap, adjacent=1)
    return list(zip(mlo.tolist(), mhi.tolist()))


def _per_geom_ranges(cover_fn, bounds_list, cap):
    all_r = []
    for b in bounds_list:
        for r in cover_fn(b):
            all_r.append((int(r.lo), int(r.hi)))
    return [ZRange(lo, hi) for lo, hi in _merge_zranges(all_r, cap)]


def _oracle_z3_plan(ksp, f, budget):
    """(ranges, edge sets, coverage) as the tuple path planned them."""
    geoms = ir.extract_geometries(f, ksp.geom)
    intervals = ir.extract_intervals(f, ksp.dtg)
    clamp = 2**45
    iv = [(max(lo, -clamp), min(hi, clamp)) for lo, hi in intervals.values]
    bins = np.unique(
        np.concatenate([ksp.binned.bins_between(lo, hi) for lo, hi in iv])
    )
    max_off = float(ksp.binned.max_offset_ms)
    xy = ([(-180.0, -90.0, 180.0, 90.0)] if geoms.is_empty
          else [g.bounds() for g in geoms.values])
    ranges = _per_geom_ranges(
        lambda b: _as_list(ksp.sfc.ranges((b[0], b[2]), (b[1], b[3]),
                                          (0.0, max_off))),
        xy, budget,
    )
    edge = {}
    for lo, hi in iv:
        blo, olo = ksp.binned.to_bin_and_offset(np.asarray([lo], np.int64))
        bhi, ohi = ksp.binned.to_bin_and_offset(np.asarray([hi], np.int64))
        blo, olo = int(blo[0]), float(olo[0])
        bhi, ohi = int(bhi[0]), float(ohi[0])
        for b, off_lo, off_hi in (
            ((blo, olo, max_off if blo != bhi else ohi),)
            + (((bhi, 0.0, ohi),) if bhi != blo else ())
        ):
            rs = [
                (int(r.lo), int(r.hi))
                for box in xy
                for r in _as_list(ksp.sfc.ranges(
                    (box[0], box[2]), (box[1], box[3]), (off_lo, off_hi)))
            ]
            edge.setdefault(b, []).extend(rs)
    span = sum(r.hi - r.lo + 1 for r in ranges)
    cov = span / float(1 << 63) * min(1.0, len(bins) / max(len(bins), 1))
    return ranges, {b: _merge_zranges(rs, budget) for b, rs in edge.items()}, cov


def _oracle_needles(rs, sh, cap, dtype):
    merged = _merge_zranges([(lo >> sh, hi >> sh) for lo, hi in rs], cap)
    return (np.asarray([r[0] for r in merged], dtype),
            np.asarray([r[1] for r in merged], dtype))


def _oracle_z3_windows(bins, base, esets, cols):
    """One search pair per (needle range, bin), then the window cap."""
    bins_col, z_col = cols["__z3_bin"], cols["__z3"]
    work = [(b, base) for b in bins.tolist() if b not in esets]
    work += list(esets.items())
    starts, ends = [], []
    for b, (los, his) in work:
        s = int(np.searchsorted(bins_col, np.int32(b), side="left"))
        e = int(np.searchsorted(bins_col, np.int32(b), side="right"))
        seg = z_col[s:e]
        for lo, hi in zip(los, his):
            ws = s + int(np.searchsorted(seg, lo, side="left"))
            we = s + int(np.searchsorted(seg, hi, side="right"))
            if we > ws:
                starts.append(ws)
                ends.append(we)
    if not starts:
        return np.zeros(1, np.int64), np.zeros(1, np.int64)
    return ks._cap_windows(np.asarray(starts, np.int64),
                           np.asarray(ends, np.int64), ks.shard_window_cap())


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _pairs(ranges):
    return [(int(r[0]), int(r[1])) for r in ranges]


def _assert_arrays(got, want_pairs):
    lo, hi = got
    assert lo.dtype == hi.dtype == np.int64
    np.testing.assert_array_equal(lo, [p[0] for p in want_pairs])
    np.testing.assert_array_equal(hi, [p[1] for p in want_pairs])


def _assert_needles(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def _shard(bounds, bins, sh, dtype, seed):
    """A (bin, key)-sorted shard with keys on every range bound, rows in
    every other of ``bins`` (the rest empty) and in a bin outside them."""
    rng = np.random.default_rng(seed)
    z = np.concatenate(bounds + [rng.integers(0, 1 << 62, 3000)])
    held = np.concatenate([bins[::2], [bins[0] - 1]])
    b = rng.choice(held, len(z))
    zq = (z.astype(np.uint64) >> np.uint64(sh)).astype(dtype)
    order = np.lexsort((zq, b))
    cols = {"__z3_bin": b[order].astype(np.int32), "__z3": zq[order],
            "__z2": zq[order]}
    if sh:
        cols["__shifts__"] = {"__z3": sh, "__z2": sh}
    return cols


@pytest.fixture(params=["native", "no_native"])
def cover_lib(request, monkeypatch):
    if request.param == "native":
        assert native.available()
    else:
        monkeypatch.setattr(native, "lib", lambda: None)
    return request.param


def _budgets(cover_lib, query):
    """The Python cover at the raised budget takes seconds a plan: without
    the library, one query takes both budgets and the rest the planner's."""
    if cover_lib == "no_native" and not query.startswith("one_box"):
        return (2000,)
    return (2000, RAISED)


# ---------------------------------------------------------------------------
# z3
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("query", sorted(Z3_QUERIES))
def test_z3_arrays_match_the_tuple_path(query, cover_lib):
    ksp = ks.Z3KeySpace("geom", "dtg")
    f = parse_ecql(Z3_QUERIES[query])
    for budget in _budgets(cover_lib, query):
        with config.SCAN_RANGES_TARGET.scoped(budget):
            kp = ksp.plan(FT, f)
            ranges, edge, cov = _oracle_z3_plan(ksp, f, budget)
        _assert_arrays((kp.lo, kp.hi), ranges)
        assert list(kp._edge) == list(edge)
        for b in edge:
            _assert_arrays(kp._edge[b], edge[b])
        assert kp.coverage == cov
        if query.startswith("no_geometry"):
            # the whole 63-bit space: a span of 2^63, one past int64
            assert (kp.lo.tolist(), kp.hi.tolist()) == ([0], [(1 << 63) - 1])
            assert kp.coverage == 1.0
        if query.endswith("one_bin"):
            assert len(kp.bins) == 1 and list(kp._edge) == kp.bins.tolist()
        else:
            assert len(kp.bins) > 2 and len(kp._edge) == 2
        bounds = [kp.lo, kp.hi] + [a for e in kp._edge.values() for a in e]
        for sh, dtype in SHIFTS:
            cols = _shard(bounds, kp.bins, sh, dtype, budget + sh)
            for cap in (ks.MAX_SHARD_WINDOWS, budget):
                per_bin = max(1, cap // len(kp.bins))
                base = _oracle_needles(_pairs(ranges), sh, per_bin, dtype)
                esets = {b: _oracle_needles(rs, sh, per_bin, dtype)
                         for b, rs in edge.items()}
                with ks.window_cap(cap):
                    got = ksp.resolve_windows(kp, cols, len(cols["__z3"]))
                    want = _oracle_z3_windows(kp.bins, base, esets, cols)
                gbase, gsets = kp._shifted_ranges[(sh, cap)]
                _assert_needles(gbase, base)
                assert list(gsets) == list(esets)
                for b in esets:
                    _assert_needles(gsets[b], esets[b])
                np.testing.assert_array_equal(got[0], want[0])
                np.testing.assert_array_equal(got[1], want[1])
                assert (got[1] > got[0]).any()


def test_z3_plan_builds_no_zrange_on_the_native_path(monkeypatch):
    """From the native cover to the needles and windows of a raised-budget
    plan, no range becomes a Python object."""
    assert native.available()
    ksp = ks.Z3KeySpace("geom", "dtg")
    f = parse_ecql(Z3_QUERIES["two_boxes.many_bins"])

    def refuse(cls, *a, **k):
        raise AssertionError("ZRange built on the native path")

    monkeypatch.setattr(ZRange, "__new__", refuse)
    with pytest.raises(AssertionError):
        ZRange(1, 2)
    with config.SCAN_RANGES_TARGET.scoped(RAISED), ks.window_cap(RAISED):
        kp = ksp.plan(FT, f)
        bounds = [kp.lo, kp.hi] + [a for e in kp._edge.values() for a in e]
        cols = _shard(bounds, kp.bins, 0, np.uint64, 1)
        starts, ends = ksp.resolve_windows(kp, cols, len(cols["__z3"]))
    assert len(kp.lo) > 2000 and (ends > starts).any()


# ---------------------------------------------------------------------------
# z2
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("query", sorted(SPACE))
def test_z2_arrays_match_the_tuple_path(query, cover_lib):
    ksp = ks.Z2KeySpace("geom")
    f = parse_ecql(SPACE[query])
    for budget in _budgets(cover_lib, query):
        with config.SCAN_RANGES_TARGET.scoped(budget):
            kp = ksp.plan(FT, f)
            geoms = ir.extract_geometries(f, "geom")
            ranges = _per_geom_ranges(
                lambda b: _as_list(ksp.sfc.ranges(*b)),
                [g.bounds() for g in geoms.values], budget,
            )
        _assert_arrays((kp.lo, kp.hi), ranges)
        assert kp.coverage == sum(r.hi - r.lo + 1 for r in ranges) / float(1 << 62)
        for sh, dtype in SHIFTS:
            cols = _shard([kp.lo, kp.hi], np.zeros(1, np.int32), sh, dtype,
                          budget + sh)
            z_col = np.sort(cols["__z2"])
            cols["__z2"] = z_col
            for cap in (ks.MAX_SHARD_WINDOWS, budget):
                with ks.window_cap(cap):
                    got = ksp.resolve_windows(kp, cols, len(z_col))
                los, his = _oracle_needles(_pairs(ranges), sh, cap, dtype)
                ws = np.searchsorted(z_col, los, side="left")
                we = np.searchsorted(z_col, his, side="right")
                keep = we > ws
                want = ks._cap_windows(ws[keep].astype(np.int64),
                                       we[keep].astype(np.int64), cap)
                np.testing.assert_array_equal(got[0], want[0])
                np.testing.assert_array_equal(got[1], want[1])
                assert len(got[0]) and (got[1] > got[0]).all()


def test_z2_plan_without_geometry_is_a_full_scan():
    kp = ks.Z2KeySpace("geom").plan(FT, parse_ecql(TIME["one_bin"]))
    assert kp.full_scan and len(kp.lo) == len(kp.hi) == 0 and kp.ranges == []
