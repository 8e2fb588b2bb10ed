"""z3 scan-window resolution: the vectorized per-bin search against the
per-range, per-bin algorithm it replaced, and a pin on its search count.

The oracle is the plain algorithm: for every (range, bin) pair one
``searchsorted`` pair into the bin's segment, then ``_cap_windows``. Windows
from disjoint ranges and disjoint bin segments are disjoint, so the order of
collection cannot change the capped result: the arrays must be equal.
"""

import numpy as np
import pytest

from geomesa_tpu import config
from geomesa_tpu.filter import parse_ecql
from geomesa_tpu.filter.ecql import parse_iso_ms
from geomesa_tpu.index import keyspace as ks
from geomesa_tpu.index.store import FeatureStore
from geomesa_tpu.kernels.registry import bucket_count
from geomesa_tpu.schema.feature_type import FeatureType

SPEC = "dtg:Date,*geom:Point;geomesa.z3.interval='week'"
N = 12_000

#: a multi-week interval starting and ending mid-week (weeks start on
#: Thursdays): edge bins on both ends and plain bins between, the weeks of
#: 2020-01-16 and 2020-01-23 with no rows
SPAN = "dtg DURING 2020-01-03T05:00:00Z/2020-02-05T17:30:00Z"
QUERIES = {
    "edge_bins_both_ends": f"BBOX(geom, -100, 30, -80, 45) AND {SPAN}",
    "single_bin": (
        "BBOX(geom, -110, 28, -75, 48) AND "
        "dtg DURING 2020-01-03T00:00:00Z/2020-01-06T12:00:00Z"
    ),
    "two_boxes": (
        "(BBOX(geom, -118, 26, -105, 35) OR BBOX(geom, -85, 40, -72, 49))"
        f" AND {SPAN}"
    ),
}
RAISED = 32768


def _bound_points(ft: FeatureType):
    """Points whose z3 keys sit exactly on every cover range's bounds, at
    both range budgets: the base ranges' in a plain bin with rows, each edge
    bin's in that bin. A window one row short at either side misses them."""
    ksp = ks.Z3KeySpace("geom", "dtg")
    plain = ksp.binned.bin_of(parse_iso_ms("2020-01-09"))
    zs, bins = [], []
    for ecql in QUERIES.values():
        for target in (2000, RAISED):
            with config.SCAN_RANGES_TARGET.scoped(target):
                kp = ksp.plan(ft, parse_ecql(ecql))
            for b, (lo, hi) in [(plain, (kp.lo, kp.hi))] + list(kp._edge.items()):
                zs += lo.tolist() + hi.tolist()
                bins += [b] * (2 * len(lo))
    z = np.asarray(zs, np.uint64)
    x, y, off = ksp.sfc.invert(z)
    off = np.rint(off).astype(np.int64)
    assert (ksp.sfc.index(x, y, off) == z).all()
    dtg = ksp.binned.bin_start_ms(np.asarray(bins)) + off
    return x, y, dtg


def _store(n_shards: int, quantized: bool, monkeypatch) -> FeatureStore:
    """A z3 table over populated weeks around two empty ones, with points on
    the cover's bounds. Unquantized tables come from the argsort build (key
    shift 0)."""
    if not quantized:
        monkeypatch.setattr(ks.Z3KeySpace, "fast_build", lambda *a, **k: None)
    rng = np.random.default_rng(25 + n_shards)
    weeks = [("2020-01-01", "2020-01-16"), ("2020-01-30", "2020-02-12")]
    ft = FeatureType.from_spec("t", SPEC)
    bx, by, bt = _bound_points(ft)
    dtg = np.concatenate([
        rng.integers(parse_iso_ms(a), parse_iso_ms(b), N // 2)
        for a, b in weeks
    ] + [bt])
    fs = FeatureStore(ft, n_shards=n_shards)
    fs.append({
        "geom__x": np.concatenate((rng.uniform(-120, -70, N), bx)),
        "geom__y": np.concatenate((rng.uniform(25, 50, N), by)),
        "dtg": dtg.astype("datetime64[ms]"),
    })
    fs.flush()
    table = fs.tables["z3"]
    assert (table.key_shifts is not None) == quantized
    return fs


def _plan(fs: FeatureStore, ecql: str):
    kp = fs.tables["z3"].keyspace.plan(fs.ft, parse_ecql(ecql))
    assert kp is not None and not kp.disjoint and not kp.full_scan
    return kp


def _oracle_shard(plan, bins_col, z_col, sh, cap):
    """Per-range, per-bin windows of one shard (the replaced algorithm)."""
    per_bin_cap = max(1, cap // max(len(plan.bins), 1))

    def shifted(lo, hi):
        mlo, mhi = ks._merge_cap(lo >> sh, hi >> sh, per_bin_cap, adjacent=1)
        return list(zip(mlo.tolist(), mhi.tolist()))

    base = shifted(plan.lo, plan.hi)
    edge = {b: shifted(lo, hi) for b, (lo, hi) in plan._edge.items()}
    work = [(b, base) for b in plan.bins.tolist() if b not in edge]
    work += list(edge.items())
    starts, ends = [], []
    for b, rs in work:
        s = int(np.searchsorted(bins_col, np.int32(b), side="left"))
        e = int(np.searchsorted(bins_col, np.int32(b), side="right"))
        for lo, hi in rs:
            seg = z_col[s:e]
            ws = s + int(np.searchsorted(seg, np.uint64(lo), side="left"))
            we = s + int(np.searchsorted(seg, np.uint64(hi), side="right"))
            if we > ws:
                starts.append(ws)
                ends.append(we)
    if not starts:
        return np.zeros(1, np.int64), np.zeros(1, np.int64)
    return ks._cap_windows(
        np.asarray(starts, np.int64), np.asarray(ends, np.int64), cap
    )


def _oracle(table, plan, cap):
    """The oracle's windows padded as ``IndexTable.windows`` pads them."""
    sh = (table.key_shifts or {}).get("__z3", 0)
    per_shard = []
    for s in range(table.n_shards):
        sl = table.shard_slice(s)
        per_shard.append(_oracle_shard(
            plan, table.key_columns["__z3_bin"][sl],
            table.key_columns["__z3"][sl], sh, cap,
        ))
    K = bucket_count(max(len(s) for s, _ in per_shard))
    starts = np.zeros((table.n_shards, K), np.int32)
    ends = np.zeros((table.n_shards, K), np.int32)
    for i, (s, e) in enumerate(per_shard):
        starts[i, : len(s)] = s
        ends[i, : len(e)] = e
    return starts, ends


def _empty_bin_in_some_shard(table, plan) -> bool:
    for s in range(table.n_shards):
        col = table.key_columns["__z3_bin"][table.shard_slice(s)]
        if not np.isin(plan.bins, col).all():
            return True
    return False


@pytest.mark.parametrize("raised", [False, True], ids=["cap256", "cap32768"])
@pytest.mark.parametrize("query", sorted(QUERIES))
@pytest.mark.parametrize("quantized", [True, False], ids=["shifted", "unshifted"])
@pytest.mark.parametrize("n_shards", [1, 4])
def test_z3_windows_match_per_range_oracle(
    n_shards, quantized, query, raised, monkeypatch
):
    fs = _store(n_shards, quantized, monkeypatch)
    table = fs.tables["z3"]
    if raised:
        with config.SCAN_RANGES_TARGET.scoped(RAISED), ks.window_cap(RAISED):
            plan = _plan(fs, QUERIES[query])
            got = table.windows(plan)
        cap = RAISED
    else:
        plan = _plan(fs, QUERIES[query])
        got = table.windows(plan)
        cap = ks.MAX_SHARD_WINDOWS
    if query == "single_bin":
        assert len(plan.bins) == 1 and list(plan._edge) == plan.bins.tolist()
    else:
        assert len(plan.bins) > 2 and len(plan._edge) == 2
        assert _empty_bin_in_some_shard(table, plan)
    want = _oracle(table, plan, cap)
    assert got[0].shape == want[0].shape
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    assert (got[1] > got[0]).any()


def test_z3_resolve_windows_searches_per_bin_not_per_range(monkeypatch):
    """With a cover of thousands of ranges, each shard's resolution makes
    a number of searches bounded by its bins, and never calls the native
    per-range search."""
    from geomesa_tpu import native

    fs = _store(4, False, monkeypatch)
    table = fs.tables["z3"]
    ksp = table.keyspace
    with config.SCAN_RANGES_TARGET.scoped(RAISED), ks.window_cap(RAISED):
        plan = _plan(fs, QUERIES["two_boxes"])
        assert len(plan.lo) >= 2000

        def refuse(*a, **k):
            raise AssertionError("per-range native search")

        monkeypatch.setattr(native, "bin_windows", refuse)
        real = np.searchsorted
        calls = []

        def counting(*a, **k):
            calls.append(1)
            return real(*a, **k)

        per_shard = []
        resolve = ksp.resolve_windows

        def counted(*a, **k):
            before = len(calls)
            out = resolve(*a, **k)
            per_shard.append(len(calls) - before)
            return out

        monkeypatch.setattr(np, "searchsorted", counting)
        monkeypatch.setattr(ksp, "resolve_windows", counted)
        starts, ends = table.windows(plan)
        monkeypatch.undo()
    assert len(per_shard) == table.n_shards
    assert max(per_shard) <= 2 * (len(plan.bins) + 2) + 4
    # thousands of ranges still resolve to hundreds of distinct windows
    assert int((ends > starts).sum()) > 500
