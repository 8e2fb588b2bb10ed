"""Window-compacted scan layout: results must be identical to the padded
device path and the host oracle (reference parity: range scans only read
planned ranges, AbstractBatchScan.scala:32, with unchanged semantics)."""

import numpy as np
import pytest

from geomesa_tpu import GeoDataset
from geomesa_tpu.filter.ecql import parse_iso_ms
from geomesa_tpu.planning import executor as exmod
from geomesa_tpu.planning.viewcache import caches_of


@pytest.fixture
def ds_data():
    rng = np.random.default_rng(11)
    n = 60_000
    lo = parse_iso_ms("2020-01-01")
    hi = parse_iso_ms("2020-02-01")
    data = {
        "geom__x": rng.uniform(-120, -70, n),
        "geom__y": rng.uniform(25, 50, n),
        "dtg": rng.integers(lo, hi, n).astype("datetime64[ms]"),
        "weight": rng.uniform(0, 1, n).astype(np.float32),
    }
    ds = GeoDataset(n_shards=4)
    ds.create_schema("t", "weight:Float,dtg:Date,*geom:Point")
    ds.insert("t", data, fids=np.arange(n).astype(str))
    ds.flush("t")
    return ds, data


ECQL = (
    "BBOX(geom, -100, 30, -80, 45) AND "
    "dtg DURING 2020-01-05T00:00:00Z/2020-01-15T00:00:00Z"
)


def _oracle_mask(data):
    x, y = data["geom__x"], data["geom__y"]
    t = data["dtg"].astype(np.int64)
    return (
        (x >= -100) & (x <= -80) & (y >= 30) & (y <= 45)
        & (t >= parse_iso_ms("2020-01-05"))
        & (t <= parse_iso_ms("2020-01-15"))
    )


@pytest.fixture
def force_compact():
    from geomesa_tpu import config

    config.COMPACT_MIN_ROWS.set(1)
    config.COMPACT_FRACTION.set(2.0)
    yield
    config.COMPACT_MIN_ROWS.set(None)
    config.COMPACT_FRACTION.set(None)


def _compact_was_used(ds, plan):
    st = ds._store("t")
    return any(k[0] == "compact_win"
               for k, _ in caches_of(st).device_windows.items())


def test_compact_count_density_match_oracle(ds_data, force_compact):
    ds, data = ds_data
    want = int(_oracle_mask(data).sum())
    st, _, plan = ds._plan("t", ECQL)
    ex = ds._executor(st)
    assert ex.count(plan) == want
    assert _compact_was_used(ds, plan), "compact path did not engage"
    bbox = (-100.0, 30.0, -80.0, 45.0)
    grid = ex.density(plan, bbox, 64, 64)
    assert abs(float(grid.sum()) - want) < 1e-3
    # per-cell equality against the padded device path
    ds2 = GeoDataset(n_shards=4)
    ds2.create_schema("t", "weight:Float,dtg:Date,*geom:Point")
    ds2.insert("t", data, fids=np.arange(len(data["dtg"])).astype(str))
    ds2.flush("t")
    grid2 = ds2.density("t", ECQL, bbox=bbox, width=64, height=64)
    np.testing.assert_allclose(grid, grid2)


def test_compact_features_mask(ds_data, force_compact):
    ds, data = ds_data
    out = ds.query("t", ECQL)
    want = _oracle_mask(data)
    assert len(out) == int(want.sum())
    assert set(out.fids) == set(np.nonzero(want)[0].astype(str))


def test_compact_sampling_parity(ds_data, force_compact, monkeypatch):
    from geomesa_tpu.api.dataset import Query

    ds, data = ds_data
    q = Query(ecql=ECQL, sampling=10)
    n_compact = ds.count("t", q)
    st, _, plan = ds._plan("t", q)
    assert _compact_was_used(ds, plan)
    # same query, compaction off: the deterministic 1-in-n counter must
    # select the identical sample
    monkeypatch.setenv("GEOMESA_COMPACT_ENABLED", "false")
    n_full = ds.count("t", Query(ecql=ECQL, sampling=10))
    want = int(_oracle_mask(data).sum())
    assert n_compact == n_full == -(-want // 10)


def test_compact_stats(ds_data, force_compact):
    ds, data = ds_data
    got = ds.stats("t", "MinMax(weight)", ECQL)
    m = _oracle_mask(data)
    w = data["weight"][m]
    assert np.isclose(got.lo, w.min(), atol=1e-6)
    assert np.isclose(got.hi, w.max(), atol=1e-6)


def _f32_hist(x, y, bbox, W, H):
    """Host oracle replicating the device's f32 cell binning (the device
    computes px/py from f32 coordinates; a row on a cell boundary may bin
    one cell off vs f64 — established device-path semantics)."""
    x32, y32 = x.astype(np.float32), y.astype(np.float32)
    b = [np.float32(v) for v in bbox]
    px = np.clip(((x32 - b[0]) / (b[2] - b[0]) * np.float32(W)).astype(np.int64), 0, W - 1)
    py = np.clip(((y32 - b[1]) / (b[3] - b[1]) * np.float32(H)).astype(np.int64), 0, H - 1)
    out = np.zeros(H * W, np.float32)
    np.add.at(out, py * W + px, 1.0)
    return out.reshape(H, W)


def test_mxu_density_per_cell(ds_data, force_compact):
    """The MXU pair kernel must be per-cell exact vs the host histogram."""
    ds, data = ds_data
    bbox = (-100.0, 30.0, -80.0, 45.0)
    W = H = 96
    st, _, plan = ds._plan("t", ECQL)
    ex = ds._executor(st)
    grid = ex.density(plan, bbox, W, H)
    # the ladder must have chosen the MXU pair list (proves the MXU path ran)
    assert any(v[0] == "mxu" for _, v in caches_of(st).ladder.items()), \
        "MXU pair path did not engage"
    m = _oracle_mask(data)
    want = _f32_hist(data["geom__x"][m], data["geom__y"][m], bbox, W, H)
    np.testing.assert_allclose(grid, want)


def test_mxu_density_unclipped_rows(ds_data, force_compact):
    """Rows outside the density bbox clamp into edge cells on both paths
    (RenderingGrid convention) — the pair boxes must cover the clip."""
    ds, data = ds_data
    # filter wider than the density bbox: many matched rows fall outside
    ecql = "BBOX(geom, -110, 27, -75, 48)"
    bbox = (-100.0, 33.0, -90.0, 42.0)
    W = H = 64
    grid = ds.density("t", ecql, bbox=bbox, width=W, height=H)
    x, y = data["geom__x"], data["geom__y"]
    m = (x >= -110) & (x <= -75) & (y >= 27) & (y <= 48)
    want = _f32_hist(x[m], y[m], bbox, W, H)
    np.testing.assert_allclose(grid, want)


def test_compact_weighted_density(ds_data, force_compact):
    ds, data = ds_data
    bbox = (-100.0, 30.0, -80.0, 45.0)
    grid = ds.density("t", ECQL, bbox=bbox, width=32, height=32,
                      weight="weight")
    m = _oracle_mask(data)
    assert np.isclose(
        float(grid.sum()), float(data["weight"][m].sum()), rtol=1e-4
    )
