"""Pallas grouped density kernel: exact parity with the host scatter oracle
(DensityScan.scala:29-136 semantics) in interpret mode on the CPU mesh."""

import numpy as np
import pytest

from geomesa_tpu import GeoDataset, config
from geomesa_tpu.filter.ecql import parse_iso_ms

ECQL = (
    "BBOX(geom, -100, 30, -80, 45) AND "
    "dtg DURING 2020-01-05T00:00:00Z/2020-01-15T00:00:00Z"
)
BBOX = (-100.0, 30.0, -80.0, 45.0)


@pytest.fixture
def ds_data():
    rng = np.random.default_rng(13)
    n = 40_000
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


@pytest.fixture
def force_pallas(monkeypatch):
    monkeypatch.setenv("GEOMESA_PALLAS_INTERPRET", "1")
    config.COMPACT_MIN_ROWS.set(1)
    config.COMPACT_FRACTION.set(2.0)
    yield
    config.COMPACT_MIN_ROWS.set(None)
    config.COMPACT_FRACTION.set(None)


def _oracle_grid(data, width, height, weight=None):
    x, y = data["geom__x"], data["geom__y"]
    t = data["dtg"].astype(np.int64)
    m = (
        (x >= -100) & (x <= -80) & (y >= 30) & (y <= 45)
        & (t >= parse_iso_ms("2020-01-05"))
        & (t <= parse_iso_ms("2020-01-15"))
    )
    px = np.clip(((x - BBOX[0]) / (BBOX[2] - BBOX[0]) * width).astype(np.int64),
                 0, width - 1)
    py = np.clip(((y - BBOX[1]) / (BBOX[3] - BBOX[1]) * height).astype(np.int64),
                 0, height - 1)
    g = np.zeros(height * width, np.float64)
    w = m.astype(np.float64) if weight is None else np.where(m, data[weight], 0)
    np.add.at(g, py[m] * width + px[m], w[m])
    return g.reshape(height, width)


def _grouped_was_built(ds, plan, bbox, width, height):
    st = ds._store("t")
    ex = ds._executor(st)
    setup = ex._scan_setup(plan, [])
    ex._maybe_compact(plan, setup, True)
    if setup["compact"] is None:
        return False
    return ex._density_ladder(setup, bbox, width, height)[0] == "grouped"


@pytest.mark.parametrize("segment", [None, 64])
def test_grouped_counts_exact(ds_data, force_pallas, segment, monkeypatch):
    """Exact counts, in one pallas call or (``segment``) as several calls
    whose tile partials add — the path of schedules too long for SMEM."""
    from geomesa_tpu.kernels import density_pallas as dp

    if segment is not None:
        monkeypatch.setattr(dp, "SEGMENT", segment)
    ds, data = ds_data
    st, _, plan = ds._plan("t", ECQL)
    grid = ds.density("t", ECQL, bbox=BBOX, width=256, height=256)
    assert _grouped_was_built(ds, plan, BBOX, 256, 256), (
        "pallas grouped kernel did not engage; test exercised another path"
    )
    oracle = _oracle_grid(data, 256, 256)
    assert np.array_equal(grid.astype(np.float64), oracle)


def test_grouped_ragged_grid(ds_data, force_pallas):
    """Grid not a multiple of the 128-cell tile: padded tiles are cropped."""
    ds, data = ds_data
    st, _, plan = ds._plan("t", ECQL)
    grid = ds.density("t", ECQL, bbox=BBOX, width=300, height=200)
    assert _grouped_was_built(ds, plan, BBOX, 300, 200)
    oracle = _oracle_grid(data, 300, 200)
    assert np.array_equal(grid.astype(np.float64), oracle)


def test_grouped_weighted(ds_data, force_pallas):
    ds, data = ds_data
    st, _, plan = ds._plan("t", ECQL)
    grid = ds.density("t", ECQL, bbox=BBOX, width=256, height=256,
                      weight="weight")
    assert _grouped_was_built(ds, plan, BBOX, 256, 256)
    oracle = _oracle_grid(data, 256, 256, weight="weight")
    # f32 accumulation in a different order than the oracle's f64
    assert np.allclose(grid, oracle, rtol=1e-4, atol=1e-3)
    assert abs(grid.sum() - oracle.sum()) / max(oracle.sum(), 1) < 1e-4


def test_grouped_matches_scatter_path(ds_data, force_pallas):
    """The grouped kernel agrees exactly on unweighted counts with the host
    scatter (``np.add.at``) of the same query, and with the device scatter
    of the padded layout."""
    ds, data = ds_data
    st, _, plan = ds._plan("t", ECQL)
    g1 = ds.density("t", ECQL, bbox=BBOX, width=256, height=256)
    assert _grouped_was_built(ds, plan, BBOX, 256, 256)
    assert np.array_equal(g1.astype(np.float64), _oracle_grid(data, 256, 256))
    with config.COMPACT_ENABLED.scoped(False):
        g2 = ds.density("t", ECQL, bbox=BBOX, width=256, height=256)
    assert np.array_equal(g1, g2)
