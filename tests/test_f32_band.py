"""f32-uncertainty band certificate (r4): the device evaluates f64 columns
at f32; rows whose value collides with an f32-rounded query bound are the
only ones it can misclassify. The executor counts them once per (plan,
store version) — zero certifies the device result exact, nonzero reroutes
to the f64 host path. r1-r3 silently over-counted one bbox-edge row in the
20M bench because of exactly this.
"""

import numpy as np
import pytest

from geomesa_tpu import GeoDataset
from geomesa_tpu.filter.ecql import parse_iso_ms
from geomesa_tpu.planning.viewcache import caches_of

SPEC = "v:Double,dtg:Date,*geom:Point"


def _mk(xs, ys, vs=None):
    n = len(xs)
    ds = GeoDataset(n_shards=2)
    ds.create_schema("t", SPEC)
    ds.insert("t", {
        "v": np.asarray(vs if vs is not None else np.zeros(n), np.float64),
        "dtg": np.full(n, parse_iso_ms("2022-01-01")).astype("datetime64[ms]"),
        "geom__x": np.asarray(xs, np.float64),
        "geom__y": np.asarray(ys, np.float64),
    }, fids=np.arange(n).astype(str))
    ds.flush()
    return ds


def test_bbox_edge_row_exact():
    """A point just OUTSIDE the bbox whose f32 image sits ON the bound must
    not be counted (f32 compare alone would include it)."""
    eps = 1e-9
    xs = [-90.0, -80.0 + eps, -80.0 - eps, -80.0, -70.0]
    ys = [35.0, 35.0, 35.0, 35.0, 35.0]
    assert np.float32(-80.0 + eps) == np.float32(-80.0)  # collides
    ds = _mk(xs, ys)
    q = "BBOX(geom, -100, 30, -80, 40)"
    # truth: -90, -80-eps, -80 inside; -80+eps and -70 outside
    assert ds.count("t", q) == 3
    fc = ds.query("t", q)
    assert sorted(fc.fids) == ["0", "2", "3"]
    # the band info was computed and found surviving uncertain rows
    st = ds._store("t")
    infos = [v for _, v in caches_of(st).band_verdicts.items()]
    assert any(len(v) for v in infos)


def test_clean_data_keeps_device_path():
    """Data with no f32-bound collisions certifies band-free: the device
    path stays in use (verdict True)."""
    rng = np.random.default_rng(3)
    ds = _mk(rng.uniform(-120, -70, 5000), rng.uniform(25, 50, 5000))
    q = "BBOX(geom, -100.5, 30.5, -80.5, 40.5)"
    x = ds._store("t")._all.columns["geom__x"]
    y = ds._store("t")._all.columns["geom__y"]
    want = int(((x >= -100.5) & (x <= -80.5) & (y >= 30.5) & (y <= 40.5)).sum())
    assert ds.count("t", q) == want
    verdicts = [v for _, v in caches_of(ds._store("t")).band_verdicts.items()]
    assert verdicts and all(len(v) == 0 for v in verdicts)


def test_float64_attribute_boundary():
    eps = 1e-12
    vs = [1.0, 2.0 + eps, 2.0 - eps, 2.0, 3.0]
    assert np.float32(2.0 + eps) == np.float32(2.0)
    ds = _mk(np.zeros(5), np.zeros(5), vs)
    assert ds.count("t", "v <= 2.0") == 3      # 1.0, 2.0-eps, 2.0
    assert ds.count("t", "v = 2.0") == 1
    assert ds.count("t", "v > 2.0") == 2       # 2.0+eps, 3.0


def test_not_polarity_band():
    eps = 1e-9
    xs = [-80.0 + eps, -90.0]
    ds = _mk(xs, [35.0, 35.0])
    # NOT bbox: the just-outside point must be counted
    assert ds.count("t", "NOT (BBOX(geom, -100, 30, -80, 40))") == 1


def test_band_exact_on_binspace_mesh():
    """The 2-D (shard, bin) mesh path must excise band rows like the GSPMD
    kernel (r4 review): one f32-colliding row outside the box must not be
    counted on a meshed dataset."""
    from geomesa_tpu.parallel import binspace

    eps = 1e-9
    mesh = binspace.mesh_2d(2, 2)
    ds = GeoDataset(mesh=mesh, n_shards=2)
    ds.create_schema("t", SPEC)
    n = 4_000
    rng = np.random.default_rng(5)
    xs = np.concatenate([rng.uniform(-120, -70, n - 1), [-80.0 + eps]])
    ys = np.concatenate([rng.uniform(25, 50, n - 1), [35.0]])
    ds.insert("t", {
        "v": np.zeros(n), "geom__x": xs, "geom__y": ys,
        "dtg": np.full(n, parse_iso_ms("2022-01-01")).astype("datetime64[ms]"),
    }, fids=np.arange(n).astype(str))
    ds.flush()
    q = "BBOX(geom, -100, 30, -80, 40)"
    want = int(((xs >= -100) & (xs <= -80) & (ys >= 30) & (ys <= 40)).sum())
    assert ds.count("t", q) == want


@pytest.mark.parametrize("outside", [False, True])
def test_stats_band_rows_merge_on_device(outside):
    """Stats with surviving band rows stay on the device: the band rows'
    exact host partial merges into the device partial (Count and MinMax),
    instead of the whole scan being recomputed on the host."""
    eps = 1e-9
    rng = np.random.default_rng(8)
    n = 2_000
    edge = -80.0 + (eps if outside else -eps)  # collides with f32(-80)
    xs = np.concatenate([rng.uniform(-99, -81, n - 1), [edge]])
    ys = np.concatenate([rng.uniform(31, 39, n - 1), [35.0]])
    vs = np.concatenate([rng.uniform(0, 1, n - 1), [7.5]])
    ds = _mk(xs, ys, vs)
    q = "BBOX(geom, -100, 30, -80, 40)"
    keep = (xs >= -100) & (xs <= -80) & (ys >= 30) & (ys <= 40)
    count, mm = ds.stats("t", "Count();MinMax(v)", q).stats
    assert count.count == int(keep.sum())
    # the device reads the Double column at f32; the band row's 7.5 is exact
    assert np.isclose(mm.lo, vs[keep].min(), rtol=1e-6, atol=0)
    assert np.isclose(mm.hi, vs[keep].max(), rtol=1e-6, atol=0)
    assert (mm.hi == 7.5) == (not outside)
    path = ds.audit.recent(1)[-1].hints["exec_path"]
    assert path["scan"].startswith("device"), path
    assert path["band_rows"] == (0 if outside else 1)
