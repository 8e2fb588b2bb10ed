"""AIS vessel pings at a small size: the benchmark's ``ais_vessels``
generator around one port, with vessels moored for days, served through
``GeoDataset``. Every density grid and ``Count();MinMax(SOG)`` equals the
plain reference (``benchmarks/reference.py``) exactly, on each rung of the
density ladder the CPU runs: the einsum pair kernel, scatter (a vessel's
pings planned on the ``MMSI`` attribute index, which has no morton key)
and the pallas grouped kernel in interpret mode. The ``scan.kernel`` span
and the ``exec.density.kernel.<kernel>`` counters name the rung that
served, with the pair budget's P and C."""

import importlib.util
import json
import os

import numpy as np
import pytest

from geomesa_tpu import GeoDataset, config, metrics, tracing
from geomesa_tpu.kernels import density_pallas
from geomesa_tpu.planning.viewcache import caches_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 7
PORT = "Los Angeles/Long Beach"
#: (centre lon, centre lat, width deg, window days): port views over the
#: berths, the approach, and last fishing grounds offshore, where a few
#: trawl tracks spread each chunk's key box over several grid tiles
VIEWS = (
    (-118.235, 33.755, 0.5, 1), (-118.235, 33.755, 1.0, 3),
    (-118.235, 33.755, 2.0, 2), (-118.25, 33.6, 0.5, 2),
    (-119.3, 33.9, 0.5, 3),
)


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"_ais_{name.replace('/', '_')}", os.path.join(ROOT, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ais():
    """(dataset, reference arrays, generator, configuration): 100k pings
    over 4 days, one port, every berth stay 2-5 days."""
    gen = _load("benchmarks/configs/ais_vessels.py")
    gen.PORTS = tuple(p for p in gen.PORTS if p[0] == PORT)
    ref = _load("benchmarks/reference.py")
    with open(os.path.join(ROOT, "benchmarks/configs/ais_vessels.json")) as f:
        cfg = json.load(f)
    stays = {k: [48, 120] for k in ("cargo", "tanker", "fishing", "tug")}
    cfg = dict(cfg, rows=100_000, days=4,
               assumed=dict(cfg["assumed"], berth_hours=stays))
    cols, fids, data = gen.generate(cfg, SEED)
    ds = GeoDataset()
    ds.create_schema("ais", cfg["spec"])
    ds.insert("ais", cols, fids=fids)
    ds.flush("ais")
    return ds, dict(data, MMSI=np.asarray(cols["MMSI"])), gen, ref, cfg


@pytest.fixture
def compact():
    """The compact layout (and so the density ladder) at this size."""
    with config.COMPACT_MIN_ROWS.scoped("0"), \
            config.COMPACT_FRACTION.scoped("2.0"), \
            config.TRACE_ENABLED.scoped("true"):
        yield


def _request(gen, cfg, view, op="density"):
    cx, cy, w, days = view
    x0, y0 = round(cx - w / 2, 4), round(cy - w / 4, 4)
    t0 = (gen.cc.iso_ms(cfg["t_start"]) + cfg["window_grain_ms"]
          + cfg["window_offset_ms"])
    req = {"op": op, "bbox": [x0, y0, round(x0 + w, 4), round(y0 + w / 2, 4)],
           "t0": t0, "t1": t0 + days * 86_400_000, "grid": [512, 512],
           "stat": "Count();MinMax(SOG)"}
    req["ecql"] = (f"BBOX(geom, {', '.join(repr(v) for v in req['bbox'])}) "
                   f"AND dtg DURING {gen.cc.ms_iso(req['t0'])}/"
                   f"{gen.cc.ms_iso(req['t1'])}")
    return req


def _kernel_attrs():
    """The density ``scan.kernel`` span attributes of the last query."""
    tree = tracing.last_trace().root.to_dict()
    out, todo = [], [tree]
    while todo:
        s = todo.pop()
        todo.extend(s.get("children", ()))
        if s["name"] == "scan.kernel" and "density_kernel" in s["attrs"]:
            out.append(s["attrs"])
    assert len(out) == 1, out
    return out[0]


def _counters():
    reg = metrics.registry()
    return {k: reg.counter(f"{metrics.EXEC_DENSITY_KERNEL}.{k}").value
            for k in ("grouped", "mxu", "scatter")}


def _serve(ds, data, gen, ref, cfg, view):
    """Serve one density view; check it against the reference and return
    its kernel span attributes, after checking the counters moved with
    the span."""
    req = _request(gen, cfg, view)
    before = _counters()
    grid = ds.density("ais", req["ecql"], bbox=req["bbox"], width=512,
                      height=512)
    want = ref.density(data, req)
    assert np.array_equal(np.asarray(grid, np.float64),
                          want.astype(np.float64)), view
    attrs = _kernel_attrs()
    after = _counters()
    moved = {k: after[k] - before[k] for k in after}
    assert moved == {k: int(k == attrs["density_kernel"]) for k in moved}
    return attrs


@pytest.fixture
def served(ais, compact):
    return lambda view: _serve(*ais, view)


def test_generator_piles_pings_at_berths(ais):
    _, data, gen, _, cfg = ais
    t = data["t"]
    assert len(t) == cfg["rows"]
    assert np.all(np.diff(t) >= 0) and np.all(t % 1000 == 0)
    for k in ("x", "y"):
        assert np.array_equal(np.round(data[k], 5), data[k])
    assert data["SOG"].dtype == np.float32
    # some berth holds thousands of pings within a few metres
    cell = (np.round(data["x"], 3) * 1e4).astype(np.int64) * 100_000 + \
        (np.round(data["y"], 3) * 1e3).astype(np.int64)
    assert np.unique(cell, return_counts=True)[1].max() > 5000


def _serve_vessel(ds, data, ref, mmsi, bbox):
    """Serve the density of one vessel's pings (``MMSI = '<id>'``) over
    ``bbox``; check it against the reference's f32 binning of those pings,
    rows outside the bbox clamped into the edge cells, and return its
    kernel span attributes after checking the counters moved with it."""
    before = _counters()
    grid = ds.density("ais", f"MMSI = '{mmsi}'", bbox=bbox, width=512,
                      height=512)
    m = data["MMSI"] == mmsi
    xmin, ymin, xmax, ymax = bbox
    px = ref._bins(data["x"][m].astype(np.float32), xmin, xmax - xmin, 512)
    py = ref._bins(data["y"][m].astype(np.float32), ymin, ymax - ymin, 512)
    want = np.bincount(py.astype(np.int64) * 512 + px, minlength=512 * 512)
    assert np.array_equal(np.asarray(grid, np.float64).reshape(-1),
                          want.astype(np.float64)), mmsi
    attrs = _kernel_attrs()
    after = _counters()
    assert {k: after[k] - before[k] for k in after} == \
        {k: int(k == attrs["density_kernel"]) for k in after}
    return attrs


@pytest.fixture
def fresh_ladder(ais):
    """The store's ladder decisions emptied before and after the test:
    its key holds only what the ladder observes, not what a test
    patches."""
    ladder = caches_of(ais[0]._store("ais")).ladder
    ladder.clear()
    yield
    ladder.clear()


@pytest.mark.parametrize("rung", ["mxu", "scatter"])
def test_density_rung_equals_reference(ais, served, rung):
    """The CPU's own rungs, each where the ladder observes it applies: the
    einsum pair kernel on the port views' z3 layout, where pallas does not
    run; scatter on the ``MMSI`` attribute index, which has no morton key
    to box the chunks with."""
    if rung == "mxu":
        for view in VIEWS:
            attrs = served(view)
            assert attrs["density_kernel"] == rung, (view, attrs)
            assert attrs["chunks"] > 0 and attrs["pairs"] > 0
        return
    ds, data, gen, ref, cfg = ais
    bbox = _request(gen, cfg, VIEWS[1])["bbox"]
    x0, y0, x1, y1 = bbox
    inside = ((data["x"] >= x0) & (data["x"] <= x1)
              & (data["y"] >= y0) & (data["y"] <= y1))
    ids, n = np.unique(data["MMSI"][inside], return_counts=True)
    for mmsi in ids[np.argsort(-n, kind="stable")[:3]]:
        st, _, plan = ds._plan("ais", f"MMSI = '{mmsi}'")
        assert plan.index_name.startswith("attr:"), plan.index_name
        attrs = _serve_vessel(ds, data, ref, mmsi, bbox)
        assert attrs["density_kernel"] == rung, (mmsi, attrs)
        assert attrs["chunks"] > 0 and attrs["pairs"] == 0


def test_grouped_rung_equals_reference_in_interpret_mode(served,
                                                         monkeypatch):
    """The pallas grouped kernel where the pair budget holds, the einsum
    rung where it does not: both exact, and the span says which."""
    monkeypatch.setenv("GEOMESA_PALLAS_INTERPRET", "1")
    seen = set()
    for view in VIEWS:
        attrs = served(view)
        fits = attrs["pairs"] <= 4.0 * attrs["chunks"]
        assert attrs["density_kernel"] == ("grouped" if fits else "mxu")
        seen.add(attrs["density_kernel"])
    assert seen == {"grouped", "mxu"}


def test_fishing_ground_view_past_the_pair_budget_falls_back(ais, served,
                                                             monkeypatch,
                                                             fresh_ladder):
    """A view whose chunks' key boxes span more than ``MAX_DUP`` tiles
    each: pallas is available, yet the einsum rung serves, exactly; with
    the budget raised past its P, the grouped kernel serves it."""
    monkeypatch.setenv("GEOMESA_PALLAS_INTERPRET", "1")
    _, data, gen, ref, cfg = ais
    assert ref.matched_rows(data, _request(gen, cfg, VIEWS[-1])) > 100
    attrs = served(VIEWS[-1])
    assert attrs["pairs"] > 4.0 * attrs["chunks"] > 0
    assert attrs["density_kernel"] == "mxu"
    monkeypatch.setattr(density_pallas, "MAX_DUP", float(attrs["pairs"]))
    caches_of(ais[0]._store("ais")).ladder.clear()
    assert served(VIEWS[-1])["density_kernel"] == "grouped"


@pytest.mark.parametrize("view", VIEWS[:4])
def test_count_minmax_sog_equals_reference(ais, view):
    ds, data, gen, ref, cfg = ais
    req = _request(gen, cfg, view, op="stats")
    cs, mm = ds.stats("ais", req["stat"], req["ecql"]).stats
    n, lo, hi = ref.stats(data, req)
    assert n > 0 and int(cs.count) == n
    assert (float(mm.lo), float(mm.hi)) == (lo, hi)


def test_repeated_stats_hit_the_kernel_registry(ais):
    """The serial stats path keys its kernel by the stat's structure: a
    repeat of a query traces and compiles nothing."""
    ds, data, gen, ref, cfg = ais
    req = _request(gen, cfg, VIEWS[1], op="stats")
    reg = metrics.registry()
    first = ds.stats("ais", req["stat"], req["ecql"]).stats
    before = reg.counter(metrics.KERNEL_RECOMPILES).value
    again = ds.stats("ais", req["stat"], req["ecql"]).stats
    assert reg.counter(metrics.KERNEL_RECOMPILES).value == before
    assert ds.audit.recent(1)[0].hints["exec_path"]["kernel"] == "hit"
    assert [(s.count, s.lo, s.hi) for s in first[1:]] == \
        [(s.count, s.lo, s.hi) for s in again[1:]]


def test_edge_pings_bin_as_the_device_bins():
    """A 5-decimal ping exactly on a 4-decimal view edge is an f32 band
    row: its membership is decided exactly on the host, and its cell is
    binned from its f32 coordinates like every other row's, also where
    f64 arithmetic would put it one cell over."""
    ref = _load("benchmarks/reference.py")
    bbox = [-89.0643, 29.6216, -87.0643, 30.6216]
    f = np.float32
    ys = np.round(np.arange(29.6217, 30.6215, 1e-5), 5)
    p64 = np.floor((ys - bbox[1]) / (bbox[3] - bbox[1]) * 512)
    p32 = ((ys.astype(f) - f(bbox[1])) / f(bbox[3] - bbox[1])
           * f(512)).astype(np.int32)
    split = ys[p64 != p32][:5]
    assert len(split) == 5
    rng = np.random.default_rng(0)
    n = 20_000
    x = np.round(rng.uniform(-90, -86, n), 5)
    y = np.round(rng.uniform(29, 31, n), 5)
    x[:5], y[:5] = bbox[0], split  # on the west edge
    t = np.full(n, 1_686_614_400_000, np.int64)  # 2023-06-13
    ds = GeoDataset()
    ds.create_schema("edge", "dtg:Date,*geom:Point:srid=4326")
    ds.insert("edge", {"geom__x": x, "geom__y": y,
                       "dtg": t.astype("datetime64[ms]")},
              fids=np.arange(n).astype(str))
    ds.flush("edge")
    req = {"bbox": bbox, "grid": [512, 512], "t0": t[0] - 86_400_000,
           "t1": t[0] + 86_400_000}
    ecql = (f"BBOX(geom, {', '.join(repr(v) for v in bbox)}) AND dtg DURING "
            "2023-06-12T00:00:00Z/2023-06-14T00:00:00Z")
    grid = ds.density("edge", ecql, bbox=bbox, width=512, height=512)
    assert ds.audit.recent(1)[0].hints["exec_path"]["band_rows"] == 5
    want = ref.density({"x": x, "y": y, "t": t}, req)
    assert np.array_equal(np.asarray(grid, np.float64),
                          want.astype(np.float64))
