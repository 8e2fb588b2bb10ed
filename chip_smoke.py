"""On-chip smoke: the GeoDataset scan -> filter -> aggregate path on a TPU.

One process holds the chip and drives the public API at a size users run:
a GDELT-like CONUS point feed (the generator of ``bench.py``), 20M rows by
default, in ``GeoDataset(n_shards=8)``. Every answer is checked against a
plain numpy brute force over the same arrays; the line before the last
names the execution path that served each query.

    python chip_smoke.py                 # one chip (what the driver runs)
    python chip_smoke.py --chips 4       # the multi-chip path only

The last line of standard output, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
With no TPU it exits non-zero and prints no such line: there is no CPU
fallback. Device-scan failures raise instead of being recomputed on the host
(``GEOMESA_TPU_STRICT_DEVICE`` is set before the package is imported).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SCHEMA = "gdelt"
SPEC = "weight:Float,dtg:Date,*geom:Point"
BBOX = (-100.0, 30.0, -80.0, 45.0)
POLYGON = ((-120, 26), (-84, 25), (-70, 42), (-100, 48), (-122, 46),
           (-120, 26))


class Window:
    """One time window's queries: BBOX AND DURING (count, density, stats)
    and the bench's polygon AND DURING (count)."""

    def __init__(self, start: str, end: str):
        self.start, self.end = start, end
        during = f"dtg DURING {start}/{end}"
        self.ecql = f"BBOX(geom, -100, 30, -80, 45) AND {during}"
        self.poly_ecql = ("INTERSECTS(geom, POLYGON(("
                          + ", ".join(f"{x} {y}" for x, y in POLYGON)
                          + f"))) AND {during}")


#: the bench's window (``bench.py`` main): ten days of the month
TEN_DAYS = Window("2020-01-05T00:00:00Z", "2020-01-15T00:00:00Z")
#: the whole month: every time partition, so the sharded scan reaches every
#: device (a ten-day window prunes to two or three weekly partitions)
MONTH = Window("2020-01-01T00:00:00Z", "2020-02-01T00:00:00Z")
GRID = 512
STATS = "Count();MinMax(weight)"

#: Tolerances. Counts, unweighted grids and the polygon count are exact.
#: Weighted grids sum f32 weights in another order than the f64 reference:
#: each cell within WEIGHT_RTOL of the reference (plus WEIGHT_ATOL for
#: near-empty cells). MinMax(weight) reads f32 values back through the
#: stat's float fields: within MINMAX_RTOL.
WEIGHT_RTOL = 1e-5
WEIGHT_ATOL = 1e-5
MINMAX_RTOL = 1e-6


class SmokeFailure(AssertionError):
    """A phase of the smoke produced a wrong answer or a wrong path."""


_T0 = time.perf_counter()


def log(msg: str) -> None:
    """One progress line, stamped with the seconds since the script began."""
    print(f"[{time.perf_counter() - _T0:8.2f}s] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# data and the numpy reference
# ---------------------------------------------------------------------------
def make_data(rows: int, seed: int):
    """The bench generator (``bench.py`` main): uniform CONUS points over one
    month per 20M rows, f32 weights."""
    from geomesa_tpu.filter.ecql import parse_iso_ms

    rng = np.random.default_rng(seed)
    lo = parse_iso_ms("2020-01-01")
    span = int((parse_iso_ms("2020-02-01") - lo) * max(rows / 20_000_000, 1.0))
    return {
        "geom__x": rng.uniform(-125, -66, rows),
        "geom__y": rng.uniform(24, 49, rows),
        "dtg": rng.integers(lo, lo + span, rows).astype("datetime64[ms]"),
        "weight": rng.uniform(0, 1, rows).astype(np.float32),
    }


def reference(data, win: Window = TEN_DAYS):
    """Brute-force answers over the raw arrays, written independently of
    the package: the exact f64 BBOX/DURING predicate; grid cells from the
    f32 coordinates the store keeps (f32 arithmetic, as the device bins);
    the polygon by even-odd crossing parity over f32 points and an f32 edge
    table (the documented f32 semantics of general polygon edges)."""
    from geomesa_tpu.filter.ecql import parse_iso_ms

    x, y = data["geom__x"], data["geom__y"]
    t = data["dtg"].astype(np.int64)
    in_time = (t >= parse_iso_ms(win.start)) & (t <= parse_iso_ms(win.end))
    xmin, ymin, xmax, ymax = BBOX
    m = in_time & (x >= xmin) & (x <= xmax) & (y >= ymin) & (y <= ymax)

    x32 = x[m].astype(np.float32)
    y32 = y[m].astype(np.float32)
    f = np.float32
    px = np.clip(((x32 - f(xmin)) / f(xmax - xmin) * f(GRID)).astype(np.int32),
                 0, GRID - 1)
    py = np.clip(((y32 - f(ymin)) / f(ymax - ymin) * f(GRID)).astype(np.int32),
                 0, GRID - 1)
    cell = py.astype(np.int64) * GRID + px
    grid = np.bincount(cell, minlength=GRID * GRID).reshape(GRID, GRID)
    w = data["weight"][m]
    wgrid = np.bincount(cell, weights=w.astype(np.float64),
                        minlength=GRID * GRID).reshape(GRID, GRID)

    xs = x[in_time].astype(np.float32)
    ys = y[in_time].astype(np.float32)
    inside = np.zeros(len(xs), bool)
    for (x1, y1), (x2, y2) in zip(POLYGON[:-1], POLYGON[1:]):
        if y1 == y2:
            continue
        slope = f((x2 - x1) / (y2 - y1))
        ex1, ey1, ey2 = f(x1), f(y1), f(y2)
        cross = (ey1 > ys) != (ey2 > ys)
        inside ^= cross & (xs < ex1 + (ys - ey1) * slope)
    return {
        "count": int(m.sum()),
        "grid": grid.astype(np.float32),
        "wgrid": wgrid,
        "wmin": float(w.min()) if len(w) else None,
        "wmax": float(w.max()) if len(w) else None,
        "poly_count": int(inside.sum()),
    }


# ---------------------------------------------------------------------------
# checks
# ---------------------------------------------------------------------------
def check_answers(tag: str, got: dict, ref: dict) -> None:
    check(got["count"] == ref["count"],
          f"{tag}: count {got['count']} != numpy {ref['count']}")
    g = np.asarray(got["grid"])
    check(g.shape == (GRID, GRID), f"{tag}: grid shape {g.shape}")
    diff = g != ref["grid"]
    check(not diff.any(),
          f"{tag}: unweighted grid differs from numpy in {int(diff.sum())} "
          f"cells (sum {float(g.sum())} vs {float(ref['grid'].sum())})")
    wg = np.asarray(got["wgrid"], np.float64)
    werr = np.abs(wg - ref["wgrid"])
    bound = WEIGHT_ATOL + WEIGHT_RTOL * np.abs(ref["wgrid"])
    check(bool(np.isfinite(wg).all()) and bool((werr <= bound).all()),
          f"{tag}: weighted grid off numpy by up to {float(werr.max())} "
          f"({int((werr > bound).sum())} cells over tolerance)")
    count, (lo, hi) = got["stats"]
    check(count == ref["count"],
          f"{tag}: stats Count {count} != numpy {ref['count']}")
    check(np.isclose(lo, ref["wmin"], rtol=MINMAX_RTOL, atol=0)
          and np.isclose(hi, ref["wmax"], rtol=MINMAX_RTOL, atol=0),
          f"{tag}: MinMax(weight) ({lo}, {hi}) != numpy "
          f"({ref['wmin']}, {ref['wmax']})")
    check(got["poly_count"] == ref["poly_count"],
          f"{tag}: polygon count {got['poly_count']} != numpy "
          f"{ref['poly_count']}")
    log(f"{tag}: answers match numpy (count={got['count']}, "
        f"polygon={got['poly_count']}, weighted max err "
        f"{float(werr.max()):.3g})")


def served_path(ds, op: str) -> dict:
    """The newest audit event's ``exec_path`` (what explain(analyze=True)
    reports) for ``op``."""
    for ev in reversed(ds.audit.recent(20)):
        if ev.hints.get("op") == op:
            return dict(ev.hints.get("exec_path") or {})
    raise SmokeFailure(f"no audit event for {op}")


def check_device_path(tag: str, path: dict, density: bool = False,
                      pip: bool = False) -> None:
    scan = str(path.get("scan", ""))
    check(scan.startswith("device"), f"{tag}: served by scan={scan!r} {path}")
    check("device_error" not in path, f"{tag}: device error {path}")
    if density:
        check(path.get("density_kernel") == "pallas-grouped-mxu",
              f"{tag}: density kernel {path.get('density_kernel')!r}")
    if pip and path.get("kernel") == "trace":
        # kernel choices are recorded when the scan is traced; a cached
        # kernel is the one checked on its first call
        check(path.get("kernel:pip") == "pallas",
              f"{tag}: polygon kernel {path.get('kernel:pip')!r}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def ingest(ds, data, spec: str = SPEC, name: str = SCHEMA,
           chunk: int = 5_000_000) -> float:
    t0 = time.perf_counter()
    ds.create_schema(name, spec)
    n = len(data["weight"])
    for lo in range(0, n, chunk):
        hi = min(lo + chunk, n)
        ds.insert(name, {k: v[lo:hi] for k, v in data.items()},
                  fids=np.arange(lo, hi).astype(str))
    ds.flush(name)
    return time.perf_counter() - t0


def run_queries(ds, win: Window = TEN_DAYS, kernels: bool = True,
                timings: dict = None, name: str = SCHEMA) -> dict:
    """The five queries through ``GeoDataset``. Each one's execution path
    is printed and must be a device scan; with ``kernels`` also the Pallas
    density and point-in-polygon kernels (the one-chip compacted layout)."""
    from geomesa_tpu.stats.sketches import CountStat, MinMax

    def run(label, op, fn, density=False, pip=False):
        t0 = time.perf_counter()
        out = fn()
        if timings is not None:
            timings.setdefault(label, []).append(time.perf_counter() - t0)
        p = served_path(ds, op)
        check_device_path(label, p, density=kernels and density,
                          pip=kernels and pip)
        log(f"path {label}: {p}")
        return out

    got = {"count": run("count", "count", lambda: ds.count(name, win.ecql))}
    got["grid"] = run("density", "density", lambda: ds.density(
        name, win.ecql, bbox=BBOX, width=GRID, height=GRID), density=True)
    got["wgrid"] = run("density_weighted", "density", lambda: ds.density(
        name, win.ecql, bbox=BBOX, width=GRID, height=GRID,
        weight="weight"), density=True)
    st = run("stats", "stats", lambda: ds.stats(name, STATS, win.ecql))
    c, mm = st.stats
    check(isinstance(c, CountStat) and isinstance(mm, MinMax),
          f"stats shape {st!r}")
    got["stats"] = (c.count, (mm.lo, mm.hi))
    got["poly_count"] = run("polygon_count", "count",
                            lambda: ds.count(name, win.poly_ecql), pip=True)
    return got


def run_flight(ds, ref: dict) -> None:
    """The same queries served by the Flight sidecar in this process."""
    from geomesa_tpu.sidecar.client import GeoFlightClient
    from geomesa_tpu.sidecar.service import GeoFlightServer

    srv = GeoFlightServer(ds, "grpc+tcp://127.0.0.1:0")
    win = TEN_DAYS

    def served(label, op, fn, density=False):
        out = fn()
        p = served_path(ds, op)
        check_device_path(f"flight {label}", p, density=density)
        log(f"path flight {label}: {p}")
        return out

    try:
        with GeoFlightClient(f"grpc+tcp://127.0.0.1:{srv.port}") as c:
            got = {"count": served(
                "count", "count", lambda: c.count(SCHEMA, win.ecql))}
            got["grid"] = served("density", "density", lambda: c.density(
                SCHEMA, win.ecql, bbox=BBOX, width=GRID, height=GRID),
                density=True)
            got["wgrid"] = served(
                "density_weighted", "density", lambda: c.density(
                    SCHEMA, win.ecql, bbox=BBOX, width=GRID, height=GRID,
                    weight="weight"), density=True)
            cs, mm = served("stats", "stats", lambda: c.stats(
                SCHEMA, STATS, win.ecql)).stats
            got["stats"] = (cs.count, (mm.lo, mm.hi))
            got["poly_count"] = served(
                "polygon_count", "count",
                lambda: c.count(SCHEMA, win.poly_ecql))
    finally:
        srv.shutdown()
    check_answers("flight", got, ref)


def one_chip(rows: int, seed: int) -> None:
    from geomesa_tpu import GeoDataset, native

    log(f"native host library: available={native.available()}")
    t0 = time.perf_counter()
    data = make_data(rows, seed)
    ref = reference(data)
    log(f"data: {rows} rows, seed {seed}, generated + numpy reference in "
        f"{time.perf_counter() - t0:.3f} s")

    ds = GeoDataset(n_shards=8)
    log(f"ingest: {ingest(ds, data):.3f} s for {rows} rows")

    timings = {}
    got = run_queries(ds, timings=timings)
    check_answers("GeoDataset", got, ref)
    run_queries(ds, timings=timings)  # warm: compiled kernels
    for label, (cold, warm) in timings.items():
        log(f"compile {label}: first call {cold:.3f} s, warm call "
            f"{warm:.3f} s (host clock, includes transfer)")
    run_flight(ds, ref)


def four_chips(rows: int, seed: int) -> None:
    """The multi-chip path and what it is compared with: the same data in a
    meshed ``GeoDataset`` and in a time-partitioned store whose sharded
    scan fans partitions out over the devices. Each answer is bit-identical
    to the one-chip answer (weighted grids, whose f32 partials merge in
    another order, are held to the numpy tolerance instead) and checked
    against numpy."""
    import jax

    from geomesa_tpu import GeoDataset, config, metrics
    from geomesa_tpu.parallel.mesh import shard_mesh

    n_dev = 4
    devs = jax.devices()
    check(len(devs) >= n_dev, f"--chips 4 needs 4 devices, found {len(devs)}")
    data = make_data(rows, seed)
    ref = {TEN_DAYS: reference(data, TEN_DAYS), MONTH: reference(data, MONTH)}

    single = GeoDataset(n_shards=8)
    log(f"ingest one-chip: {ingest(single, data):.3f} s")
    one = {}
    for win in (TEN_DAYS, MONTH):
        # a whole-month window scans most rows: the padded layout, whose
        # density is XLA's scatter, serves it
        one[win] = run_queries(single, win, kernels=win is TEN_DAYS)
        check_answers(f"one chip {win.start[:10]}", one[win], ref[win])

    meshed = GeoDataset(mesh=shard_mesh(n_dev), n_shards=8)
    log(f"ingest mesh: {ingest(meshed, data):.3f} s")
    got = run_queries(meshed, kernels=False)
    holders = _column_holders(meshed)
    check(len(holders) == n_dev and all(v > 0 for v in holders.values()),
          f"mesh columns not spread over {n_dev} devices: {holders}")
    log(f"mesh columns: elements per device {holders}")
    check_identical("mesh", got, one[TEN_DAYS], weighted=False)
    check_answers("mesh", got, ref[TEN_DAYS])

    part = GeoDataset(n_shards=8)
    spec = SPEC + ";geomesa.partition='time'"
    log(f"ingest partitioned: {ingest(part, data, spec):.3f} s")
    reg = metrics.registry()

    def per_device():
        return {d.id: reg.counter(
            f"{metrics.SCAN_SHARDED_DEVICE}.{d.id}").value for d in devs}

    before = per_device()
    with config.MESH_DEVICES.scoped(str(n_dev)):
        sharded = run_queries(part, MONTH, kernels=False)
    used = {k: v - before[k] for k, v in per_device().items()}
    check(sum(1 for v in used.values() if v > 0) == n_dev,
          f"sharded scan did not reach all {n_dev} devices: {used}")
    log(f"sharded partitioned scan: dispatches per device {used}")
    with config.MESH_DEVICES.scoped("off"):
        serial = run_queries(part, MONTH, kernels=False)
    check_identical("sharded scan vs the same store on one chip", sharded,
                    serial)
    check_identical("sharded scan vs the one-chip store", sharded, one[MONTH],
                    weighted=False)
    check_answers("sharded scan", sharded, ref[MONTH])


def _column_holders(ds) -> dict:
    """Elements of the meshed store's device-resident x column that each
    device holds (the column the queries above uploaded)."""
    st, _, plan = ds._plan(SCHEMA, TEN_DAYS.ecql)
    table = st.tables[plan.index_name]
    held = {}
    for cols in table._device_cache.values():
        arr = cols.get("geom__x")
        if arr is None or arr.sharding.is_fully_replicated:
            continue
        for s in arr.addressable_shards:
            held[s.device.id] = held.get(s.device.id, 0) + s.data.size
    return held


def check_identical(tag: str, got: dict, want: dict,
                    weighted: bool = True) -> None:
    """Bit-for-bit equality of every answer. ``weighted=False`` leaves the
    weighted grid to :func:`check_answers`: stores that merge f32 partials
    in another order sum the same weights in another order."""
    check(got["count"] == want["count"], f"{tag}: count differs")
    check(np.array_equal(got["grid"], want["grid"]),
          f"{tag}: unweighted grid differs")
    if weighted:
        check(np.array_equal(got["wgrid"], want["wgrid"]),
              f"{tag}: weighted grid differs")
    check(got["stats"] == want["stats"], f"{tag}: stats differ")
    check(got["poly_count"] == want["poly_count"],
          f"{tag}: polygon count differs")
    log(f"{tag}: bit-identical to the one-chip answers")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=20_000_000)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path")
    args = ap.parse_args(argv)
    # a failed device scan must raise, not be answered on the host
    os.environ["GEOMESA_TPU_STRICT_DEVICE"] = "1"

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devs[0].platform}); "
              "refusing to run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from geomesa_tpu.kernels.registry import enable_persistent_cache

    log(f"device: {devs[0].device_kind} x{len(devs)}, jax {jax.__version__}, "
        f"compile cache {enable_persistent_cache()}")
    try:
        (one_chip if args.chips == 1 else four_chips)(args.rows, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
