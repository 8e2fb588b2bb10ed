"""The executor's per-view caches (``planning/viewcache.py``): the
clear-on-full policy, which caches a cold request fills, and the exact
sequence of hits and misses a cyclic view pool meets.

The benchmark's view pools are sized against this policy, so it is pinned
here: a change to it (an LRU, another capacity) fails the replay test and
belongs with resized pools."""

import collections
import importlib.util
import itertools
import os

import numpy as np
import pytest

from geomesa_tpu import GeoDataset, config, tracing
from geomesa_tpu.filter.ecql import parse_iso_ms
from geomesa_tpu.planning.viewcache import BoundedCache, caches_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("capacity", [32, 64, 256])
def test_bounded_cache_empties_when_a_put_finds_it_full(capacity):
    c = BoundedCache(capacity)
    for i in range(capacity):
        assert c.put(i, str(i)) == str(i)
    assert len(c) == capacity and c.get(0) == "0"
    c.put("over", 1)
    assert dict(c.items()) == {"over": 1}
    assert c.get(0) is None


def test_put_all_checks_fullness_once():
    """One miss's entries (the slabs of a scan's columns) go in together:
    the cache may pass its capacity until the next put finds it full."""
    c = BoundedCache(4)
    for i in range(3):
        c.put(i, i)
    c.put_all({"a": 1, "b": 2, "c": 3})
    assert len(c) == 6
    c.put_all({"d": 4})
    assert dict(c.items()) == {"d": 4}


def _store(n=20_000, seed=13):
    rng = np.random.default_rng(seed)
    lo, hi = parse_iso_ms("2020-01-01"), parse_iso_ms("2020-02-01")
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
    return ds


def _ecql(x0, y0, w, d0, days):
    return (f"BBOX(geom, {x0}, {y0}, {x0 + w}, {y0 + w / 2}) AND dtg DURING "
            f"2020-01-{d0:02d}T00:00:00Z/2020-01-{d0 + days:02d}T00:00:00Z")


@pytest.fixture
def compact():
    with config.COMPACT_MIN_ROWS.scoped("0"), \
            config.COMPACT_FRACTION.scoped("2.0"), \
            config.TRACE_ENABLED.scoped("true"):
        yield


def _kinds(name, cache):
    """Entry kinds of one named cache: the key's tag, the slab's column,
    the ladder's rung, or one kind for caches of a single entry type."""
    def kind(k, v):
        if name in ("resolved", "device_windows", "shared_descriptors"):
            return k[0]
        if name == "slabs":
            return k[-1]
        if name == "ladder":
            return v[0]
        return "entry"
    return dict(collections.Counter(kind(k, v) for k, v in cache.items()))


def test_a_cold_density_and_stats_request_fill_each_named_cache(compact):
    """Entry kinds and counts after one cold density request and one cold
    stats request, as the executor's caches held them before they had one
    owner."""
    ds = _store()
    ds.density("t", _ecql(-100, 30, 16, 5, 10), bbox=(-100, 30, -84, 38),
               width=256, height=256)
    ds.stats("t", "Count();MinMax(weight)", _ecql(-110, 35, 8, 3, 7))
    caches = caches_of(ds._store("t"))
    got = {n: _kinds(n, getattr(caches, n)) for n in caches.__slots__}
    assert got == {
        "resolved": {"win": 2, "fine": 2, "compact_desc": 2},
        "device_windows": {"compact_win": 2},
        "slabs": {"geom__x": 2, "geom__y": 2, "dtg__bin": 2, "dtg__off": 2,
                  "weight": 1},
        "shared_descriptors": {"flat": 2},
        "ladder": {"mxu": 1},
        "chunk_boxes": {"entry": 1},
        "sample_spans": {},
        "band_verdicts": {"entry": 2},
        "curve_positions": {},
    }
    caps = {n: getattr(caches, n).capacity for n in caches.__slots__}
    assert caps == dict({n: 64 for n in caches.__slots__},
                        band_verdicts=256, curve_positions=32)


#: the phases a cold view pays, one bit each in a visit's code
PHASES = ("scan.windows", "scan.compact", "scan.windows.fine",
          "scan.schedule")

#: the codes of the replay below on the executor before its caches had
#: one owner, one line a cycle of 72 views
RECORDED = (
    "ffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff"
    "fffffff0ffffffffffff7fff7ff7ffffffffffffffffffffff7ffffffffff7ffffffffff"
    "f88f0f8f0ff0ffff7f7ffffff7ffffffffff7fffff77fffffffffffffff7ffffffffffff"
    "ffffffffffffffffffffffffffffffff7fff7fffffffffffff7f7f7fffff7f7fffffffff"
    "7ffffffff7fffff7ffffff7f77f7fffffffff7ffffff7fffffffffffffffffffffffffff"
)


def _views(n=72, seed=28):
    rng = np.random.default_rng(seed)
    out = []
    while len(out) < n:
        v = (float(rng.choice([2, 4, 8])), float(rng.integers(-118, -80)),
             float(rng.integers(27, 45)), int(rng.integers(1, 20)),
             int(rng.integers(1, 10)))
        if v not in out:
            out.append(v)
    return out


def _visit(ds, view):
    """One density view's host phases (no kernel compiles): the code of
    the phases that missed their cache."""
    w, x0, y0, d0, days = view
    with tracing.start("visit") as root:
        st, _, plan = ds._plan("t", _ecql(x0, y0, w, d0, days))
        ex = ds._executor(st)
        setup = ex._scan_setup(plan, ("geom__x", "geom__y"))
        ex._maybe_compact(plan, setup, True)
        assert setup["compact"], view
        ex._density_ladder(setup, (x0, y0, x0 + w, y0 + w / 2), 256, 256)
    names, todo = set(), [root.to_dict()]
    while todo:
        s = todo.pop()
        todo.extend(s.get("children", ()))
        names.add(s["name"])
    return "%x" % sum(1 << i for i, p in enumerate(PHASES) if p in names)


def _model(order, capacity=64):
    """The same visits against plain dicts emptied when a put finds them
    full: coarse windows, descriptor and fine windows share one cache,
    the ladder has its own."""
    resolved, ladder = {}, {}

    def put(cache, key):
        if len(cache) >= capacity:
            cache.clear()
        cache[key] = True

    codes = []
    for v in order:
        code = 0
        if ("win", v) not in resolved:
            code |= 1
            put(resolved, ("win", v))
        if ("desc", v) not in resolved:
            code |= 2
            if ("fine", v) not in resolved:
                code |= 4
                put(resolved, ("fine", v))
            put(resolved, ("desc", v))
        if v not in ladder:
            code |= 8
            put(ladder, v)
        codes.append("%x" % code)
    return "".join(codes)


def test_cyclic_view_pool_meets_the_recorded_hits_and_misses(compact):
    """72 distinct views, five cycles of seed-drawn permutations sent as
    the benchmark's ``loadgen.cyclic`` sends them: each visit's misses
    equal the recording and the clear-on-full model."""
    spec = importlib.util.spec_from_file_location(
        "_view_cache_loadgen", os.path.join(ROOT, "benchmarks/loadgen.py"))
    loadgen = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loadgen)
    ds = _store()
    views = _views()
    order = list(itertools.islice(loadgen.cyclic(2801, len(views)),
                                  5 * len(views)))
    seen = "".join(_visit(ds, views[i]) for i in order)
    assert seen == _model(order)
    assert seen == RECORDED
