"""Stats sketches vs numpy oracles (reference analog: geomesa-utils stats tests)."""

import numpy as np
import pytest

from geomesa_tpu.stats import (
    CountStat, DescriptiveStats, EnumerationStat, Frequency, GroupBy,
    Histogram, MinMax, SeqStat, Stat, TopK, Z3HistogramStat, parse_stat,
)


@pytest.fixture
def cols(rng):
    n = 5000
    return {
        "v": rng.normal(10, 5, n),
        "cat": rng.integers(0, 7, n),
        "geom__x": rng.uniform(-75, -73, n),
        "geom__y": rng.uniform(40, 42, n),
        "dtg": rng.integers(1_600_000_000_000, 1_601_000_000_000, n).astype(np.int64),
    }


def roundtrip(s: Stat) -> Stat:
    return Stat.from_json(s.to_json())


def test_count_observe_merge_unobserve(cols):
    a, b = CountStat(), CountStat()
    a.observe(cols)
    mask = cols["cat"] == 3
    b.observe(cols, mask)
    assert a.value() == 5000
    assert b.value() == int(mask.sum())
    a.merge(b)
    assert a.value() == 5000 + int(mask.sum())
    a.unobserve(cols, mask)
    assert a.value() == 5000
    assert roundtrip(a).value() == a.value()


def test_minmax_numeric_and_geom(cols):
    m = MinMax("v")
    m.observe(cols)
    assert m.value()["min"] == pytest.approx(cols["v"].min())
    assert m.value()["max"] == pytest.approx(cols["v"].max())
    g = MinMax("geom")
    g.observe(cols)
    assert g.value()["min"][0] == pytest.approx(cols["geom__x"].min())
    assert g.value()["max"][1] == pytest.approx(cols["geom__y"].max())
    # split-merge == whole
    h1, h2 = MinMax("v"), MinMax("v")
    h1.observe({"v": cols["v"][:2000]})
    h2.observe({"v": cols["v"][2000:]})
    h1.merge(h2)
    assert h1.value() == m.value()
    assert roundtrip(h1).value() == m.value()


def test_enumeration_and_topk(cols):
    e = EnumerationStat("cat")
    e.observe(cols)
    vals, counts = np.unique(cols["cat"], return_counts=True)
    for v, c in zip(vals.tolist(), counts.tolist()):
        assert e.counts[v] == c
    t = TopK("cat", 3)
    t.observe(cols)
    top = t.value()
    assert len(top) == 3
    assert top[0][1] == counts.max()
    assert roundtrip(t).value() == top


def test_histogram_merge_and_selectivity(cols):
    h = Histogram("v", 50, -10.0, 30.0)
    h.observe(cols)
    assert int(h.counts.sum()) == 5000
    # split-merge equivalence
    h1, h2 = Histogram("v", 50, -10.0, 30.0), Histogram("v", 50, -10.0, 30.0)
    h1.observe({"v": cols["v"][:1000]})
    h2.observe({"v": cols["v"][1000:]})
    h1.merge(h2)
    np.testing.assert_array_equal(h1.counts, h.counts)
    # selectivity estimate close to truth for an aligned range
    est = h.count_between(0.0, 20.0)
    truth = int(((cols["v"] >= 0) & (cols["v"] <= 20)).sum())
    assert abs(est - truth) / truth < 0.1
    assert roundtrip(h).value() == h.value()


def test_frequency_overestimates_bounded(cols):
    f = Frequency("cat", width=256)
    f.observe(cols)
    vals, counts = np.unique(cols["cat"], return_counts=True)
    for v, c in zip(vals.tolist(), counts.tolist()):
        assert f.count(v) >= c  # count-min never underestimates
        assert f.count(v) <= c + 5000 // 256 * 4  # loose CM bound
    f2 = Frequency("cat", width=256)
    f2.observe(cols)
    f.merge(f2)
    assert f.count(int(vals[0])) >= 2 * int(counts[0])
    assert roundtrip(f).count(int(vals[0])) == f.count(int(vals[0]))


def test_descriptive_stats(cols):
    d = DescriptiveStats(["v"])
    d.observe(cols)
    v = d.value()
    assert v["mean"][0] == pytest.approx(cols["v"].mean())
    assert v["stddev"][0] == pytest.approx(cols["v"].std(), rel=1e-6)
    d1, d2 = DescriptiveStats(["v"]), DescriptiveStats(["v"])
    d1.observe({"v": cols["v"][:777]})
    d2.observe({"v": cols["v"][777:]})
    d1.merge(d2)
    assert d1.value()["mean"][0] == pytest.approx(v["mean"][0])


def test_groupby(cols):
    g = GroupBy("cat", "MinMax(v)")
    g.observe(cols)
    for k, sub in g.value().items():
        sel = cols["cat"] == k
        assert sub["min"] == pytest.approx(cols["v"][sel].min())
    assert roundtrip(g).value().keys() == g.value().keys()


def test_z3histogram_estimate(cols):
    z = Z3HistogramStat("geom", "dtg", "week", 1024)
    z.observe(cols)
    assert sum(z.value().values()) == 5000
    # estimate over the full window ~ total count
    from geomesa_tpu.curves.zorder import Z3SFC

    sfc = Z3SFC("week")
    bins = np.array(sorted(z.bins.keys()))
    # Whole-space cover -> estimate must equal the exact total.
    from geomesa_tpu.curves.cover import ZRange

    whole = [ZRange(0, (1 << 63) - 1)]
    est = z.estimate_count(bins, whole)
    assert est == pytest.approx(5000, rel=0.01)
    # A small-bbox cover must be monotonically smaller, never negative.
    lo, hi = sfc.ranges((-75, -73), (40, 42), (0, float(sfc.binned.max_offset_ms)))
    ranges = [ZRange(a, b) for a, b in zip(lo.tolist(), hi.tolist())]
    sub = z.estimate_count(bins, ranges)
    assert 0 <= sub <= est
    rt = roundtrip(z)
    assert rt.estimate_count(bins, whole) == pytest.approx(est)


def test_parser_roundtrip(cols):
    s = parse_stat(
        "Count();MinMax(v);Histogram(v,20,-10,30);TopK(cat,5);"
        "GroupBy(cat,DescriptiveStats(v));Z3Histogram(geom,dtg,week,512)"
    )
    assert isinstance(s, SeqStat)
    s.observe(cols)
    vals = s.value()
    assert vals[0] == 5000
    rt = roundtrip(s)
    assert rt.value()[0] == 5000


def test_parser_errors():
    with pytest.raises(ValueError):
        parse_stat("Bogus(x)")
    with pytest.raises(ValueError):
        parse_stat("MinMax(")
    with pytest.raises(ValueError):
        parse_stat("")
