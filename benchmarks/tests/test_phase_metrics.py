"""The readers of the executor's phase spans and the kernel's rows, on
hand-built span trees, and in the tiny traced cell."""

import os

import pytest

from benchmarks import harness
from benchmarks.tests import tiny

PHASE = ("executor.windows_ms", "executor.compact_ms",
         "executor.schedule_ms", "kernel.rows_per_match")


def reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", name + ".py"),
        "test_metric_" + name.replace(".", "_"))


def span(name, ms, *children, **attrs):
    s = {"name": name, "ms": ms}
    if attrs:
        s["attrs"] = attrs
    if children:
        s["children"] = list(children)
    return s


def context(spans, matched, pools):
    records = [{"seq": seq, "pool": pools[seq], "ok": True}
               for seq in spans]
    return harness.Context(None, [], records, {}, {}, spans, None, {},
                           matched, None, None, None, None, None)


# request 0: a cold view (every miss-path span); request 1: a warm repeat
COLD = span(
    "density", 400.0,
    span("plan", 0.1),
    span("scan.windows", 10.0, rows=5000),
    span("scan.compact", 150.0,
         span("scan.windows.fine", 120.0, rows=3000, ranges=4000),
         B=128, C=30, rows=3840),
    span("scan.schedule", 40.0, kernel="grouped", pairs=64),
    span("scan.device_put", 30.0, span("scan.gather", 25.0, columns=3,
                                       rows=4096), compact=True),
    span("scan.kernel", 1.0, compact=True, site="density", rows=4096),
    span("scan.sync", 8.0),
)
WARM = span(
    "density", 12.0,
    span("plan", 0.1),
    span("scan.kernel", 1.0, compact=True, site="density", rows=4096),
    span("scan.sync", 8.0),
)


def test_phase_readers_on_known_trees():
    ctx = context({0: [COLD], 1: [WARM]}, {7: 2048, 8: 2048},
                  {0: 7, 1: 8})
    got = {m: reader(m).read(ctx) for m in PHASE}
    # windows: coarse 10 + fine 120 over two requests (the warm one adds 0)
    assert got["executor.windows_ms"] == pytest.approx(65.0)
    # compact: self time only (150 less the nested fine cover 120)
    assert got["executor.compact_ms"] == pytest.approx(15.0)
    assert got["executor.schedule_ms"] == pytest.approx(20.0)
    # 8192 rows read for 4096 matched
    assert got["kernel.rows_per_match"] == pytest.approx(2.0)
    # executor.host_ms no longer holds the phases: op self time is the
    # unspanned remainder
    host = reader("executor.host_ms").read(ctx)
    assert host == pytest.approx(((400.0 - 0.1 - 10 - 150 - 40 - 30
                                   - 1 - 8) + 30 + (12 - 0.1 - 1 - 8)) / 2)


def test_phase_readers_with_a_trace_per_fused_member():
    # a request can carry several finished trees (one per server root)
    ctx = context({0: [COLD, WARM]}, {3: 1000}, {0: 3})
    assert reader("executor.windows_ms").read(ctx) == pytest.approx(130.0)
    assert reader("kernel.rows_per_match").read(ctx) == \
        pytest.approx(8192 / 1000)


def test_phase_readers_without_the_spans():
    # a program without the spans or the rows attribute: nothing to read
    bare = span("density", 300.0, span("plan", 0.1),
                span("scan.kernel", 1.0, site="density"),
                span("scan.sync", 8.0))
    ctx = context({0: [bare]}, {1: 10}, {0: 1})
    for m in PHASE:
        assert reader(m).read(ctx) is None, m
    # no request of the window traced
    empty = context({}, {}, {})
    for m in PHASE:
        assert reader(m).read(empty) is None, m


def test_warm_requests_read_zero_and_nothing_matched_reads_none():
    ctx = context({0: [WARM], 1: [WARM]}, {5: 0}, {0: 5, 1: 5})
    assert reader("kernel.rows_per_match").read(ctx) is None
    # warm requests alone: the phase metrics read 0, the cache-hit case
    for m in PHASE[:3]:
        assert reader(m).read(ctx) == 0.0, m


@pytest.fixture
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("GEOMESA_COMPILE_CACHE_DIR", str(tmp_path / "jax"))


def test_traced_tiny_cell_reports_phase_metrics(_cache):
    res, info, err = tiny.run(tiny.cell("gdelt.heatmap_pow2"), trace=True)
    assert res["correct"], (info, err)
    for m in PHASE:
        assert m in res["metrics"], (m, sorted(res["metrics"]))
        assert res["metrics"][m]["value"] >= 0
    assert res["metrics"]["kernel.rows_per_match"]["value"] >= 1
