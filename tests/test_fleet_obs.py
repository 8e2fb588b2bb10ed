"""Fleet-level observability tests (docs/OBSERVABILITY.md): per-device
utilization accounting, executor-slot occupancy, the per-query cost
ledger, the SLO burn-rate monitor, histogram exemplars, and the
/debug/devices + filtered /debug/queries surfaces.

Runs on the conftest-forced 8-virtual-device CPU mesh, so the sharded
fan-out's per-device attribution is exercised for real.
"""

import json

import numpy as np
import pytest

from geomesa_tpu import (
    GeoDataset, config, metrics, slo, tracing, utilization,
)
from geomesa_tpu.filter.ecql import parse_iso_ms
from geomesa_tpu.index.partitioned import PartitionedFeatureStore

BBOX = "BBOX(geom, -100, 30, -80, 45)"


def _mk_ds(n=4000, partitioned=False, seed=9, n_shards=2):
    spec = "name:String,weight:Float,dtg:Date,*geom:Point"
    if partitioned:
        spec += ";geomesa.partition='time'"
    ds = GeoDataset(n_shards=n_shards)
    ds.create_schema("t", spec)
    rng = np.random.default_rng(seed)
    lo, hi = parse_iso_ms("2020-01-01"), parse_iso_ms("2020-03-01")
    ds.insert("t", {
        "name": rng.choice(["a", "b"], n),
        "weight": rng.uniform(0, 1, n).astype(np.float32),
        "geom__x": rng.uniform(-120, -70, n),
        "geom__y": rng.uniform(25, 50, n),
        "dtg": rng.integers(lo, hi, n).astype("datetime64[ms]"),
    }, fids=np.arange(n).astype(str))
    ds.flush("t")
    return ds


# ---------------------------------------------------------------------------
# utilization interval math
# ---------------------------------------------------------------------------


def test_busy_fraction_window_math(monkeypatch):
    utilization.reset()
    now = [998.0]
    monkeypatch.setattr(utilization, "_clock", lambda: now[0])
    with config.DEVICE_BUSY_WINDOW.scoped("10"):
        # 2s in flight ending at t=1000 -> fraction 0.2 over the 10s window
        utilization.dispatched(3)
        now[0] = 1000.0
        utilization.settle()
        frac = utilization.snapshot()["devices"]["3"]["busy_fraction"]
        assert frac == pytest.approx(0.2, abs=1e-6)
        # window start (999) bisects the interval: 1 of its 2 busy
        # seconds remains inside -> fraction 0.1
        now[0] = 1009.0
        u = utilization._devices[3]
        assert u.fraction() == pytest.approx(0.1, abs=1e-6)
        # fully rolled out
        now[0] = 1020.0
        assert u.fraction() == 0.0
        # totals never roll: the cumulative busy_s survives the window
        assert u.busy_s == pytest.approx(2.0)
        # overlapping slot intervals (summed host brackets) clamp at 1.0
        utilization.record_slot(4, 8.0)
        utilization.record_slot(4, 8.0)
        assert utilization._slots[4].fraction() == 1.0


def test_device_busy_feeds_gauge_and_trace_cost():
    utilization.reset()
    with config.TRACE_ENABLED.scoped("true"):
        with tracing.start("op_cost_test"):
            utilization.dispatched(6)
            utilization.settle()
            cost = tracing.current_cost()
    assert "device_ms.6" in cost
    g = metrics.registry().gauge(f"{metrics.DEVICE_BUSY_PREFIX}.6")
    assert 0.0 <= g.value <= 1.0
    snap = utilization.snapshot()
    assert snap["devices"]["6"]["intervals"] == 1
    assert cost["device_ms.6"] == pytest.approx(
        snap["devices"]["6"]["busy_s"] * 1e3, rel=1e-6, abs=1e-3)


def test_overlapping_partition_settles_cost_the_union(monkeypatch):
    """Two partitions in flight on one device at once, each settled at its
    own read: the query's device_ms is the gauge's union, not the sum."""
    utilization.reset()
    now = [10.0]
    monkeypatch.setattr(utilization, "_clock", lambda: now[0])
    with config.TRACE_ENABLED.scoped("true"), \
            config.DEVICE_BUSY_WINDOW.scoped("60"):
        with tracing.start("op_union_test"):
            utilization.dispatched(2)
            first = utilization.detach()
            now[0] = 11.0
            utilization.dispatched(2)
            second = utilization.detach()
            now[0] = 14.0
            utilization.settle(first)    # [10, 14]
            now[0] = 16.0
            utilization.settle(second)   # [11, 16]: only [14, 16] is new
            cost = tracing.current_cost()
    assert utilization._devices[2].busy_s == pytest.approx(6.0)
    assert cost["device_ms.2"] == pytest.approx(6000.0)


def test_in_flight_interval_covers_the_sync_wait(monkeypatch):
    """A dispatch whose result the host reads later: the device's interval
    runs from the dispatch stamp to the read, not around the enqueue."""
    utilization.reset()
    now = [100.0]
    monkeypatch.setattr(utilization, "_clock", lambda: now[0])
    with config.DEVICE_BUSY_WINDOW.scoped("60"):
        utilization.dispatched(5)
        now[0] = 100.01   # the enqueue call returns
        now[0] = 103.0    # the host blocks on the result until here
        utilization.settle()
        u = utilization._devices[5]
        assert u.busy_s == pytest.approx(3.0)
        assert u.fraction() == pytest.approx(3.0 / 60)
        utilization.settle()   # nothing open: no second interval
        assert u.count == 1


def test_overlapping_in_flight_dispatches_count_as_union(monkeypatch):
    utilization.reset()
    now = [10.0]
    monkeypatch.setattr(utilization, "_clock", lambda: now[0])
    with config.DEVICE_BUSY_WINDOW.scoped("60"):
        # two pipelined dispatches on one device, one read: [10, 14]
        utilization.dispatched(7)
        now[0] = 11.0
        utilization.dispatched(7)
        now[0] = 14.0
        utilization.settle()
        assert utilization._devices[7].busy_s == pytest.approx(4.0)
        # a partition dispatched at 12 (as another lane would have, so the
        # clock steps back), carried to its own merge and read at 16: it
        # overlaps [10, 14], so only [14, 16] is new
        now[0] = 12.0
        utilization.dispatched(7)
        carried = utilization.detach()
        assert carried == [(7, 12.0)]
        now[0] = 16.0
        utilization.settle(carried)
        u = utilization._devices[7]
        assert u.busy_s == pytest.approx(6.0)
        assert u.fraction() == pytest.approx(6.0 / 60)
        # another device is its own union
        utilization.dispatched(8)
        now[0] = 17.0
        utilization.settle()
        assert utilization._devices[8].busy_s == pytest.approx(1.0)
        assert u.busy_s == pytest.approx(6.0)


def test_query_in_flight_time_covers_its_sync_span():
    """A real device query: the in-flight time the cost ledger and the
    device gauge get runs from dispatch through the scan.sync read."""
    utilization.reset()
    ds = _mk_ds(4000)
    with config.TRACE_ENABLED.scoped("true"):
        ds.density("t", BBOX, bbox=(-100, 30, -80, 45), width=64,
                   height=64)
        tr = tracing.last_trace()
    tree = tr.root.to_dict()

    def find(t, name):
        if t["name"] == name:
            return t
        for c in t.get("children", ()):
            hit = find(c, name)
            if hit is not None:
                return hit
        return None

    sync = find(tree, "scan.sync")
    kernel = find(tree, "scan.kernel")
    assert sync is not None and kernel is not None
    cost = tr.cost["device_ms.0"]
    assert cost >= sync["ms"] * 0.999
    assert cost <= tree["ms"] * 1.001
    assert utilization.snapshot()["devices"]["0"]["busy_s"] == \
        pytest.approx(cost / 1e3, rel=1e-6, abs=1e-6)


def test_sharded_scan_attributes_busy_time_across_devices(tmp_path):
    """The 8-virtual-device mesh: a sharded partitioned scan must leave
    busy intervals on MORE THAN ONE device (the CI smoke gate's
    in-process twin)."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device mesh")
    utilization.reset()
    ds = _mk_ds(20_000, partitioned=True)
    st = ds._store("t")
    assert isinstance(st, PartitionedFeatureStore)
    st.max_resident = 1
    st._spill_dir = str(tmp_path / "spill")
    n = ds.count("t", BBOX)
    assert n > 0
    busy = {k: v for k, v in utilization.snapshot()["devices"].items()
            if v["busy_s"] > 0}
    assert len(busy) > 1, f"busy time landed on {sorted(busy)} only"


def test_pool_slot_occupancy_and_wait_breakdown():
    utilization.reset()
    ds = _mk_ds(2000)
    with config.SERVING_EXECUTORS.scoped("2"):
        s = ds.serving.start()
        try:
            futs = [s.submit(lambda: ds.count("t", BBOX), user="u",
                             op="count") for _ in range(6)]
            [f.result(60) for f in futs]
        finally:
            s.stop()
    snap = utilization.snapshot()
    assert snap["slots"], "no slot occupancy recorded"
    assert sum(v["intervals"] for v in snap["slots"].values()) >= 6
    # queue-wait half of the breakdown recorded one sample per query
    assert snap["breakdown"]["waits"] >= 6
    assert snap["breakdown"]["device_time_s"] >= 0.0


# ---------------------------------------------------------------------------
# per-query cost ledger
# ---------------------------------------------------------------------------


def test_cost_ledger_rolls_into_user_rollups(tmp_path):
    ds = _mk_ds(20_000, partitioned=True)
    st = ds._store("t")
    st.max_resident = 1
    st._spill_dir = str(tmp_path / "spill")
    with config.TRACE_ENABLED.scoped("true"), config.USER.scoped("alice"):
        ds.count("t", BBOX)
    roll = ds.serving.user_rollups()["alice"]
    cost = roll["cost"]
    assert any(k.startswith("device_ms.") for k in cost), cost
    assert cost.get("partitions_scanned", 0) >= 2
    assert cost.get("bytes_staged", 0) > 0
    assert "partitions_pruned" in cost


def test_cache_hit_lands_in_cost_ledger():
    ds = _mk_ds(4000)
    with config.TRACE_ENABLED.scoped("true"), \
            config.CACHE_ENABLED.scoped("true"), \
            config.USER.scoped("bob"):
        ds.count("t", BBOX)
        ds.count("t", BBOX)  # whole-result hit
    cost = ds.serving.user_rollups()["bob"]["cost"]
    assert cost.get("cache_hits", 0) >= 1, cost


def test_explain_carries_cost_section():
    ds = _mk_ds(2000)
    with config.TRACE_ENABLED.scoped("true"):
        out = ds.explain("t", BBOX, analyze=True)
    assert "Cost" in out
    assert "device_ms." in out


# ---------------------------------------------------------------------------
# SLO burn-rate monitor
# ---------------------------------------------------------------------------


def _slo_scope(op, target_ms):
    return config.SystemProperty(
        f"geomesa.slo.{op}.p99.ms", None
    ).scoped(str(target_ms))


def test_slo_target_resolution():
    with _slo_scope("slo_res_op", 25):
        t = config.slo_targets()
        assert t["slo_res_op"] == 25.0


def test_burn_rate_window_arithmetic(monkeypatch):
    slo.reset()
    now = [10_000.0]
    monkeypatch.setattr(slo, "_clock", lambda: now[0])
    op = "slo_burn_op"
    hist = metrics.registry().histogram(f"trace.{op}")
    with _slo_scope(op, 100), \
            config.SLO_WINDOW_FAST_S.scoped("300"), \
            config.SLO_WINDOW_SLOW_S.scoped("3600"):
        m = slo.monitor()
        # t0: 100 healthy observations (1 ms, far under the 100 ms target)
        for _ in range(100):
            hist.observe(0.001)
        m.evaluate(force=True)
        assert m.burn(op, 300) == 0.0
        # t0+200s (t0 still inside the fast window): 96 healthy + 4 bad
        # on top of the 100 healthy -> 4/200 bad -> burn 2 over both
        # windows (the whole history sits inside each)
        now[0] += 200
        for _ in range(96):
            hist.observe(0.001)
        for _ in range(4):
            hist.observe(10.0)
        m.evaluate(force=True)
        assert m.burn(op, 300) == pytest.approx(
            (4 / 200) / slo.P99_BUDGET)
        assert m.burn(op, 3600) == pytest.approx(2.0)
        # t0+800s: the bad burst has rolled OUT of the fast window but is
        # still inside the slow one — fast burn recovers, slow remembers
        now[0] += 600
        hist.observe(0.001)
        m.evaluate(force=True)
        assert m.burn(op, 300) == 0.0
        slow_burn = m.burn(op, 3600)
        assert slow_burn > 1.0
        # the slo.burn.<op> gauge mirrors the fast window
        g = metrics.registry().gauge(f"{metrics.SLO_BURN_PREFIX}.{op}")
        assert g.value == 0.0
    slo.reset()


def test_healthz_degrades_when_fast_window_burns(monkeypatch):
    from geomesa_tpu import obs

    slo.reset()
    op = "slo_hot_op"
    hist = metrics.registry().histogram(f"trace.{op}")
    with _slo_scope(op, 1):
        for _ in range(10):
            hist.observe(5.0)  # every observation blows the 1 ms target
        h = obs.health()
        assert h["slo"][op]["hot"] is True
        assert op in h["slo_burning"]
        assert h["status"] == "degraded"
    slo.reset()
    # target retracted: healthy again (absent breakers/other burns)
    h = obs.health()
    assert op not in h.get("slo", {})


def test_over_count_snaps_target_to_bucket():
    h = metrics.Histogram()
    for v in (0.004, 0.004, 0.2, 0.2, 0.2):
        h.observe(v)
    # target 4 ms snaps to the 5 ms bucket bound: the two 4 ms
    # observations are within, the three 200 ms ones are over
    total, over = slo._over_count(h, 4.0)
    assert (total, over) == (5, 3)
    # a target beyond the largest bucket counts only +Inf overflow as over
    total, over = slo._over_count(h, 60_000.0)
    assert (total, over) == (5, 0)


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------


def test_histogram_exemplar_links_bucket_to_trace():
    reg = metrics.MetricRegistry(prefix="t")
    h = reg.histogram("trace.exemplar_op")
    h.observe(0.002)                      # no exemplar
    h.observe(0.2, trace_id="abc123def")  # exemplar on the 0.25 bucket
    text = reg.prometheus(exemplars=True)
    ex_lines = [ln for ln in text.splitlines() if "# {" in ln]
    assert len(ex_lines) == 1
    assert 'le="0.25"' in ex_lines[0]
    assert 'trace_id="abc123def"' in ex_lines[0]
    assert "0.200000" in ex_lines[0]
    # exemplar-free histograms render exactly as before (OpenMetrics)
    plain = [ln for ln in text.splitlines() if 'le="0.0025"' in ln]
    assert plain == ['t_trace_exemplar_op_seconds_bucket{le="0.0025"} 1']
    # the CLASSIC text format stays exemplar-free: a '#' suffix on a
    # sample line is a parse error for standard version=0.0.4 scrapers
    assert "# {" not in reg.prometheus()


def test_metrics_route_negotiates_openmetrics_for_exemplars():
    from geomesa_tpu import obs

    metrics.observe("trace.negotiate_op", 0.01, trace_id="feedbeef")
    # no Accept header: classic text, no exemplars
    code, ctype, body = obs.handle("/metrics")
    assert code == 200 and "0.0.4" in ctype
    assert b"# {" not in body
    # OpenMetrics negotiated: exemplars + the required EOF trailer
    code, ctype, body = obs.handle(
        "/metrics", accept="application/openmetrics-text"
    )
    assert code == 200 and ctype.startswith("application/openmetrics-text")
    assert b'trace_id="feedbeef"' in body
    assert body.endswith(b"# EOF\n")


def test_traced_query_leaves_exemplars(tmp_path):
    ds = _mk_ds(2000)
    with config.TRACE_ENABLED.scoped("true"):
        ds.count("t", BBOX)
        tid = tracing.last_trace().trace_id
    snap = metrics.registry().histogram("trace.count").snapshot()
    tids = {e[0] for e in snap["exemplars"].values()}
    assert tid in tids


# ---------------------------------------------------------------------------
# endpoints
# ---------------------------------------------------------------------------


def test_debug_devices_endpoint():
    import urllib.request

    from geomesa_tpu import obs

    ds = _mk_ds(1000)
    ds.count("t", BBOX)
    srv = obs.serve(ds, port=0, background=True)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debug/devices", timeout=10
        ) as r:
            assert r.status == 200
            d = json.loads(r.read())
        assert "devices" in d and "slots" in d and "breakdown" in d
        assert "slo" in d
        assert d["devices"], "no device usage recorded"
    finally:
        srv.shutdown()


def test_debug_queries_user_and_op_filters():
    from geomesa_tpu import obs

    ds = _mk_ds(2000)
    with config.USER.scoped("alice"):
        ds.count("t", BBOX)
        ds.density("t", BBOX, bbox=(-100, 30, -80, 45), width=16, height=16)
    with config.USER.scoped("bob"):
        ds.count("t", BBOX)
    all_q = obs.debug_queries(ds, n=50)
    assert len(all_q["queries"]) >= 3
    alice = obs.debug_queries(ds, n=50, user="alice")
    assert alice["queries"]
    assert all(e["user"] == "alice" for e in alice["queries"])
    assert set(alice["users"]) == {"alice"}
    dens = obs.debug_queries(ds, n=50, op="density")
    assert dens["queries"]
    assert all(e["hints"]["op"] == "density" for e in dens["queries"])
    # filters apply BEFORE the n cap
    one = obs.debug_queries(ds, n=1, user="alice", op="count")
    assert len(one["queries"]) == 1
    e = one["queries"][0]
    assert e["user"] == "alice" and e["hints"]["op"] == "count"
    # the HTTP route passes them through
    out = obs.handle("/debug/queries?n=5&user=bob&op=count", ds)
    assert out[0] == 200
    body = json.loads(out[2])
    assert all(e["user"] == "bob" for e in body["queries"])


def test_debug_queries_user_filter_joins_slow_traces():
    """Slow traces carry no user; the ?user= filter joins through the
    trace_id shared with that user's audit events, so one tenant's view
    never includes another's slow span trees."""
    from geomesa_tpu import obs

    tracing.clear_slow_traces()
    ds = _mk_ds(2000)
    with config.TRACE_ENABLED.scoped("true"), \
            config.TRACE_SLOW_MS.scoped("0"):
        with config.USER.scoped("alice"):
            ds.count("t", BBOX)
            alice_tid = tracing.last_trace().trace_id
        with config.USER.scoped("bob"):
            ds.count("t", BBOX)
            bob_tid = tracing.last_trace().trace_id
    out = obs.debug_queries(ds, n=50, user="alice")
    tids = {s["trace_id"] for s in out["slow_traces"]}
    assert alice_tid in tids
    assert bob_tid not in tids
