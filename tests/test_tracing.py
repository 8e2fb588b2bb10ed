"""Observability layer tests (docs/OBSERVABILITY.md): span-tree tracing
through the query path, latency histograms, the slow-query log, the
exposition surface, and the off-by-default-cheap contract.

The contract under test:

* a traced query produces ONE span tree — plan, (cache cell lookups /
  residual scans when decomposed), per-partition {stage, device_put,
  kernel, sync} — with the same trace_id in the QueryEvent, the explain
  output, and (over Flight) the server-side audit;
* the prefetch worker adopts the query's span context the way it adopts
  config overrides, so staging spans land in the query's tree;
* with tracing disabled the span API returns a shared no-op singleton —
  no allocation, no trace state;
* histograms bucket correctly and render prometheus text p50/p99 can be
  derived from;
* a root span slower than geomesa.trace.slow.ms appends its full tree as
  JSONL through the SAME audit appender (file order = event order).
"""

import gc
import json
import tracemalloc

import numpy as np
import pytest

from geomesa_tpu import GeoDataset, config, metrics, tracing
from geomesa_tpu.filter.ecql import parse_iso_ms


def _mk_ds(n=5000, partitioned=False, seed=3, n_shards=2):
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


BBOX = "BBOX(geom, -100, 30, -80, 45)"


def _names(tree, acc=None):
    acc = [] if acc is None else acc
    acc.append(tree["name"])
    for c in tree.get("children", ()):
        _names(c, acc)
    return acc


@pytest.fixture()
def traced():
    with config.TRACE_ENABLED.scoped("true"):
        yield


# ---------------------------------------------------------------------------
# off-path cheapness
# ---------------------------------------------------------------------------


def test_disabled_span_is_shared_noop_singleton():
    assert not tracing.enabled()
    assert tracing.span("plan") is tracing.NOOP
    assert tracing.span("scan.kernel") is tracing.NOOP
    assert tracing.start("query") is tracing.NOOP
    assert tracing.current_trace_id() is None
    # the singleton is inert under the full protocol
    with tracing.span("x") as s:
        assert s.set(part=1) is s


def test_disabled_span_path_allocates_nothing():
    tracing.span("warmup")  # warm any lazy state
    gc.collect()
    tracemalloc.start()
    for _ in range(1000):
        tracing.span("hot")
    current, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # a single ContextVar read + singleton return: no per-call allocation
    # (the small constant slack absorbs interpreter-internal noise)
    assert peak < 2048, f"no-op span path allocated {peak} bytes over 1000 calls"


# ---------------------------------------------------------------------------
# span-tree shape
# ---------------------------------------------------------------------------


def test_plain_query_span_tree_and_audit_trace_id(traced):
    ds = _mk_ds()
    n = ds.count("t", BBOX)
    assert n > 0
    tr = tracing.last_trace()
    assert tr is not None
    tree = tr.root.to_dict()
    names = _names(tree)
    assert tree["name"] == "count"
    assert "plan" in names
    # the scan ran SOMEWHERE: device (kernel+sync) or host
    assert any(s.startswith("scan.") for s in names)
    ev = ds.audit.recent(1)[0]
    assert ev.hints.get("trace_id") == tr.trace_id


def test_explain_carries_trace_id_and_alert_section(traced):
    ds = _mk_ds(1000)
    out = ds.explain("t", BBOX)
    assert "Observability" in out
    assert "trace_id (this explain call):" in out
    assert "recompile alert:" in out
    tr = tracing.last_trace()
    assert tr.trace_id in out


def test_partitioned_query_tree_has_partition_and_stage_spans(traced):
    ds = _mk_ds(20_000, partitioned=True)
    with config.PIPELINE_PREFETCH.scoped("true"):
        n = ds.count("t", BBOX)
    assert n > 0
    tree = tracing.last_trace().root.to_dict()
    names = _names(tree)
    parts = [s for s in names if s == "scan.partition"]
    assert len(parts) >= 2, names
    # the prefetch WORKER opened these: span-context adoption across the
    # thread boundary (the worker snapshot/adopt pair)
    assert "scan.stage" in names, names


def test_cached_partial_query_tree(traced):
    ds = _mk_ds(20_000)
    with config.CACHE_ENABLED.scoped("true"):
        c1 = ds.count("t", BBOX)
        tree1 = tracing.last_trace().root.to_dict()
        # overlapping pan: partial-cover reuse
        c2 = ds.count("t", "BBOX(geom, -99, 30, -79, 45)")
        tree2 = tracing.last_trace().root.to_dict()
    assert c1 > 0 and c2 > 0
    n1, n2 = _names(tree1), _names(tree2)
    assert "cache.lookup" in n1
    assert "cache.cells" in n1 and "cache.merge" in n1
    assert "cache.cells" in n2
    ev = ds.audit.recent(1)[0]
    assert ev.hints["exec_path"]["cache"] in ("partial", "miss")


def test_query_batches_stream_trace(traced):
    ds = _mk_ds(2000)
    batches = list(ds.query_batches("t", BBOX))
    assert sum(b.n for b in batches) > 0
    tr = tracing.last_trace()
    assert tr.root.name == "query_batches"
    assert tr.root.duration_ms > 0
    ev = ds.audit.recent(1)[0]
    assert ev.hints.get("trace_id") == tr.trace_id


def test_span_budget_bounds_tree(traced):
    with config.TRACE_MAX_SPANS.scoped("4"):
        with tracing.start("query") as root:
            for i in range(16):
                with tracing.span(f"s{i}"):
                    pass
        tr = root.trace
    assert tr.n_spans <= 4
    assert tr.dropped > 0


def test_recompile_event_visible_in_trace(traced):
    ds = _mk_ds(4000)
    ds.count("t", BBOX)  # cold: compiles at least one kernel
    names = _names(tracing.last_trace().root.to_dict())
    assert "kernel.recompile" in names


# ---------------------------------------------------------------------------
# flight round-trip
# ---------------------------------------------------------------------------


def test_trace_id_round_trips_over_flight_headers(traced):
    pytest.importorskip("pyarrow.flight")
    from geomesa_tpu.sidecar import GeoFlightClient, GeoFlightServer

    srv = GeoFlightServer(GeoDataset(n_shards=1, prefer_device=False))
    try:
        with GeoFlightClient(f"grpc+tcp://127.0.0.1:{srv.port}") as c:
            c.create_schema("t", "name:String,*geom:Point")
            import pyarrow as pa

            c.insert_arrow("t", pa.table({
                "__fid__": ["1", "2"], "name": ["a", "b"],
                "geom__x": [0.0, 1.0], "geom__y": [0.0, 1.0],
            }))
            n = c.count("t", "INCLUDE")
            assert n == 2
            client_tid = tracing.last_trace().trace_id
        # the SERVER audit event carries the CLIENT'S trace id (propagated
        # as a Flight header, adopted by the server-side root span)
        ev = srv.dataset.audit.recent(1)[0]
        assert ev.hints.get("op") == "count"
        assert ev.hints.get("trace_id") == client_tid
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# histograms + gauges (metrics.py upgrades)
# ---------------------------------------------------------------------------


def test_histogram_bucket_math():
    h = metrics.Histogram()
    for v in (0.0004, 0.003, 0.003, 0.07, 20.0, 999.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["counts"][0] == 1            # 0.0004 <= 0.0005
    assert snap["counts"][-1] == 1           # 999 -> +Inf overflow
    assert h.quantile(0.5) == 0.005          # 3rd of 6 lands in le=0.005
    assert h.quantile(1.0) == 30.0           # +Inf resolves to top bound
    assert abs(snap["sum_s"] - (0.0004 + 0.006 + 0.07 + 20.0 + 999.0)) < 1e-9


def test_histogram_prometheus_rendering():
    reg = metrics.MetricRegistry(prefix="t")
    reg.histogram("trace.plan").observe(0.002)
    reg.histogram("trace.plan").observe(0.2)
    text = reg.prometheus()
    lines = [ln for ln in text.splitlines() if "trace_plan" in ln]
    assert 't_trace_plan_seconds_bucket{le="0.0025"} 1' in lines
    assert 't_trace_plan_seconds_bucket{le="0.25"} 2' in lines
    assert 't_trace_plan_seconds_bucket{le="+Inf"} 2' in lines
    assert any(ln.startswith("t_trace_plan_seconds_count 2") for ln in lines)
    # cumulative monotone
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines if "_bucket" in ln]
    assert cums == sorted(cums)


def test_timer_feeds_histogram_and_report_quantiles():
    reg = metrics.MetricRegistry(prefix="t")
    t = reg.timer("query.scan")
    for s in (0.001, 0.002, 0.004, 0.3):
        t.update(s)
    rep = reg.report()["query.scan"]
    assert rep["count"] == 4
    assert rep["p50_s"] <= rep["p99_s"]
    text = reg.prometheus()
    assert 't_query_scan_seconds_bucket{le="+Inf"} 4' in text
    # legacy lines preserved
    assert "t_query_scan_count 4" in text


def test_gauge_locked_and_explicit_replacement():
    reg = metrics.MetricRegistry(prefix="t")
    g = reg.gauge("x")
    g.set(3)
    assert g.value == 3.0

    fn1 = lambda: 1.0  # noqa: E731
    fn2 = lambda: 2.0  # noqa: E731
    reg.gauge("backed", fn1)
    reg.gauge("backed", fn1)  # same fn: idempotent
    with pytest.raises(ValueError):
        reg.gauge("backed", fn2)  # silent replacement refused
    assert reg.gauge("backed").value == 1.0
    reg.gauge("backed", fn2, replace=True)  # explicit replacement
    assert reg.gauge("backed").value == 2.0


# ---------------------------------------------------------------------------
# slow-query log
# ---------------------------------------------------------------------------


def test_slow_query_writes_span_tree_jsonl(tmp_path, traced):
    from geomesa_tpu import audit as audit_mod

    path = tmp_path / "audit.jsonl"
    ds = _mk_ds(2000)
    with config.AUDIT_PATH.scoped(str(path)), \
            config.TRACE_SLOW_MS.scoped("0"):
        n = ds.count("t", BBOX)
    audit_mod._appender.reset()
    assert n > 0
    tid = tracing.last_trace().trace_id
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    kinds = [ln.get("kind", "query_event") for ln in lines]
    slow = [ln for ln in lines if ln.get("kind") == "slow_trace"]
    assert slow, kinds
    rec = slow[-1]
    assert rec["trace_id"] == tid
    assert rec["tree"]["name"] == "count"
    assert any(c["name"] == "plan" for c in rec["tree"].get("children", []))
    # the query event for the same query rides the same file, in order:
    # audit fires inside the root span, so event precedes its slow trace
    ev_idx = max(i for i, ln in enumerate(lines)
                 if ln.get("hints", {}).get("trace_id") == tid)
    slow_idx = lines.index(rec)
    assert ev_idx < slow_idx


def test_late_child_stretches_finished_root_for_slow_check(traced):
    # a streamed query's scan spans finish AFTER the sidecar do_get root
    # returned the stream object: the late finish must stretch the root
    # and still trip the slow-query threshold (once)
    import time as _t

    tracing.clear_slow_traces()
    with config.TRACE_SLOW_MS.scoped("5"):
        root = tracing.start("sidecar.do_get")
        with root:
            child = tracing.span("query_batches")
            child.t0 = _t.perf_counter()
        assert not tracing.slow_traces()  # root alone was under threshold
        _t.sleep(0.02)
        child.finish()
        assert tracing.slow_traces(), "late child must re-trip the check"
        n = len(tracing.slow_traces())
        child.finish()  # idempotent: one slow record per trace
        assert len(tracing.slow_traces()) == n


def test_query_batches_restores_enclosing_span(traced):
    ds = _mk_ds(1000)
    with tracing.start("outer") as outer:
        batches = ds.query_batches("t", BBOX)
        assert tracing.current_span() is outer, \
            "eager planning must restore the enclosing span"
        list(batches)
        assert tracing.current_span() is outer, \
            "stream exhaustion must restore the enclosing span"


def test_slow_trace_ring_served(traced):
    tracing.clear_slow_traces()
    ds = _mk_ds(1000)
    with config.TRACE_SLOW_MS.scoped("0"):
        ds.count("t", BBOX)
    recent = tracing.slow_traces()
    assert recent and recent[-1]["tree"]["name"] == "count"


# ---------------------------------------------------------------------------
# exposition surface
# ---------------------------------------------------------------------------


def test_obs_endpoints(traced):
    import urllib.request

    from geomesa_tpu import obs

    ds = _mk_ds(1000)
    ds.count("t", BBOX)
    srv = obs.serve(ds, port=0, background=True)
    try:
        port = srv.server_address[1]

        def get(path):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10
            ) as r:
                return r.status, r.read().decode()

        code, text = get("/metrics")
        assert code == 200
        assert "geomesa_query_plan_count" in text
        assert "geomesa_kernel_recompile_alert" in text
        assert "_seconds_bucket" in text  # histograms exposed
        code, body = get("/healthz")
        h = json.loads(body)
        assert code == 200 and h["status"] == "ok"
        assert "breakers" in h and "device" in h
        code, body = get("/debug/queries?n=5")
        d = json.loads(body)
        assert code == 200
        assert d["queries"] and d["queries"][-1]["type_name"] == "t"
        assert "degradations" in d and "slow_traces" in d
    finally:
        srv.shutdown()


def test_web_server_mounts_obs_routes():
    import urllib.request

    from geomesa_tpu import web

    ds = _mk_ds(500)
    ds.count("t", "INCLUDE")
    srv = web.serve(ds, port=0, background=True)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as r:
            assert r.status == 200
            assert "geomesa_" in r.read().decode()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10
        ) as r:
            assert json.loads(r.read())["status"] in ("ok", "degraded")
        # malformed ?n= must come back as a clean 400, not a dropped
        # connection (web.py routes obs paths before its own try/except)
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/queries?n=abc", timeout=10
            )
            assert False, "expected HTTP 400"
        except urllib.error.HTTPError as e:
            assert e.code == 400
    finally:
        srv.shutdown()


def test_healthz_degraded_when_breaker_open():
    from geomesa_tpu import obs, resilience

    resilience.reset_breakers()
    try:
        b = resilience.breaker("sidecar:test-loc", threshold=1)
        b.record_failure()
        assert b.state == "open"
        h = obs.health()
        assert h["status"] == "degraded"
        assert "sidecar:test-loc" in h["open_breakers"]
    finally:
        resilience.reset_breakers()


# ---------------------------------------------------------------------------
# cli
# ---------------------------------------------------------------------------


def test_cli_trace_and_metrics(tmp_path, capsys):
    from geomesa_tpu import cli

    ds = _mk_ds(500)
    ds.save(str(tmp_path / "cat"))
    rc = cli.main([
        "trace", "-c", str(tmp_path / "cat"), "-f", "t", "-q", BBOX,
        "--op", "count", "--json",
    ])
    out = capsys.readouterr().out
    assert rc == 0
    d = json.loads(out)
    assert d["tree"]["name"] == "count"
    assert d["trace_id"]
    rc = cli.main(["metrics"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "geomesa_" in out


# ---------------------------------------------------------------------------
# executor phase spans (cold view) and the stats sync span
# ---------------------------------------------------------------------------

PHASE_Q = ("BBOX(geom, -100, 30, -90, 35) AND dtg DURING "
           "2020-01-05T00:00:00Z/2020-01-20T00:00:00Z")
MISS_SPANS = ("scan.windows", "scan.windows.fine", "scan.cover",
              "scan.compact", "scan.schedule", "scan.gather")


def _spans(tree, acc=None):
    acc = [] if acc is None else acc
    acc.append(tree)
    for c in tree.get("children", ()):
        _spans(c, acc)
    return acc


def _by_name(tree):
    out = {}
    for s in _spans(tree):
        out.setdefault(s["name"], []).append(s)
    return out


@pytest.fixture()
def compact_on():
    with config.COMPACT_MIN_ROWS.scoped("0"):
        yield


def test_cold_compact_density_has_phase_spans(traced, compact_on):
    from geomesa_tpu.kernels.density_mxu import ladder8

    ds = _mk_ds(100_000)
    ds.density("t", PHASE_Q, bbox=(-100, 30, -90, 35), width=32, height=32)
    tree = tracing.last_trace().root.to_dict()
    assert ds.audit.recent(1)[0].hints["exec_path"]["scan"] == \
        "device-compact"
    spans = _by_name(tree)
    for name in MISS_SPANS:
        assert name in spans, (name, sorted(spans))
    win = spans["scan.windows"][0]["attrs"]
    assert win["rows"] > 0
    fine = spans["scan.windows.fine"][0]
    assert fine["attrs"]["rows"] > 0 and fine["attrs"]["ranges"] > 0
    # the re-cover is split from the window resolution
    assert [c["name"] for c in fine.get("children", ())] == ["scan.cover"]
    assert fine["children"][0]["attrs"] == {"ranges": fine["attrs"]["ranges"]}
    # the fine cover runs inside the compaction that chose between the sets
    compact = spans["scan.compact"][0]
    assert [c["name"] for c in compact.get("children", ())] == \
        ["scan.windows.fine"]
    B, C = compact["attrs"]["B"], compact["attrs"]["C"]
    assert compact["attrs"]["rows"] == C * B > 0
    sched = spans["scan.schedule"][0]["attrs"]
    assert sched["kernel"] in ("grouped", "mxu") and sched["pairs"] > 0
    # the gather is a child of the device_put that stages the slabs
    put = [s for s in spans["scan.device_put"]
           if any(c["name"] == "scan.gather" for c in s.get("children", ()))]
    assert put, spans["scan.device_put"]
    gather = spans["scan.gather"][0]["attrs"]
    rows = ladder8(C) * B
    assert gather["columns"] >= 2 and gather["rows"] == rows
    kernel = spans["scan.kernel"][0]["attrs"]
    assert kernel["rows"] == rows and kernel["site"] == "density"
    for s in _spans(tree):
        for v in (s.get("attrs") or {}).values():
            assert isinstance(v, (int, str)), s


def test_warm_repeat_opens_no_miss_path_spans(traced, compact_on):
    ds = _mk_ds(100_000)
    for _ in range(2):
        ds.density("t", PHASE_Q, bbox=(-100, 30, -90, 35), width=32,
                   height=32)
    spans = _by_name(tracing.last_trace().root.to_dict())
    for name in MISS_SPANS:
        assert name not in spans, name
    assert "scan.kernel" in spans and "scan.sync" in spans


def test_padded_kernel_span_reads_every_stored_row(traced):
    ds = _mk_ds(5000, n_shards=2)
    ds.count("t", BBOX)
    ev = ds.audit.recent(1)[0]
    assert ev.hints["exec_path"]["scan"] == "device-padded"
    spans = _by_name(tracing.last_trace().root.to_dict())
    rows = spans["scan.kernel"][0]["attrs"]["rows"]
    assert rows in {t.n_shards * t.shard_len
                    for t in ds._store("t").tables.values()}
    assert rows >= 5000


def test_stats_query_has_sync_span(traced, compact_on):
    ds = _mk_ds(100_000)
    ds.stats("t", "Count();MinMax(weight)", PHASE_Q)
    spans = _by_name(tracing.last_trace().root.to_dict())
    assert "scan.kernel" in spans
    assert spans["scan.kernel"][0]["attrs"]["site"] == "stats"
    assert "scan.sync" in spans
