"""The cell's every op kind, served through the Flight sidecar on the CPU
at a tiny size, equals the plain reference."""

import json

import pytest

from benchmarks.tests import tiny


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("GEOMESA_COMPILE_CACHE_DIR", str(tmp_path / "jax"))


def test_cell_answers_equal_reference():
    c = tiny.cell("gdelt.heatmap_pow2")
    res, info, err = tiny.run(c)
    assert res["correct"], (info, err)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert tiny.kinds(c) == {(o["op"], bool(o.get("weight")))
                             for o in c.traffic["ops"]}
    assert set(res["metrics"]) == {"query_qps", "setup_s", "latency_p50_ms",
                                   "latency_p95_ms"}
    # the result line is the last line, and its last key is the checks
    last = json.loads(info.strip().splitlines()[-1])
    assert list(last)[-1] == "checks"
    assert err.strip().splitlines()[-1].startswith("check ")


def test_traced_run_reports_span_and_counter_metrics():
    res, info, err = tiny.run(tiny.cell("gdelt.heatmap_pow2"), trace=True)
    assert res["correct"], (info, err)
    for m in ("serving.queue_wait_ms", "serving.queries_per_dispatch",
              "planning.plan_ms", "executor.host_ms", "executor.sync_ms"):
        assert m in res["metrics"], m
        assert res["metrics"][m]["value"] >= 0
    # no device trace on the CPU: the device metrics are left out, not 0
    assert "kernel.scan_roofline" not in res["metrics"]
    assert "device.idle_share" not in res["metrics"]
