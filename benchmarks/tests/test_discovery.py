"""A traffic mix and a per-layer metric are added as files alone: the
harness finds them by the names a new BENCHMARK.json entry gives."""

import json
import os

import pytest

from benchmarks import harness
from benchmarks.tests import tiny
from benchmarks.tests.conftest import ROOT

MIX = "_discovery_mix"
METRIC = "_discovery.requests"
CELL = "_discovery.cell"


@pytest.fixture
def added_files(tmp_path, monkeypatch):
    monkeypatch.setenv("GEOMESA_COMPILE_CACHE_DIR", str(tmp_path / "jax"))
    here = os.path.join(ROOT, "benchmarks")
    mix = os.path.join(here, "traffic", MIX + ".json")
    metric = os.path.join(here, "layer_metrics", METRIC + ".py")
    limits = os.path.join(here, "limits", CELL + ".json")
    with open(os.path.join(here, "traffic", "heatmap_pow2.json")) as f:
        t = json.load(f)
    # an op kind and a client count the committed cell does not use
    t["ops"] = [{"op": "count", "share": 1.0}]
    t["pool_seed"] = 4242
    t["clients"] = 3
    paths = {mix: json.dumps(t),
             metric: "def read(ctx):\n    return float(len(ctx.records))\n",
             limits: json.dumps({"answers_off": 0, "failed": 0})}
    for p, text in paths.items():
        assert not os.path.exists(p)
        with open(p, "w") as f:
            f.write(text)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": CELL, "config": "gdelt_events",
                               "traffic": MIX, "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": METRIC, "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "service",
        "moves": "latency_p50_ms", "workloads": [CELL]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    try:
        yield str(path)
    finally:
        for p in paths:
            os.remove(p)


def test_new_mix_and_metric_found_by_name(added_files):
    cell = tiny.cell(CELL, bench=added_files)
    assert cell.traffic["ops"] == [{"op": "count", "share": 1.0}]
    assert cell.traffic["clients"] == 3
    assert [m["name"] for m in cell.per_layer][-1] == METRIC
    res, info, _ = tiny.run(cell, trace=True)
    assert res["correct"], info
    assert res["metrics"][METRIC]["value"] == res["attempted"] > 0


def test_existing_cells_do_not_see_the_new_metric(added_files):
    cell = harness.Cell("gdelt.heatmap_pow2", added_files)
    assert METRIC not in [m["name"] for m in cell.per_layer]
