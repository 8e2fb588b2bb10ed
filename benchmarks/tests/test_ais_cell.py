"""The AIS cell served through the Flight sidecar on the CPU at a tiny
size: its answers equal the plain reference, the bf16 control fails, and
a traced run reports the density ladder's per-layer metrics."""

import pytest

from benchmarks.tests import tiny

CELL = "ais.port_heatmap"


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("GEOMESA_COMPILE_CACHE_DIR", str(tmp_path / "jax"))
    # the compact layout, which the cell's 20M rows take, at the tiny size
    monkeypatch.setenv("GEOMESA_COMPACT_MIN_ROWS", "0")


def test_cell_answers_equal_reference_and_control_fails():
    c = tiny.cell(CELL)
    assert tiny.kinds(c) == {("density", False), ("stats", False)}
    res, info, err = tiny.run(c, control=True)
    assert res["correct"], (info, err)
    assert res["failed"] == 0 and res["attempted"] > 0
    # bf16 resolves about 0.5 deg at 118 W: the control must read false
    assert res["control"]["correct"] is False, info
    assert res["control"]["checks"]["answers_off"]["value"] > 0


def test_traced_run_reports_density_ladder_metrics():
    res, info, err = tiny.run(tiny.cell(CELL), trace=True)
    assert res["correct"], (info, err)
    share = res["metrics"]["kernel.density_grouped_share"]["value"]
    ratio = res["metrics"]["kernel.density_pairs_per_chunk"]["value"]
    # no pallas on the CPU: the einsum rung serves every compact view
    assert share == 0.0
    assert ratio > 0
