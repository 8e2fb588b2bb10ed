"""The comparison that decides ``correct`` fails the control and each
fault the cell can have, with the harness's look for a chip skipped."""

import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.tests import tiny
from benchmarks.tests.conftest import ROOT


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("GEOMESA_COMPILE_CACHE_DIR", str(tmp_path / "jax"))


def test_bf16_control_is_not_correct():
    res, info, err = tiny.run(tiny.cell("gdelt.heatmap_pow2"), control=True)
    assert res["correct"], (info, err)
    ctl = res["control"]
    assert not ctl["correct"], info
    assert ctl["checks"]["answers_off"]["value"] > 0
    assert "# control (bf16" in info


def _alter_answers(monkeypatch):
    """An answer altered where the server produces it: every grid gains a
    count in its first cell and every count one row."""
    from geomesa_tpu.sidecar.service import GeoFlightServer

    orig = GeoFlightServer._wrap_fused

    def wrap(self, op, opts, raw):
        if op == "density":
            raw = np.array(raw, copy=True)
            raw[0, 0] += 1
        elif op == "count":
            raw = int(raw) + 1
        return orig(self, op, opts, raw)

    monkeypatch.setattr(GeoFlightServer, "_wrap_fused", wrap)


def _drop_half(monkeypatch):
    """Half of the rows left out where the device bins them: every second
    row of the density scan's mask is cleared."""
    from geomesa_tpu.kernels import density as kdensity

    orig = kdensity.density_grid

    def density_grid(x, y, m, *args, **kw):
        xp = args[-1] if args else kw["xp"]
        keep = (xp.arange(m.size).reshape(m.shape) % 2) == 0
        return orig(x, y, m & keep, *args, **kw)

    monkeypatch.setattr(kdensity, "density_grid", density_grid)


@pytest.mark.parametrize("fault,pool_seed", [(_alter_answers, 0),
                                             (_drop_half, 9_100_003)])
def test_fault_is_not_correct(fault, pool_seed, monkeypatch):
    fault(monkeypatch)
    res, info, err = tiny.run(tiny.cell("gdelt.heatmap_pow2",
                                        pool_seed=pool_seed))
    assert not res["correct"], info
    assert res["checks"]["answers_off"]["value"] > 0
    assert "answers_off" in err.strip().splitlines()[-len(res["checks"])]


def test_no_tpu_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "gdelt.heatmap_pow2", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr
