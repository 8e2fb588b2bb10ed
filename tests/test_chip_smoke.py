"""``chip_smoke.py``: no CPU fallback, and its phases rehearsed on the CPU.

The script itself refuses to run without a TPU. Its phases run here on the
CPU with the Pallas kernels in interpret mode and the device-scan failures
strict, at a size where the compacted layout and the grouped density kernel
engage — the same checks against numpy, the same path assertions.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 200_000


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_refuses_without_a_tpu(where, tmp_path):
    """Exits non-zero and prints no ``"ok": true`` line on the CPU, in the
    checkout and in a directory holding nothing but the script."""
    cwd = REPO
    if where == "alone":
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rows", str(ROWS)],
        cwd=cwd, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


@pytest.fixture()
def rehearsal(monkeypatch):
    monkeypatch.setenv("GEOMESA_PALLAS_INTERPRET", "1")
    # the compacted layout engages from 1Mi rows by default
    monkeypatch.setenv("GEOMESA_COMPACT_MIN_ROWS", "0")
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke

    return chip_smoke


def test_one_chip_phases_on_cpu(rehearsal, capsys):
    rehearsal.one_chip(ROWS, seed=7)
    out = capsys.readouterr().out
    assert "GeoDataset: answers match numpy" in out
    assert "flight: answers match numpy" in out
    assert "'kernel:pip': 'pallas'" in out


def test_four_chip_phases_on_cpu(rehearsal, capsys):
    """The multi-chip phases on 4 of the 8 virtual CPU devices."""
    rehearsal.four_chips(ROWS, seed=7)
    out = capsys.readouterr().out
    assert "mesh: bit-identical to the one-chip answers" in out
    assert "sharded scan: answers match numpy" in out
