"""A benchmark cell cut to a size a CPU test can hold."""

from __future__ import annotations

import io
import time

from benchmarks import harness

ROWS = 150_000


def kinds(c: harness.Cell):
    """The (op, weighted) kinds the cell's pool holds."""
    from benchmarks import loadgen

    pool = loadgen.build_pool(c.traffic, c.cfg, c.cfg_mod)
    return {(r["op"], bool(r.get("weight"))) for r in pool}


def cell(name: str, bench: str = "", pool_seed: int = 0) -> harness.Cell:
    """The cell at a tiny size, with the smallest pool that still holds
    every op kind of its mix (drawn from ``pool_seed`` when given, so that
    a test can send query texts no other test compiled)."""
    c = harness.Cell(name, bench)
    c.cfg = dict(c.cfg, rows=ROWS)
    if pool_seed:
        c.traffic = dict(c.traffic, pool_seed=pool_seed)
    want = {(o["op"], bool(o.get("weight"))) for o in c.traffic["ops"]}
    for n in range(1, 120):
        c.traffic = dict(c.traffic, pool=n, warm_seconds=2)
        if kinds(c) == want:
            break
    return c


def run(c: harness.Cell, seed: int = 3_000_000_019, trace: bool = False,
        control: bool = False, seconds: float = 2.0):
    """Run the cell without the harness's look for a chip; returns the
    result, the info lines and the compared-number lines."""
    out, err = io.StringIO(), io.StringIO()
    res = harness.run(c.name, seed, seconds, trace, time.monotonic(),
                      require_tpu=False, control=control, out=out, err=err,
                      cell=c)
    return res, out.getvalue(), err.getvalue()
