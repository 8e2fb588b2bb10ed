"""The traffic generator: a pool fixed by the mix, an order drawn from
the run's seed, and windows whose ends no stored timestamp can touch."""

import numpy as np

from benchmarks import harness, loadgen
from benchmarks.configs_common import iso_ms


def _pool(name):
    c = harness.Cell(name)
    return c, loadgen.build_pool(c.traffic, c.cfg, c.cfg_mod)


def test_pool_is_fixed_distinct_and_larger_than_the_store_caches():
    c, reqs = _pool("gdelt.heatmap_pow2")
    assert reqs == loadgen.build_pool(c.traffic, c.cfg, c.cfg_mod)
    assert len({r["ecql"] + str(r.get("weight")) + r["op"]
                for r in reqs}) == len(reqs) == c.traffic["pool"]
    # the store's window, layout and schedule caches hold 64 entries
    assert len(reqs) > 64


def test_order_follows_the_seed_and_never_ends():
    n = 12

    def take(seed, k):
        it = loadgen.cyclic(seed, n)
        return [next(it) for _ in range(k)]

    a, d = take(2**31 + 5, 50 * n), take(2**31 + 6, 50 * n)
    assert a == take(2**31 + 5, 50 * n) and a != d
    # cyclic: every pool item is sent once before any is sent again
    for k in range(50):
        assert sorted(a[k * n:(k + 1) * n]) == list(range(n))


def test_window_ends_miss_every_stored_timestamp():
    c, reqs = _pool("gdelt.heatmap_pow2")
    start = iso_ms(c.cfg["t_start"])
    end = start + c.cfg["days"] * 86_400_000
    for r in reqs:
        assert start - 86_400_000 < r["t0"] < r["t1"] <= end
        # events sit at midnight; window ends at noon
        assert (r["t0"] - start) % 86_400_000 == 43_200_000


def test_view_spans_are_powers_of_two():
    """The f32 spans the device divides by are exact powers of two."""
    c, reqs = _pool("gdelt.heatmap_pow2")
    for r in reqs:
        x0, y0, x1, y1 = r["bbox"]
        w, h = np.float32(x1 - x0), np.float32(y1 - y0)
        assert w in (32.0, 64.0) and h == w / 2
