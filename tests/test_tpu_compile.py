"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The CPU tests run the Pallas kernels in interpret mode, which accepts
layouts and block shapes the chip's compiler refuses. These tests hand the
TPU compiler the shapes ``chip_smoke.py`` produces at its default 20M rows
and check that each program compiles, keeps its hand kernel
(``tpu_custom_call``) and fits a v5e's 16 GB of HBM. Nothing runs: a compile
that passes here is not a chip run.

Shapes (read off the smoke's data at 20M rows on the host, PR 21): the
ten-day BBOX+DURING query compacts to C=17352 chunks of B=128 rows with
42384 grouped-density pairs on a 4x4 tile grid, the whole-month one to
52984 chunks and 129376 pairs (more pair arrays than SMEM holds in one
call); the polygon query compacts to 66232 chunks of 128 rows; the store's
padded shard length is 2506752.

The topology is described only inside fixtures, never at import: only one
process may load the TPU library at a time, and every xdist worker imports
this file.
"""

import numpy as np
import pytest

B, NT = 128, 4
#: (chunks, pairs) of the ten-day and the whole-month window
SCHEDULES = [(17352, 42384), (52984, 129376)]
POLY_CHUNKS = 66232
SHARD_LEN = 2506752
BBOX = (-100.0, 30.0, -80.0, 45.0)
GRID = 512
POLYGON = ((-120, 26), (-84, 25), (-70, 42), (-100, 48), (-122, 46),
           (-120, 26))
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache(topo):
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off meanwhile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_persistent_cache):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, dtype, sharding):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    return used < HBM_BYTES, used


@pytest.mark.parametrize("C,PAIRS", SCHEDULES)
@pytest.mark.parametrize("weighted", [False, True])
def test_grouped_density_kernel_compiles(one_chip, weighted, C, PAIRS):
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.kernels import density_pallas as dp

    def step(x, y, m, w, sc, row, tile, ox, oy):
        return dp.density_grid_grouped(
            x, y, m, BBOX, GRID, GRID, w if weighted else None,
            sc, row, tile, ox, oy, B, NT, NT, PAIRS,
        )

    cb = lambda dt: _shape((C, B), dt, one_chip)  # noqa: E731
    pairs = [_shape((PAIRS,), jnp.int32, one_chip)] * 5
    compiled = jax.jit(step).lower(
        cb(jnp.float32), cb(jnp.float32), cb(jnp.bool_), cb(jnp.float32),
        *pairs,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ok, used = _fits(compiled)
    assert ok, used


def test_pip_kernel_compiles(one_chip):
    import jax
    import jax.numpy as jnp

    from geomesa_tpu.kernels import pallas_kernels as pk

    v = np.asarray(POLYGON, np.float64)
    x1, y1, x2, y2 = v[:-1, 0], v[:-1, 1], v[1:, 0], v[1:, 1]
    edges = pk.pack_edges(x1, y1, y2, (x2 - x1) / (y2 - y1))
    pts = _shape((POLY_CHUNKS, B), jnp.float32, one_chip)
    compiled = jax.jit(lambda x, y: pk.pip_mask(x, y, edges)).lower(
        pts, pts).compile()
    assert "tpu_custom_call" in compiled.as_text()
    ok, used = _fits(compiled)
    # the points and the mask, nothing the size of [N, 128]
    assert ok and used < 4 * POLY_CHUNKS * B * 4, used


def test_query_step_compiles_sharded_over_four_chips(topo,
                                                     no_persistent_cache):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import __graft_entry__ as graft

    mesh = Mesh(np.array(topo.devices), ("shard",))
    assert mesh.devices.size == 4
    rows = NamedSharding(mesh, P("shard", None))
    S = 8
    cols = {
        "geom__x": _shape((S, SHARD_LEN), jnp.float32, rows),
        "geom__y": _shape((S, SHARD_LEN), jnp.float32, rows),
        "dtg__bin": _shape((S, SHARD_LEN), jnp.int32, rows),
        "dtg__off": _shape((S, SHARD_LEN), jnp.int32, rows),
    }
    win = _shape((S, 1), jnp.int32, rows)
    counts = _shape((S,), jnp.int32, NamedSharding(mesh, P("shard")))
    step = graft._query_step(SHARD_LEN, BBOX, GRID, GRID)
    compiled = jax.jit(step).lower(cols, win, win, counts).compile()
    text = compiled.as_text()
    # each chip scans its own shards; the grid and scalars merge across
    assert "all-reduce" in text
    ok, used = _fits(compiled)
    assert ok, used


def test_weighted_einsum_density_keeps_f32_contractions(one_chip):
    """The XLA einsum density (partition children take it at large B) must
    ask for f32 contractions: at the TPU's default precision a v5e run put
    weighted cells 1e-2 off (PR 21)."""
    import re

    import jax
    import jax.numpy as jnp

    from geomesa_tpu.kernels import density_mxu as dm

    C, Bw, P, PB = 4096, 2048, 8192, 64

    def step(x, y, m, w, pc, p0, p1, pt, pv):
        return dm.density_grid_pairs(
            x, y, m, BBOX, GRID, GRID, w, pc, p0, p1, pt, pv,
            PB, NT, NT, 128, 128, jnp,
        )

    cb = lambda dt: _shape((C, Bw), dt, one_chip)  # noqa: E731
    p = lambda dt: _shape((P,), dt, one_chip)  # noqa: E731
    lowered = jax.jit(step).lower(
        cb(jnp.float32), cb(jnp.float32), cb(jnp.bool_), cb(jnp.float32),
        p(jnp.int32), p(jnp.int32), p(jnp.int32), p(jnp.int32),
        p(jnp.float32),
    )
    precisions = re.findall(r"precision = \[([^\]]*)\]", lowered.as_text())
    assert precisions and set(precisions) == {"HIGHEST, HIGHEST"}
    ok, used = _fits(lowered.compile())
    assert ok, used
