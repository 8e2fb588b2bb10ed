"""Pallas TPU kernels for the compute-bound hot ops.

The bandwidth-bound ops (density scatter, masked reductions) are already at
the HBM roofline under plain XLA — measured on v5e, the 512x512 density
scatter over 8M points runs in ~0.1 ms, i.e. memory-bound — so they stay as
jnp. What benefits from a hand kernel is the **point-in-polygon fine filter**
(the reference's per-row geometry predicate inside AggregatingScan,
index/iterators/AggregatingScan.scala:82-116): N points x E edges of
crossing-parity work with an [N, E] broadcast intermediate. The Pallas
version keeps the edge table in SMEM and streams lane-dense point blocks
through the VPU, one edge per loop step, so no [N, E] intermediate exists.

CPU tests run the same kernel in interpret mode (tests/test_pallas.py);
production dispatch gates on the TPU backend (``use_pallas()``).
"""

from __future__ import annotations

import contextlib
import os
import threading

import numpy as np

_LANES = 128
#: point rows per program: [_ROWS, 128] f32 blocks (32K points), lane-dense
#: so HBM holds the points at their own size (a [N, 1] column layout pads
#: every point to a 128-lane tile row: 128x the bytes)
_ROWS = 256
#: edges per polygon the kernel takes: the edge table lives in SMEM
#: (4 x 1024 f32 = 16 KiB) and the kernel loops over it once per block
_MAX_EDGES = 1024

_tls = threading.local()


@contextlib.contextmanager
def sharded_execution(mesh_or_flag):
    """Mark that subsequent kernel traces run under a sharded mesh.

    pallas_call has no GSPMD partitioning rule, so under NamedSharding'd
    inputs a bare call would replicate (or fail -> permanent host fallback).
    When the executor passes its actual ``Mesh``, polygon fine-filters keep
    the hand kernel by wrapping it in an inner ``shard_map`` (per-device
    pallas over the local block); a bare truthy flag (mesh unknown) keeps
    the old behavior of falling back to the XLA broadcast path."""
    prev = getattr(_tls, "sharded", False)
    _tls.sharded = mesh_or_flag
    try:
        yield
    finally:
        _tls.sharded = prev


def current_mesh():
    """The active mesh under :func:`sharded_execution`, if one was given."""
    m = getattr(_tls, "sharded", False)
    return m if m is not False and m is not True and m is not None else None


def interpret_mode() -> bool:
    """Force interpret-mode pallas on any backend (CPU-mesh tests)."""
    return os.environ.get("GEOMESA_PALLAS_INTERPRET") == "1"


def _backend_ok() -> bool:
    if os.environ.get("GEOMESA_PALLAS", "1") == "0":
        return False
    if interpret_mode():
        return True
    import jax

    return jax.default_backend() == "tpu"


def use_pallas() -> bool:
    """Plain (unsharded) pallas dispatch gate."""
    if getattr(_tls, "sharded", False):
        return False
    return _backend_ok()


def use_pallas_sharded(mesh, lead_dim: int, kernel: str = None) -> bool:
    """Sharded dispatch gate: backend ok, mesh has a 'shard' axis that
    evenly divides the leading (shard) dimension — shard_map requires
    exact divisibility, unlike GSPMD. Pass ``kernel`` to record an
    uneven-mesh refusal as that kernel's dispatch (bare capability
    probes record nothing)."""
    if mesh is None or not _backend_ok():
        return False
    size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("shard")
    if not size:
        return False
    if lead_dim % size != 0:
        if kernel is not None:
            # the fallback to the XLA broadcast path used to be silent —
            # the dispatch record makes it visible in explain/audit
            record_dispatch(kernel,
                            f"xla-fallback(uneven mesh: {lead_dim} rows"
                            f" % {size} shards != 0)")
        return False
    return True


def record_dispatch(kernel: str, choice: str) -> None:
    """Note a kernel-dispatch decision. Decisions happen at TRACE time,
    so a record exists only for the execution that compiled the kernel;
    cached-kernel reuse produces none (exec_path's ``kernel:*`` entries
    are compile-time attribution). The executor drains these into
    ``plan.exec_path`` once per run."""
    if getattr(_tls, "dispatch", None) is None:
        _tls.dispatch = {}
    # a query may trace several predicates of the same kernel kind with
    # different outcomes (e.g. one pallas, one fallback): keep them all
    seen = _tls.dispatch.setdefault(kernel, [])
    if choice not in seen:
        seen.append(choice)


def take_dispatch() -> dict:
    """Drain the per-thread dispatch records (kernel -> choice, with
    multiple distinct outcomes joined)."""
    out = getattr(_tls, "dispatch", None) or {}
    _tls.dispatch = {}
    return {k: v[0] if len(v) == 1 else " + ".join(v)
            for k, v in out.items()}


def polygon_edge_tables(poly):
    """Shared edge-table builder for one Polygon (shell + holes).

    Returns ``(f64_tuple, packed_f32)`` where ``f64_tuple`` is
    ``(x1, y1, x2, y2, slope)`` for the host/broadcast paths and
    ``packed_f32`` is the lane-padded [4, Ep] table for the Pallas kernel.
    Horizontal edges get slope denominator 1.0 — the crossing condition is
    false for them so the value is never used."""
    from geomesa_tpu.utils import geometry as geo

    rings = [np.asarray(geo._close_ring(poly.shell), np.float64)] + [
        np.asarray(geo._close_ring(h), np.float64) for h in poly.holes
    ]
    x1 = np.concatenate([r[:-1, 0] for r in rings])
    y1 = np.concatenate([r[:-1, 1] for r in rings])
    x2 = np.concatenate([r[1:, 0] for r in rings])
    y2 = np.concatenate([r[1:, 1] for r in rings])
    dy = np.where(y2 - y1 == 0.0, 1.0, y2 - y1)
    slope = (x2 - x1) / dy
    return (x1, y1, x2, y2, slope), pack_edges(x1, y1, y2, slope)


def pack_edges(x1, y1, y2, slope) -> np.ndarray:
    """Edge table -> [4, Ep] f32, lane-padded to a multiple of 128.

    Padding rows have y1 == y2 == 0 so the crossing condition
    ``(y1 > y) != (y2 > y)`` is identically false — padded edges never
    contribute a crossing."""
    e = len(x1)
    ep = max(128, ((e + 127) // 128) * 128)
    out = np.zeros((4, ep), np.float32)
    out[0, :e] = x1
    out[1, :e] = y1
    out[2, :e] = y2
    out[3, :e] = slope
    return out


def _pip_kernel(e_ref, x_ref, y_ref, out_ref):
    """One [_ROWS, 128] block of points against every edge (even-odd
    crossing parity). ``e_ref`` is the [4, E] edge table in SMEM; each loop
    step reads one edge's scalars and updates the parity of every point."""
    import jax
    import jax.numpy as jnp

    x = x_ref[...]
    y = y_ref[...]

    def edge(i, parity):
        x1, y1, y2, slope = e_ref[0, i], e_ref[1, i], e_ref[2, i], e_ref[3, i]
        cond = (y1 > y) != (y2 > y)
        xint = x1 + (y - y1) * slope
        return parity ^ (cond & (x < xint)).astype(jnp.int32)

    out_ref[...] = jax.lax.fori_loop(
        0, e_ref.shape[1], edge, jnp.zeros(x.shape, jnp.int32)
    )


def _pip_call(xf, yf, edges, interpret: bool = False):
    """Parity of ``[rows, 128]`` point arrays (rows a multiple of _ROWS)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = xf.shape[0]
    block = pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))
    return pl.pallas_call(
        _pip_kernel,
        grid=(rows // _ROWS,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            block,
            block,
        ],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        interpret=interpret,
        name="pip_parity",
    )(edges, xf, yf)


def pip_mask(x, y, edges: np.ndarray, interpret: bool = False):
    """Even-odd point-in-polygon mask for one polygon's packed edge table.

    ``x``/``y``: jnp arrays of any shape; returns a bool mask of that shape.
    Points are zero-padded to whole blocks; padding results are sliced off
    before reshaping back. Only edges that can be crossed (y1 != y2) enter
    the kernel: the lane padding of the packed table and horizontal edges
    never change a parity."""
    import jax.numpy as jnp

    edges = np.asarray(edges, np.float32)
    edges = edges[:, edges[1] != edges[2]]
    if edges.shape[1] == 0:
        return jnp.zeros(x.shape, bool)
    shape = x.shape
    xf = jnp.ravel(x).astype(jnp.float32)
    yf = jnp.ravel(y).astype(jnp.float32)
    n = xf.shape[0]
    pad = (-n) % (_ROWS * _LANES)
    if pad:
        xf = jnp.pad(xf, (0, pad))
        yf = jnp.pad(yf, (0, pad))
    out = _pip_call(
        xf.reshape(-1, _LANES), yf.reshape(-1, _LANES), jnp.asarray(edges),
        interpret=interpret,
    )
    return out.reshape(-1)[:n].astype(bool).reshape(shape)


def pip_mask_sharded(x, y, edges: np.ndarray, mesh, interpret: bool = False):
    """:func:`pip_mask` under a NamedSharding'd [S, L] layout: an inner
    ``shard_map`` over the mesh's 'shard' axis runs the pallas kernel
    per-device on the LOCAL shard block (edge table replicated), so polygon
    fine-filtering keeps the hand kernel at pod scale instead of dropping
    to the [N, E] broadcast path. Axes other than 'shard' (e.g. the
    binspace 'bin' axis) see replicated inputs and outputs."""
    import jax
    from jax.sharding import PartitionSpec as P

    spec = P("shard", None)

    def local(xl, yl):
        return pip_mask(xl, yl, edges, interpret=interpret)

    sm = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec),
                       out_specs=spec, check_vma=False)
    return sm(x, y)


def edges_fit(n_edges: int) -> bool:
    return n_edges <= _MAX_EDGES
