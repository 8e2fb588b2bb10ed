"""Density (heatmap) kernel.

Parity with the reference's DensityScan (index/iterators/DensityScan.scala:
29-136: per-row RenderingGrid scatter in tablet servers, sparse grids merged
client-side): here ONE scatter-add over the full sharded column set — XLA
partitions the scatter per device and all-reduces the grid (the
``StatsCombiner``/reducer role, played by the XLA collective).
"""

from __future__ import annotations

import numpy as np

#: independent scatter pieces of a device density grid
SCATTER_SPLIT = 8


def density_grid(x, y, mask, bbox, width: int, height: int, weight=None, xp=None):
    """Masked 2D histogram: points -> (height, width) float32 grid.

    ``x``/``y``/``mask`` may be [S, L] or flat; backend-generic (np or jnp).
    Cells follow the reference's RenderingGrid convention: row 0 = ymin edge.
    """
    xmin, ymin, xmax, ymax = bbox
    # spans computed HERE (host f64 for baked bboxes) so the query-axis
    # batched kernel can pass the f32 images of the SAME span values as
    # traced scalars and reproduce the pixel mapping bit-for-bit — an
    # f32 (xmax - xmin) recomputed in-kernel could differ by an ulp
    return density_grid_at(
        x, y, mask, xmin, ymin, xmax - xmin, ymax - ymin,
        width, height, weight, xp,
    )


def grid_params(bbox) -> np.ndarray:
    """The traced-parameter form of a density bbox for the batched kernel:
    ``[x0, y0, dx, dy]`` as the f32 images of the host-f64 origin/span —
    exactly the scalar values the baked :func:`density_grid` closes over
    after jax's weak-type f32 conversion."""
    xmin, ymin, xmax, ymax = (float(v) for v in bbox)
    return np.asarray(
        [xmin, ymin, xmax - xmin, ymax - ymin], np.float32
    )


def density_grid_at(x, y, mask, x0, y0, dx, dy, width: int, height: int,
                    weight=None, xp=None):
    """:func:`density_grid` against an origin/span parameterization.
    ``x0``/``y0``/``dx``/``dy`` may be python floats (baked — the classic
    path) or traced f32 scalars (the query-axis batched path, one compiled
    kernel serving every viewport)."""
    if xp is None:
        xp = np
    fx = x.reshape(-1)
    fy = y.reshape(-1)
    fm = mask.reshape(-1)
    px = xp.clip(((fx - x0) / dx * width).astype(xp.int32), 0, width - 1)
    py = xp.clip(((fy - y0) / dy * height).astype(xp.int32), 0, height - 1)
    w = fm.astype(xp.float32) if weight is None else xp.where(
        fm, weight.reshape(-1).astype(xp.float32), xp.float32(0)
    )
    flat_idx = py * width + px
    if xp is np:
        grid = np.zeros(height * width, np.float32)
        np.add.at(grid, flat_idx, w)
        return grid.reshape(height, width)
    # Split the scatter into independent pieces accumulating separate
    # grids, for XLA to schedule apart (not measured on the chip: fused
    # behind a mask compute in one jit, XLA may serialize the pieces after
    # the shared producer). The pallas kernel (density_pallas.py) replaces
    # this path on z-indexed compact scans. Pieces must divide evenly —
    # callers keep row counts a multiple of 8 (see executor chunk buckets).
    P = SCATTER_SPLIT
    n = flat_idx.shape[0]
    if n % P or n < (1 << 14):
        return (
            xp.zeros(height * width, xp.float32).at[flat_idx].add(w)
        ).reshape(height, width)
    fi = flat_idx.reshape(P, -1)
    fw = w.reshape(P, -1)
    grid = None
    for p in range(P):
        s = xp.zeros(height * width, xp.float32).at[fi[p]].add(fw[p])
        grid = s if grid is None else grid + s
    return grid.reshape(height, width)
