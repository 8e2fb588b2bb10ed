"""Density (heatmap) as MXU matmuls — the scatter-free device path.

TPU scatter costs ~6.7 ns per touched row (docs/SCALE.md cost model), so
the DensityScan analog over millions of window rows is scatter-bound. This
kernel reformulates the 2D histogram as batched one-hot matmuls: the grid
splits into (TY, TX) tiles, window rows split into the compacted scan's
B-row chunks, and each (chunk, tile) PAIR contributes

    tile[y, x] += sum_b onehot(py_b == y) * w_b * onehot(px_b == x)
               == (onehot_y * w)^T @ onehot_x        -- one [TY,B]@[B,TX]

which is pure MXU work. The pair list is small because chunks are runs of
the z-sorted order: a B-row run spans a small spatial box (computed on the
host from the chunk's own sorted keys via :func:`_chunk_boxes` — no
device round-trip), so each chunk overlaps a few tiles, not all of them.
Reference parity: DensityScan.scala:29-136 (per-row RenderingGrid scatter
in tablet servers); same sparse-grid result, device-shaped execution.
"""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

#: grid tile shape (TY, TX) in cells, sized for fine-cover chunk boxes
#: (~30-70 cells): smaller tiles raise pairs-per-chunk, larger tiles raise
#: one-hot operand and tile-tensor traffic (not measured on the chip)
TILE_SHAPE = (32, 64)


#: pair-batch row budget: PB pairs x B rows ~ 512Ki rows per matmul batch
_PAIR_ROWS = 512 * 1024


def pair_batch(B: int) -> int:
    return max(8, min(4096, _PAIR_ROWS // max(B, 1)))


def ladder8(n: int) -> int:
    """Geometric (~1.25x) bucket ladder on multiples of 8 — the shared
    shape-bucketing rule for compact chunk counts and MXU pair padding
    (both feed compiled kernel shapes; one rule keeps them aligned)."""
    b = 8
    while b < n:
        b = -(-int(b * 1.25) // 8) * 8
    return b


def _chunk_boxes(compact: Dict, table, col: str, dims: int, shift: int,
                 box_cache, version):
    """Exact per-chunk normalized-index boxes from the sorted key column:
    deinterleave every window row's (quantized) key, segment-min/max per
    chunk via ``reduceat``. Exact up to key quantization (each quantized
    cell contributes its full extent), which end-point prefix cubes are
    not: a scan window is a gap-union of cover ranges, so a chunk's
    end-point cube can span the whole union while its rows sit in two
    small clusters. Cached in ``box_cache`` (a ``viewcache.BoundedCache``)
    per (windows, store version) — ~ms for millions of rows, amortized
    across grids and repeat queries."""
    ckey = (compact["whash"], compact["B"], col, table.n, version)
    hit = box_cache.get(ckey)
    if hit is not None:
        return hit
    from geomesa_tpu.curves.zorder import deinterleave2, deinterleave3

    key = table.key_columns[col]
    L = table.shard_len
    cstart, lo, valid = compact["cstart"], compact["lo"], compact["valid"]
    act = valid > 0
    cs = (cstart + lo).astype(np.int64)
    s_of = cs // L
    g0 = table.shard_bounds[s_of] + (cs % L)
    segs = [
        key[a:a + int(v)]
        for a, v in zip(g0[act], valid[act])
    ]
    if not segs:
        return None
    cat = np.concatenate(segs).astype(np.uint64)
    sh = np.uint64(shift)
    deinter = deinterleave2 if dims == 2 else deinterleave3
    lo_parts = deinter(cat << sh)
    hi_parts = deinter(((cat + np.uint64(1)) << sh) - np.uint64(1))
    starts = np.concatenate(([0], np.cumsum(valid[act].astype(np.int64))[:-1]))
    n_chunk = len(valid)
    out = []
    for d in range(2):  # x, y only (z3's t dimension is irrelevant here)
        lo_d = np.minimum.reduceat(lo_parts[d], starts)
        hi_d = np.maximum.reduceat(hi_parts[d], starts)
        full_lo = np.zeros(n_chunk, np.uint64)
        full_hi = np.zeros(n_chunk, np.uint64)
        full_lo[act] = lo_d
        full_hi[act] = hi_d
        out.append((full_lo, full_hi))
    return box_cache.put(ckey, out)


def pair_candidates(
    compact: Dict, table, keyspace, bbox, width: int, height: int,
    TY: int, TX: int, box_cache, version,
) -> Optional[Dict]:
    """Host-side (chunk, tile) candidate list for the compacted scan layout.

    Chunk spatial boxes come from the chunk's own sorted keys
    (:func:`_chunk_boxes`) — conservative supersets (quantized keys widen
    the box by one quantization cell, and the device's f32 px/py rounding
    is covered by a one-cell pad), which is all correctness needs: rows
    outside a pair's tile simply match no one-hot column. Returns None
    when the index has no morton key column (attr/id/xz tables fall back
    to the scatter path). Shared by the XLA-einsum pair kernel below and
    the pallas grouped kernel (kernels/density_pallas.py).
    """
    kind = getattr(keyspace, "kind", None)
    if kind == "z3":
        col, dims = "__z3", 3
        sfc = keyspace.sfc
    elif kind == "z2":
        col, dims = "__z2", 2
        sfc = keyspace.sfc
    else:
        return None
    key = table.key_columns.get(col)
    if key is None:
        return None
    shift = 0
    if table.key_shifts is not None:
        shift = int(table.key_shifts.get(col, 0))
    lon, lat = sfc.lon, sfc.lat
    bits = lon.bits

    valid = compact["valid"]
    act = valid > 0
    boxes = _chunk_boxes(compact, table, col, dims, shift, box_cache, version)
    if boxes is None:
        return None
    (x0, x1), (y0, y1) = boxes

    xmin, ymin, xmax, ymax = (float(v) for v in bbox)
    cellw = (xmax - xmin) / width
    cellh = (ymax - ymin) / height
    scale_x = (lon.hi - lon.lo) / (1 << bits)
    scale_y = (lat.hi - lat.lo) / (1 << bits)
    x0 = x0.astype(np.float64)
    x1 = x1.astype(np.float64)
    y0 = y0.astype(np.float64)
    y1 = y1.astype(np.float64)
    # normalized index -> cell range. The pad must cover (a) the device's
    # f32 px/py rounding and (b) f32 COORDINATE representation error —
    # |x| * 2^-24, which at deep zoom (cell smaller than the coordinate
    # ulp) exceeds one cell, so the pad scales with ulp/cell
    ulp_x = max(abs(lon.lo), abs(lon.hi)) * 2.0 ** -24
    ulp_y = max(abs(lat.lo), abs(lat.hi)) * 2.0 ** -24
    pad_x = 1 + int(np.ceil(ulp_x / max(cellw, 1e-300)))
    pad_y = 1 + int(np.ceil(ulp_y / max(cellh, 1e-300)))
    cx0 = np.floor((lon.lo + x0 * scale_x - xmin) / cellw).astype(np.int64) - pad_x
    cx1 = np.floor((lon.lo + (x1 + 1) * scale_x - xmin) / cellw).astype(np.int64) + pad_x
    cy0 = np.floor((lat.lo + y0 * scale_y - ymin) / cellh).astype(np.int64) - pad_y
    cy1 = np.floor((lat.lo + (y1 + 1) * scale_y - ymin) / cellh).astype(np.int64) + pad_y
    cx0 = np.clip(cx0, 0, width - 1)
    cx1 = np.clip(cx1, 0, width - 1)
    cy0 = np.clip(cy0, 0, height - 1)
    cy1 = np.clip(cy1, 0, height - 1)

    ntx = -(-width // TX)
    nty = -(-height // TY)
    tx0, tx1 = cx0 // TX, cx1 // TX
    ty0, ty1 = cy0 // TY, cy1 // TY
    nx = np.where(act, tx1 - tx0 + 1, 0)
    ny = np.where(act, ty1 - ty0 + 1, 0)
    per = (nx * ny).astype(np.int64)
    P = int(per.sum())
    if P == 0:
        return None
    chunk_of = np.repeat(np.arange(len(per)), per)
    j = np.arange(P) - np.repeat(np.cumsum(per) - per, per)
    tx = tx0[chunk_of] + (j % np.maximum(nx[chunk_of], 1))
    ty = ty0[chunk_of] + (j // np.maximum(nx[chunk_of], 1))
    return {
        "chunk_of": chunk_of, "tx": tx, "ty": ty,
        "ntx": ntx, "nty": nty, "P": P,
    }


def build_pairs(
    compact: Dict, table, keyspace, bbox, width: int, height: int,
    box_cache, version,
) -> Optional[Dict]:
    """(chunk, tile) pair arrays shaped for the XLA einsum kernel."""
    TY, TX = TILE_SHAPE
    cand = pair_candidates(
        compact, table, keyspace, bbox, width, height, TY, TX,
        box_cache, version,
    )
    if cand is None:
        return None
    chunk_of, tx, ty = cand["chunk_of"], cand["tx"], cand["ty"]
    ntx, nty, P = cand["ntx"], cand["nty"], cand["P"]
    B = compact["B"]
    PB = pair_batch(B)
    Pp = -(-ladder8(P) // PB) * PB
    pad = Pp - P

    def _pad(a, fill=0):
        return np.concatenate([a, np.full(pad, fill, a.dtype)]) if pad else a

    return {
        "chunk": _pad(chunk_of.astype(np.int32)),
        "px0": _pad((tx * TX).astype(np.int32)),
        "py0": _pad((ty * TY).astype(np.int32)),
        "tile": _pad((ty * ntx + tx).astype(np.int32)),
        "pvalid": _pad(np.ones(P, np.float32)),
        "P": Pp,
        "PB": PB,
        "ntx": ntx,
        "nty": nty,
        "TY": TY,
        "TX": TX,
        "n_pairs": P,
    }


def density_grid_pairs(x, y, mask, bbox, width: int, height: int, weight,
                       pair_chunk, px0, py0, ptile, pvalid,
                       PB: int, ntx: int, nty: int, TY: int, TX: int, xp):
    """Device kernel: [C, B] compact columns + [P] pair arrays -> grid.

    Unweighted counts ride the MXU in bfloat16 one-hots (0/1 exact) with
    f32 accumulation; weighted densities use f32 operands."""
    import jax
    import jax.numpy as jnp

    xmin, ymin, xmax, ymax = bbox
    px = jnp.clip(
        ((x - xmin) / (xmax - xmin) * width).astype(jnp.int32), 0, width - 1
    )
    py = jnp.clip(
        ((y - ymin) / (ymax - ymin) * height).astype(jnp.int32), 0, height - 1
    )
    w = (
        mask.astype(jnp.float32)
        if weight is None
        else jnp.where(mask, weight.astype(jnp.float32), jnp.float32(0))
    )
    dt = jnp.bfloat16 if weight is None else jnp.float32
    # f32 operands need HIGHEST: the TPU's default contract precision
    # rounds them to bf16 (a v5e run put weighted cells 1e-2 off, PR 21);
    # the per-tile partials of the second einsum are f32 sums either way
    wprec = None if weight is None else jax.lax.Precision.HIGHEST
    ntiles = ntx * nty
    P = pair_chunk.shape[0]
    ix = jnp.arange(TX, dtype=jnp.int32)[None, None, :]
    iy = jnp.arange(TY, dtype=jnp.int32)[None, None, :]
    it = jnp.arange(ntiles, dtype=jnp.int32)[None, :]

    def body(i, acc):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * PB, PB)  # noqa: E731
        pc = sl(pair_chunk)
        gw = w[pc] * sl(pvalid)[:, None]
        lx = px[pc] - sl(px0)[:, None]
        ly = py[pc] - sl(py0)[:, None]
        ohx = (lx[:, :, None] == ix).astype(dt)
        A = jnp.where(ly[:, :, None] == iy, gw[:, :, None], 0).astype(dt)
        tile = jnp.einsum(
            "pby,pbx->pyx", A, ohx, precision=wprec,
            preferred_element_type=jnp.float32,
        )
        oht = (sl(ptile)[:, None] == it).astype(jnp.float32)
        return acc + jnp.einsum(
            "pt,pyx->tyx", oht, tile, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    acc = jax.lax.fori_loop(
        0, P // PB, body, jnp.zeros((ntiles, TY, TX), jnp.float32)
    )
    grid = acc.reshape(nty, ntx, TY, TX).transpose(0, 2, 1, 3)
    return grid.reshape(nty * TY, ntx * TX)[:height, :width]
