"""Density (heatmap) as a Pallas grouped one-hot matmul — the fast device path.

All prior device formulations of the DensityScan analog
(index/iterators/DensityScan.scala:29-136) hit hardware walls on v5e:

- XLA scatter-add costs ~7 ns per touched row regardless of batching (the
  per-update serialization is architectural): 2M admitted rows = ~15 ms.
- The XLA einsum pair kernel (kernels/density_mxu.py) materializes its
  [PB, B, TX] one-hot operands in HBM between the VPU compare that builds
  them and the MXU contraction that consumes them — ~7x off roofline.
- Any per-query re-ordering of row data into pair order is itself the
  bottleneck: per-element XLA gathers run ~7.5 ns/element and B-row slab
  gathers are DMA-descriptor-bound (~0.5 us per small slab).

This kernel therefore never reorders row data. The mask/weight and cell
coordinates stay in the dense compact [C, B] layout; a per-chunk
(chunk, tile) pair list sorted by tile drives the pallas GRID. Each step
fetches the [SG, B] superchunk block CONTAINING its pair's chunk via a
scalar-prefetched index map (``BlockSpec`` index_map reading ``sc[p]`` =
chunk // SG; a single-chunk block would violate the (8, 128) minimum
block shape) and selects the chunk's sublane row with a second prefetched
scalar (``row[p]`` = chunk % SG). The stable tile sort keeps chunk ids
ascending within a tile run, so consecutive steps usually reuse the
already-fetched block. Per step, the row's one-hots are built in VMEM
with rows in LANES and grid cells in SUBLANES (natural layouts, no
relayout):

    ohx[T, B] = onehot(sublane_iota == px - tile_x0)   # VPU, VMEM-only
    A[T, B]   = w * onehot(sublane_iota == py - tile_y0)
    tile[T, T] += A @ ohx^T                            # MXU, contract lanes

Rows outside the pair's tile produce all-zero one-hot columns
(clip(1-|dx|, 0, 1) with out-of-range dx), so multi-tile chunks need no
masking; consecutive steps of one tile accumulate in VMEM and write back
on tile change (grouped-matmul revisiting). Schedules longer than
``SEGMENT`` pairs run as several calls (the pair arrays live in SMEM);
their per-tile partials add. Timed in r4 through a device plug-in since
removed (unrecorded): ~9.5 ms vs 15.5 ms scatter and ~22 ms einsum at 36k
pairs.

Unweighted counts use bfloat16 one-hots (0/1 exact, f32 accumulation);
weighted densities use f32 operands end-to-end.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

#: fixed tile = the MXU native shape
TILE = 128

#: chunks per superchunk (the fetch granularity; 8 = the minimum legal
#: sublane block)
SG = 8

#: pairs per pallas call. The five pair arrays are scalar-prefetched into
#: SMEM (20 B per pair, 1 MiB on a v5e: the compiler refuses ~52k pairs),
#: so a longer schedule runs as several calls whose tile partials add
SEGMENT = 16384

#: pad-pair tile origin: far enough off-grid that every one-hot misses,
#: small enough that int32 cell arithmetic cannot overflow
_OFFGRID = np.int32(1 << 20)


#: the pair budget: the grouped kernel serves a view only when its
#: (chunk, tile) pairs are at most this many times its real chunks
MAX_DUP = 4.0


def build_grouped(cand: Dict, B: int) -> Dict:
    """Host-side pair schedule for the grouped kernel from the view's
    ``TILE``-square candidates (``pair_candidates``): (superchunk, tile)
    pairs sorted by tile id, one pallas grid step per pair. The caller
    has checked the pair budget (:data:`MAX_DUP`)."""
    ntx, nty, P = cand["ntx"], cand["nty"], cand["P"]
    ntiles = ntx * nty
    chunk_of, tx, ty = cand["chunk_of"], cand["tx"], cand["ty"]
    tile = (ty * ntx + tx).astype(np.int32)
    # stable sort by tile keeps chunk ids ascending within each tile run,
    # so consecutive steps usually land in the same superchunk block and
    # pallas skips the re-fetch
    order = np.argsort(tile, kind="stable")
    chunk = chunk_of[order]
    tile = tile[order]
    ox = (tx[order] * TILE).astype(np.int32)
    oy = (ty[order] * TILE).astype(np.int32)
    # bucket the pair count (shared ladder with the compact chunk count) so
    # similar queries reuse one compiled kernel shape instead of tracing a
    # fresh pallas program per distinct P. Pad pairs aim at the LAST tile
    # with an off-grid origin: their one-hots are all-zero, so they
    # accumulate nothing (and keep the tile-sorted invariant).
    from geomesa_tpu.kernels.density_mxu import ladder8

    Pp = ladder8(P)
    if Pp != P:
        pad = Pp - P

        def _pad(a, fill):
            return np.concatenate([a, np.full(pad, fill, a.dtype)])

        chunk = _pad(chunk, 0)
        tile = _pad(tile, ntiles - 1)
        ox = _pad(ox, _OFFGRID)
        oy = _pad(oy, _OFFGRID)
    return {
        "sc": (chunk // SG).astype(np.int32),
        "row": (chunk % SG).astype(np.int32),
        "tile": tile,
        "ox": ox,
        "oy": oy,
        "B": B,
        "ntx": ntx,
        "nty": nty,
        "n_pairs": Pp,
    }


def density_grid_grouped(x, y, mask, bbox, width: int, height: int, weight,
                         sc, row, tile, ox, oy,
                         B: int, ntx: int, nty: int, n_pairs: int):
    """Device kernel: dense compact [C, B] columns + pair schedule -> grid.

    ``x``/``y``/``mask`` stay in compact order; the pallas index maps pull
    each pair's superchunk block on demand — no reordering pass."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from geomesa_tpu.kernels import pallas_kernels as pk

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
    # pad the chunk axis to a whole number of superchunks (ladder8 makes
    # this a no-op in practice)
    C = px.shape[0]
    pad = (-C) % SG
    if pad:
        px = jnp.pad(px, ((0, pad), (0, 0)))
        py = jnp.pad(py, ((0, pad), (0, 0)))
        w = jnp.pad(w, ((0, pad), (0, 0)))
    dt = jnp.bfloat16 if weight is None else jnp.float32
    # weighted: f32 products on the MXU, stated rather than left to the
    # compiler's default contract precision
    precision = None if weight is None else jax.lax.Precision.HIGHEST
    ntiles = ntx * nty
    T = TILE

    def kernel(sc_ref, r_ref, t_ref, ox_ref, oy_ref,
               px_ref, py_ref, w_ref, acc_ref):
        p = pl.program_id(0)
        first = (p == 0) | (t_ref[p] != t_ref[jnp.maximum(p - 1, 0)])
        iot = jax.lax.broadcasted_iota(jnp.int32, (T, B), 0)
        r = r_ref[p]
        pxr = px_ref[pl.ds(r, 1), :] - ox_ref[p]   # [1, B]
        pyr = py_ref[pl.ds(r, 1), :] - oy_ref[p]
        wr = w_ref[pl.ds(r, 1), :]
        dx = jnp.broadcast_to(pxr, (T, B)) - iot
        dy = jnp.broadcast_to(pyr, (T, B)) - iot
        # arithmetic one-hots: (dx == 0) compiles to an i1 relayout mosaic
        # rejects ("non-singleton dimension replicated"), so clip(1 - |d|)
        ohx = jnp.clip(1 - jnp.abs(dx), 0, 1).astype(dt)
        A = (jnp.broadcast_to(wr, (T, B)).astype(dt)
             * jnp.clip(1 - jnp.abs(dy), 0, 1).astype(dt))
        t = jax.lax.dot_general(
            A, ohx, (((1,), (1,)), ((), ())),
            precision=precision,
            preferred_element_type=jnp.float32,
        )[None]

        @pl.when(first)
        def _():
            acc_ref[...] = t

        @pl.when(~first)
        def _():
            acc_ref[...] += t

    def call(steps):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=5,
                grid=(steps,),
                in_specs=[
                    pl.BlockSpec(
                        (SG, B), lambda p, sc, r, t, ox, oy: (sc[p], 0)
                    ),
                    pl.BlockSpec(
                        (SG, B), lambda p, sc, r, t, ox, oy: (sc[p], 0)
                    ),
                    pl.BlockSpec(
                        (SG, B), lambda p, sc, r, t, ox, oy: (sc[p], 0)
                    ),
                ],
                out_specs=pl.BlockSpec(
                    (1, T, T), lambda p, sc, r, t, ox, oy: (t[p], 0, 0)
                ),
            ),
            out_shape=jax.ShapeDtypeStruct((ntiles, T, T), jnp.float32),
            interpret=pk.interpret_mode(),
            name="density_grouped",
        )

    acc = None
    for lo in range(0, n_pairs, SEGMENT):
        seg = [a[lo:lo + SEGMENT] for a in (sc, row, tile, ox, oy)]
        part = call(seg[0].shape[0])(*seg, px, py, w)
        # blocks this call never visited hold uninitialized VMEM
        seen = jnp.zeros(ntiles, bool).at[seg[2]].set(True)
        part = jnp.where(seen[:, None, None], part, jnp.float32(0))
        acc = part if acc is None else acc + part
    grid = acc.reshape(nty, ntx, T, T).transpose(0, 2, 1, 3)
    return grid.reshape(nty * T, ntx * T)[:height, :width]
