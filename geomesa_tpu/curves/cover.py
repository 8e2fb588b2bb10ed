"""Z-range cover: decompose an axis-aligned query box into Morton-order ranges.

Host-side, per-query planning code — the analog of ``sfcurve``'s ``zranges``
used by the reference's key spaces (e.g. geomesa-z3/.../Z3SFC.scala:54 ->
Z3IndexKeySpace.getRanges, geomesa-index-api/.../z3/Z3IndexKeySpace.scala:162).

Algorithm: BFS over z-prefix cells. A cell at level L fixes the top L bits of
every dimension; its z-values form the contiguous block
``[prefix·0…0, prefix·1…1]``. Cells fully inside the query box emit their whole
block; intersecting cells are subdivided until ``max_ranges`` would be
exceeded, at which point remaining frontier cells are emitted whole
(over-covering — correctness comes from the downstream fine filter, exactly as
in the reference). Adjacent/overlapping ranges are merged.

Bit layout matches ``zorder.py``: for d dims, bit i of dim k sits at
``d*i + (d-1-k)``.
"""

from __future__ import annotations

from collections import deque
from typing import List, NamedTuple, Sequence, Tuple

import numpy as np


class ZRange(NamedTuple):
    lo: int  # inclusive
    hi: int  # inclusive


def range_arrays(ranges: Sequence[Tuple[int, int]], dtype=np.int64
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """A list of inclusive ranges as two aligned arrays of their lows and
    highs, the form the z-range covers and key plans carry."""
    a = np.asarray(ranges, dtype).reshape(-1, 2)
    return a[:, 0].copy(), a[:, 1].copy()


def _merge(ranges: List[ZRange]) -> List[ZRange]:
    if not ranges:
        return []
    ranges.sort()
    out = [ranges[0]]
    for r in ranges[1:]:
        last = out[-1]
        if r.lo <= last.hi + 1:
            if r.hi > last.hi:
                out[-1] = ZRange(last.lo, r.hi)
        else:
            out.append(r)
    return out


def zcover_fast(
    lo: Sequence[int],
    hi: Sequence[int],
    bits: int,
    dims: int,
    max_ranges: int = 2000,
) -> Tuple[np.ndarray, np.ndarray]:
    """Cover via the native runtime when built, else the Python BFS below.

    Semantics are identical (parity enforced by tests/test_native.py); the
    native path exists because cover is the one per-query host loop whose cost
    grows with range budget (SURVEY.md §3.1 'pathological polygons')."""
    from geomesa_tpu import native

    return native.zcover(lo, hi, bits, dims, max_ranges)


def zcover(
    lo: Sequence[int],
    hi: Sequence[int],
    bits: int,
    dims: int,
    max_ranges: int = 2000,
) -> Tuple[np.ndarray, np.ndarray]:
    """Cover the integer box [lo, hi] (inclusive, per-dim) with z-ranges.

    ``lo``/``hi`` are normalized fixed-point coordinates (0 .. 2^bits-1).
    Returns merged, sorted, inclusive z-value ranges as two int64 arrays
    (lows, highs); values fit in ``dims*bits`` <= 63 bits.
    """
    d = dims
    total_bits = d * bits
    qlo = [int(v) for v in lo]
    qhi = [int(v) for v in hi]
    for k in range(d):
        if qlo[k] > qhi[k]:
            raise ValueError(f"inverted query box on dim {k}: {qlo[k]} > {qhi[k]}")

    # Frontier entries: (zmin, level, mins, maxs) where mins/maxs are the
    # cell's per-dim coordinate bounds and zmin its smallest z-value.
    full = (1 << bits) - 1
    frontier = deque([(0, 0, tuple([0] * d), tuple([full] * d))])
    out: List[ZRange] = []

    def cell_span(level: int) -> int:
        return (1 << (d * (bits - level))) - 1  # number of z values in cell - 1

    while frontier:
        zmin, level, mins, maxs = frontier.popleft()
        # Disjoint?
        if any(maxs[k] < qlo[k] or mins[k] > qhi[k] for k in range(d)):
            continue
        # Fully contained?
        if all(qlo[k] <= mins[k] and maxs[k] <= qhi[k] for k in range(d)):
            out.append(ZRange(zmin, zmin + cell_span(level)))
            continue
        # At max depth: emit (single z value).
        if level == bits:
            out.append(ZRange(zmin, zmin))
            continue
        # Budget check: if splitting would exceed the budget, emit frontier whole.
        if len(out) + len(frontier) + (1 << d) > max_ranges:
            out.append(ZRange(zmin, zmin + cell_span(level)))
            while frontier:
                zm, lv, mn, mx = frontier.popleft()
                if any(mx[k] < qlo[k] or mn[k] > qhi[k] for k in range(d)):
                    continue
                out.append(ZRange(zm, zm + cell_span(lv)))
            break
        # Subdivide: fix the next bit (bit index b = bits-1-level) of each dim.
        b = bits - 1 - level
        half = 1 << b
        group_shift = d * b  # position of this level's d-bit group in z
        for combo in range(1 << d):
            c_mins, c_maxs = [], []
            zadd = 0
            for k in range(d):
                # dim k's bit within the group is at offset (d-1-k)
                bit = (combo >> (d - 1 - k)) & 1
                if bit:
                    c_mins.append(mins[k] + half)
                    c_maxs.append(maxs[k])
                    zadd |= 1 << (group_shift + (d - 1 - k))
                else:
                    c_mins.append(mins[k])
                    c_maxs.append(maxs[k] - half)
            frontier.append((zmin + zadd, level + 1, tuple(c_mins), tuple(c_maxs)))

    return range_arrays(_merge(out))
