"""Key spaces: feature batch -> sort keys (ingest) and filter -> scan windows
(plan time).

Reference parity (SURVEY.md §2.4):

* ``Z3KeySpace``   ~ Z3IndexKeySpace (Z3Index): point geom + time
* ``Z2KeySpace``   ~ Z2IndexKeySpace (Z2Index): point geom
* ``XZ3KeySpace``  ~ XZ3IndexKeySpace: extent geom + time
* ``XZ2KeySpace``  ~ XZ2IndexKeySpace: extent geom
* ``IdKeySpace``   ~ IdIndex: feature id lookups
* ``AttributeKeySpace`` ~ AttributeIndex: per-attribute sorted index

The TPU translation of "byte ranges": each key space can compute, per shard
and per query, a set of **(start, end) row windows** into that shard's sorted
arrays via ``searchsorted`` — the slice-descriptor model (SURVEY.md §1). The
fine-grained z-ranges additionally drive selectivity estimation.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from geomesa_tpu import config
from geomesa_tpu.curves.binned_time import BinnedTime, TimePeriod
from geomesa_tpu.curves.cover import ZRange, range_arrays
from geomesa_tpu.curves.xz import XZ2SFC, XZ3SFC
from geomesa_tpu.curves.zorder import Z2SFC, Z3SFC, split_u64
from geomesa_tpu.filter import ir
from geomesa_tpu.index import packsort
from geomesa_tpu.schema.columns import ColumnBatch
from geomesa_tpu.schema.feature_type import FeatureType

MAX_WINDOW_BINS = 64  # collapse per-bin windows beyond this many time bins


def _no_ranges() -> np.ndarray:
    return np.zeros(0, np.int64)


@dataclass
class KeyPlan:
    """Plan-time product of a key space for one query (IndexValues+ranges
    analog). ``windows(shard_cols)`` resolves to row windows per shard."""

    keyspace: "KeySpace"
    #: provably empty (disjoint bounds)
    disjoint: bool = False
    #: full scan (no key constraint)
    full_scan: bool = False
    #: cover ranges, inclusive, as two aligned arrays of their low and
    #: high keys: int64 for the z and xz curves, uint64 for S2 cell ids
    #: (empty for full scans)
    lo: np.ndarray = field(default_factory=_no_ranges)
    hi: np.ndarray = field(default_factory=_no_ranges)
    #: time bins touched (z3/xz3)
    bins: Optional[np.ndarray] = None
    #: estimated fraction of key space covered (coarse; cost input)
    coverage: float = 1.0

    @property
    def ranges(self) -> List[ZRange]:
        """The cover as ``ZRange`` objects, derived from ``lo``/``hi`` on
        each read: for callers off the scan path (explain, histogram
        estimates, the xz and S2 key spaces' per-range loops)."""
        return [ZRange(a, b) for a, b in zip(self.lo.tolist(), self.hi.tolist())]

    def windows(self, shard_cols: Dict[str, np.ndarray], n: int) -> Tuple[np.ndarray, np.ndarray]:
        """Resolve to (starts, ends) row windows for one shard's host key
        columns (each sorted). ``n`` = row count of the shard."""
        if self.disjoint:
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        if self.full_scan:
            return np.zeros(1, np.int64), np.full(1, n, np.int64)
        return self.keyspace.resolve_windows(self, shard_cols, n)


class KeySpace:
    name: str = "base"   # unique per instance (table key)
    kind: str = "base"   # family (cost model / dispatch key)
    key_cols: Sequence[str] = ()

    def supports(self, ft: FeatureType) -> bool:
        raise NotImplementedError

    def index_keys(self, ft: FeatureType, batch: ColumnBatch) -> Dict[str, np.ndarray]:
        """Vectorized key encode for an ingest batch (toIndexKey analog)."""
        raise NotImplementedError

    def sort_order(self, cols: Dict[str, np.ndarray]) -> np.ndarray:
        """argsort for the table's global sort (primary last in lexsort)."""
        raise NotImplementedError

    def fast_build(
        self,
        cols: Dict[str, np.ndarray],
        force_shifts: Optional[Dict[str, int]] = None,
    ) -> Optional[tuple]:
        """Radix pack-sort build (packsort module): returns
        (order, sorted_key_columns, shifts) — key columns QUANTIZED by
        ``shifts`` — or None to fall back to :meth:`sort_order` + gather.
        ``force_shifts`` pins the quantization to an existing table's
        (LSM-append compatibility)."""
        return None

    def plan(self, ft: FeatureType, f: ir.Filter) -> Optional[KeyPlan]:
        """None if this key space cannot serve the filter at all."""
        raise NotImplementedError

    def resolve_windows(self, plan: KeyPlan, shard_cols, n: int):
        raise NotImplementedError

    #: False when appends must always fully rebuild (checked BEFORE any
    #: fresh-batch sorting so the probe costs nothing)
    can_insert = True

    def insert_positions(
        self,
        sorted_key_cols: Dict[str, np.ndarray],
        fresh_sorted: Dict[str, np.ndarray],
    ) -> Optional[np.ndarray]:
        """Merge positions of already-sorted fresh keys into the existing
        sorted key columns — the LSM append path (O(old + fresh) instead of a
        full re-sort). Generic: single key column -> one searchsorted;
        (bin, key) pairs -> per-bin two-level searchsorted. Returns None when
        this key space needs a full rebuild (e.g. rank vocabularies)."""
        cols = list(self.key_cols)
        if len(cols) == 1:
            k = cols[0]
            return np.searchsorted(
                sorted_key_cols[k], fresh_sorted[k], side="right"
            ).astype(np.int64)
        if len(cols) == 2:  # (bin, key): z3/xz3/s3 layouts
            bc, kc = cols
            bins_col = sorted_key_cols[bc]
            key_col = sorted_key_cols[kc]
            fb = fresh_sorted[bc]
            fk = fresh_sorted[kc]
            p = np.empty(len(fb), np.int64)
            for b in np.unique(fb):
                sel = fb == b
                s = int(np.searchsorted(bins_col, b, side="left"))
                e = int(np.searchsorted(bins_col, b, side="right"))
                p[sel] = s + np.searchsorted(
                    key_col[s:e], fk[sel], side="right"
                )
            return p
        return None


def _bin_and_offset(binned: BinnedTime, ft: FeatureType, dtg: str, batch):
    """(bin, offset_ms) for an ingest batch, reusing the ``<dtg>__bin``
    column encode_batch already computed (same period as the schema's key
    spaces) — saves a second floor-division pass over the timestamps."""
    bin_col = dtg + "__bin"
    if bin_col in batch and ft.time_period == binned.period:
        b = batch[bin_col]
        return b, binned.offset_from_bin(batch[dtg], b)
    return binned.to_bin_and_offset(batch[dtg])


#: per-shard budget for resolved scan windows (bins x z-ranges); beyond it
#: ranges gap-union down (over-cover; the fine filter restores exactness)
MAX_SHARD_WINDOWS = 256

_window_cap_tls = __import__("threading").local()


def shard_window_cap() -> int:
    """Active per-shard window budget. The compacted scan path raises it
    (``window_cap``) to resolve gap-union-free windows: scan cost there is
    per admitted ROW, not per window, so fine windows are strictly
    better — tighter chunk spatial boxes and fewer false-positive rows."""
    return getattr(_window_cap_tls, "cap", None) or MAX_SHARD_WINDOWS


class window_cap:
    """Context manager scoping a raised shard-window budget."""

    def __init__(self, cap: int):
        self.cap = cap

    def __enter__(self):
        self.prev = getattr(_window_cap_tls, "cap", None)
        _window_cap_tls.cap = self.cap
        return self

    def __exit__(self, *exc):
        _window_cap_tls.cap = self.prev


def _merge_cap(los: np.ndarray, his: np.ndarray, cap: int,
               adjacent: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """One vectorized pass shared by range- and window-capping: sort, merge
    overlapping (or within ``adjacent``) intervals, then keep only the
    ``cap-1`` LARGEST gaps as separators (equivalent to repeatedly unioning
    the smallest gap, without the quadratic loop). Over-covers; the fine
    filter restores exactness (Z3Filter.scala keeps every window; here the
    kernel's window count is a static shape, so a budget applies)."""
    if len(los) == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    order = np.argsort(los, kind="stable")
    los = np.asarray(los, np.int64)[order]
    his = np.asarray(his, np.int64)[order]
    # merge overlapping/adjacent: a new interval starts where lo exceeds
    # the running max of prior his (+adjacency)
    run_hi = np.maximum.accumulate(his)
    new = np.concatenate(([True], los[1:] > run_hi[:-1] + adjacent))
    idx = np.flatnonzero(new)
    mlo = los[idx]
    mhi = run_hi[np.concatenate((idx[1:] - 1, [len(los) - 1]))]
    if len(mlo) > cap:
        gaps = mlo[1:] - mhi[:-1]
        keep = np.sort(np.argpartition(gaps, -(cap - 1))[-(cap - 1):]) \
            if cap > 1 else np.zeros(0, np.int64)
        mlo = np.concatenate((mlo[:1], mlo[keep + 1]))
        mhi = np.concatenate((mhi[keep], mhi[-1:]))
    return mlo, mhi


def _merge_covers(covers: Sequence[Tuple[np.ndarray, np.ndarray]]
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Union of several (lows, highs) covers, merged and capped at the
    range budget (adjacency 1: integer key ranges touching end-to-end
    fuse). Each query geometry gets its own cover, so disjoint bboxes get
    disjoint ranges instead of one envelope cover (reference
    FilterHelper.extractGeometries feeds per-geometry ranges the same way)."""
    return _merge_cap(
        np.concatenate([lo for lo, _ in covers]),
        np.concatenate([hi for _, hi in covers]),
        config.SCAN_RANGES_TARGET.to_int() or 2000, adjacent=1,
    )


def _shift_of(shard_cols: Dict, col: str) -> int:
    """Quantization shift of a stored key column (0 on the argsort path).
    Bounds must be shifted identically before searchsorted — floor on both
    sides keeps windows supersets (side='right' then covers the whole
    quantized cell of the upper bound)."""
    shifts = shard_cols.get("__shifts__")
    return 0 if shifts is None else shifts.get(col, 0)


def _bin_segments(bins_col: np.ndarray, bins) -> Tuple[np.ndarray, np.ndarray]:
    """Each time bin's [start, end) row segment of a (bin, key)-sorted shard,
    in one vectorized lookup per side. The needles take the column's own
    dtype: a Python-int needle makes numpy cast the whole column per call."""
    needles = np.asarray(bins).astype(bins_col.dtype, copy=False)
    return (
        np.searchsorted(bins_col, needles, side="left"),
        np.searchsorted(bins_col, needles, side="right"),
    )


def _coverage(lo: np.ndarray, hi: np.ndarray, total_bits: int) -> float:
    # in uint64: the whole 63-bit space spans 2^63 keys, one past int64;
    # merged ranges are disjoint, so the exact sum fits
    span = int(((hi - lo).astype(np.uint64) + np.uint64(1)).sum(dtype=np.uint64))
    return span / float(1 << total_bits)


class Z3KeySpace(KeySpace):
    """(bin, z3) keys over point geometry + time (reference
    Z3IndexKeySpace.scala:64-233)."""

    name = "z3"
    kind = "z3"

    def __init__(self, geom: str, dtg: str, period: "str | TimePeriod" = TimePeriod.WEEK):
        self.geom = geom
        self.dtg = dtg
        self.sfc = Z3SFC(period)
        self.binned = self.sfc.binned
        self.key_cols = ("__z3_bin", "__z3")

    def supports(self, ft):
        return (
            ft.has(self.geom) and ft.attr(self.geom).is_point
            and ft.has(self.dtg) and ft.attr(self.dtg).type == "date"
        )

    def index_keys(self, ft, batch):
        xs = batch[self.geom + "__x"]
        ys = batch[self.geom + "__y"]
        b, off = _bin_and_offset(self.binned, ft, self.dtg, batch)
        z = self.sfc.index(xs, ys, off)
        return {"__z3_bin": np.asarray(b, np.int32), "__z3": z}

    def sort_order(self, cols):
        return np.lexsort((cols["__z3"], cols["__z3_bin"]))

    def fast_build(self, cols, force_shifts=None):
        fs = None if force_shifts is None else force_shifts.get("__z3")
        out = packsort.pack_sort(
            cols["__z3"], 63, prefix=cols["__z3_bin"], force_shift=fs
        )
        if out is None:
            return None
        perm, zq, bins_sorted, shift = out
        return perm, {"__z3_bin": bins_sorted, "__z3": zq}, {"__z3": shift}

    def plan(self, ft, f):
        geoms = ir.extract_geometries(f, self.geom)
        intervals = ir.extract_intervals(f, self.dtg)
        if geoms.disjoint or intervals.disjoint:
            return KeyPlan(self, disjoint=True)
        if intervals.is_empty:
            return None  # no temporal bound: z3 not applicable (reference same)
        # Clamp intervals into representable time.
        CLAMP = 2**45
        iv = [(max(lo, -CLAMP), min(hi, CLAMP)) for lo, hi in intervals.values]
        bins = np.unique(
            np.concatenate([self.binned.bins_between(lo, hi) for lo, hi in iv])
        )
        max_off = float(self.binned.max_offset_ms)
        if geoms.is_empty:
            xy = [((-180.0, -90.0, 180.0, 90.0))]
        else:
            xy = [g.bounds() for g in geoms.values]
        # Per-geometry covers over the full offset span (middle bins);
        # disjoint query boxes produce disjoint range sets (Z3Filter.scala
        # checks every window per row — here every window becomes its own
        # scan window at resolve time).
        zlo, zhi = _merge_covers([
            self.sfc.ranges((b[0], b[2]), (b[1], b[3]), (0.0, max_off))
            for b in xy
        ])
        # Edge-bin time tightening (Z3IndexKeySpace.getIndexValues:133-158:
        # per-bin offset windows): the first/last bin of each interval gets
        # its own cover restricted to the interval's offsets in that bin.
        edge: Dict[int, List[Tuple[np.ndarray, np.ndarray]]] = {}
        for lo, hi in iv:
            blo, olo = self.binned.to_bin_and_offset(np.asarray([lo], np.int64))
            bhi, ohi = self.binned.to_bin_and_offset(np.asarray([hi], np.int64))
            blo, olo = int(blo[0]), float(olo[0])
            bhi, ohi = int(bhi[0]), float(ohi[0])
            for b, off_lo, off_hi in (
                ((blo, olo, max_off if blo != bhi else ohi),)
                + (((bhi, 0.0, ohi),) if bhi != blo else ())
            ):
                edge.setdefault(b, []).extend(
                    self.sfc.ranges(
                        (box[0], box[2]), (box[1], box[3]), (off_lo, off_hi)
                    )
                    for box in xy
                )
        cov = _coverage(zlo, zhi, 63) * min(1.0, len(bins) / max(len(bins), 1))
        plan = KeyPlan(self, lo=zlo, hi=zhi, bins=bins.astype(np.int32),
                       coverage=cov)
        plan._iv = iv
        plan._edge = {b: _merge_covers(cs) for b, cs in edge.items()}
        return plan

    def resolve_windows(self, plan, shard_cols, n):
        bins_col = shard_cols["__z3_bin"]
        z_col = shard_cols["__z3"]
        sh = _shift_of(shard_cols, "__z3")
        bins = plan.bins
        if len(bins) > MAX_WINDOW_BINS:
            # collapse: one window spanning [first bin, last bin]
            s = np.searchsorted(bins_col, bins[0], side="left")
            e = np.searchsorted(bins_col, bins[-1], side="right")
            return np.asarray([s], np.int64), np.asarray([e], np.int64)
        # Per-window pushdown (Z3Filter.scala:18-62 parity): every cover
        # range resolves to its own scan window per bin — disjoint or
        # L-shaped query geometries admit only their own candidates, not
        # the [zmin, zmax] envelope. Edge bins use their time-tightened
        # range sets from plan time. The shifted+merged range sets are
        # shard-independent: computed once per (plan, shift) and cached as
        # search needles in the key column's dtype.
        edge = getattr(plan, "_edge", {})
        cap = shard_window_cap()
        per_bin_cap = max(1, cap // max(len(bins), 1))
        cache = plan.__dict__.setdefault("_shifted_ranges", {})
        sets = cache.get((sh, cap))
        if sets is None:

            def needles(lo, hi):
                mlo, mhi = _merge_cap(lo >> sh, hi >> sh, per_bin_cap,
                                      adjacent=1)
                return mlo.astype(z_col.dtype), mhi.astype(z_col.dtype)

            sets = cache[(sh, cap)] = (
                needles(plan.lo, plan.hi),
                {b: needles(lo, hi) for b, (lo, hi) in edge.items()},
            )
        base, esets = sets
        # Cost per bin, not per range: one lookup finds every bin's segment,
        # then each segment takes all of its range bounds in one search per
        # side. The windows are disjoint (disjoint ranges within a bin,
        # disjoint segments across bins), so the order collected is moot.
        plain = bins[~np.isin(bins, list(esets))]
        seg_lo, seg_hi = _bin_segments(
            bins_col, np.concatenate((plain, np.fromiter(esets, np.int64)))
        )
        sets_per_bin = [base] * len(plain) + list(esets.values())
        starts, ends = [], []
        for (los, his), s, e in zip(
            sets_per_bin, seg_lo.tolist(), seg_hi.tolist()
        ):
            if e <= s or not len(los):
                continue
            seg = z_col[s:e]
            ws = s + np.searchsorted(seg, los, side="left")
            we = s + np.searchsorted(seg, his, side="right")
            keep = we > ws
            starts.append(ws[keep])
            ends.append(we[keep])
        starts = np.concatenate(starts) if starts else np.zeros(0, np.int64)
        if not len(starts):
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        return _cap_windows(starts, np.concatenate(ends), cap)


class Z2KeySpace(KeySpace):
    """z2 keys over point geometry (reference Z2IndexKeySpace)."""

    name = "z2"
    kind = "z2"

    def __init__(self, geom: str):
        self.geom = geom
        self.sfc = Z2SFC()
        self.key_cols = ("__z2",)

    def supports(self, ft):
        return ft.has(self.geom) and ft.attr(self.geom).is_point

    def index_keys(self, ft, batch):
        return {"__z2": self.sfc.index(batch[self.geom + "__x"], batch[self.geom + "__y"])}

    def sort_order(self, cols):
        return np.argsort(cols["__z2"], kind="stable")

    def fast_build(self, cols, force_shifts=None):
        fs = None if force_shifts is None else force_shifts.get("__z2")
        out = packsort.pack_sort(cols["__z2"], 62, force_shift=fs)
        if out is None:
            return None
        perm, zq, _, shift = out
        return perm, {"__z2": zq}, {"__z2": shift}

    def plan(self, ft, f):
        geoms = ir.extract_geometries(f, self.geom)
        if geoms.disjoint:
            return KeyPlan(self, disjoint=True)
        if geoms.is_empty:
            return KeyPlan(self, full_scan=True)
        lo, hi = _merge_covers(
            [self.sfc.ranges(*g.bounds()) for g in geoms.values]
        )
        return KeyPlan(self, lo=lo, hi=hi, coverage=_coverage(lo, hi, 62))

    def resolve_windows(self, plan, shard_cols, n):
        # per-range windows (Z2Filter parity): disjoint query boxes scan
        # only their own covers, not the [zmin, zmax] envelope
        z_col = shard_cols["__z2"]
        sh = _shift_of(shard_cols, "__z2")
        los, his = _merge_cap(plan.lo >> sh, plan.hi >> sh,
                              shard_window_cap(), adjacent=1)
        if not len(los):
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        ws = np.searchsorted(z_col, los.astype(z_col.dtype), side="left")
        we = np.searchsorted(z_col, his.astype(z_col.dtype), side="right")
        keep = we > ws
        if not keep.any():
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        return _cap_windows(
            ws[keep].astype(np.int64), we[keep].astype(np.int64),
            shard_window_cap(),
        )


class XZ2KeySpace(KeySpace):
    """xz2 codes over extent geometries (reference XZ2IndexKeySpace)."""

    name = "xz2"
    kind = "xz2"

    def __init__(self, geom: str, g: int = 12):
        self.geom = geom
        self.sfc = XZ2SFC(g=g)
        self.key_cols = ("__xz2",)

    def supports(self, ft):
        a = ft.attr(self.geom) if ft.has(self.geom) else None
        return a is not None and a.is_geom and not a.is_point

    def index_keys(self, ft, batch):
        return {
            "__xz2": self.sfc.index(
                batch[self.geom + "__xmin"], batch[self.geom + "__ymin"],
                batch[self.geom + "__xmax"], batch[self.geom + "__ymax"],
            )
        }

    def sort_order(self, cols):
        return np.argsort(cols["__xz2"], kind="stable")

    def fast_build(self, cols, force_shifts=None):
        fs = None if force_shifts is None else force_shifts.get("__xz2")
        code = cols["__xz2"].astype(np.uint64)  # sequence codes, nonnegative
        bits = int(self.sfc.subtree_size[0]).bit_length()
        out = packsort.pack_sort(code, bits, force_shift=fs)
        if out is None:
            return None
        perm, cq, _, shift = out
        return perm, {"__xz2": cq}, {"__xz2": shift}

    def plan(self, ft, f):
        geoms = ir.extract_geometries(f, self.geom)
        if geoms.disjoint:
            return KeyPlan(self, disjoint=True)
        if geoms.is_empty:
            return KeyPlan(self, full_scan=True)
        bs = np.asarray([g.bounds() for g in geoms.values])
        bbox = (bs[:, 0].min(), bs[:, 1].min(), bs[:, 2].max(), bs[:, 3].max())
        ranges = self.sfc.ranges(*bbox)
        total = self.sfc.subtree_size[0]
        span = sum(r.hi - r.lo + 1 for r in ranges)
        lo, hi = range_arrays(ranges)
        return KeyPlan(self, lo=lo, hi=hi, coverage=span / total)

    def resolve_windows(self, plan, shard_cols, n):
        # XZ ranges are NOT contiguous-envelope friendly (singleton parent
        # codes interleave) — resolve each merged range to a window.
        col = shard_cols["__xz2"]
        sh = _shift_of(shard_cols, "__xz2")
        starts, ends = [], []
        for r in plan.ranges:
            s = np.searchsorted(col, r.lo >> sh, side="left")
            e = np.searchsorted(col, r.hi >> sh, side="right")
            if e > s:
                starts.append(s)
                ends.append(e)
        if not starts:
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        # cap window count: merge down to MAX_WINDOW_BINS by unioning gaps
        return _cap_windows(
            np.asarray(starts, np.int64), np.asarray(ends, np.int64), MAX_WINDOW_BINS
        )


class XZ3KeySpace(KeySpace):
    """(bin, xz3) codes over extent geometries + time (reference XZ3IndexKeySpace)."""

    name = "xz3"
    kind = "xz3"

    def __init__(self, geom: str, dtg: str, period: "str | TimePeriod" = TimePeriod.WEEK, g: int = 12):
        self.geom = geom
        self.dtg = dtg
        self.sfc = XZ3SFC(period, g=g)
        self.binned = self.sfc.binned
        self.key_cols = ("__xz3_bin", "__xz3")

    def supports(self, ft):
        a = ft.attr(self.geom) if ft.has(self.geom) else None
        return (
            a is not None and a.is_geom and not a.is_point
            and ft.has(self.dtg) and ft.attr(self.dtg).type == "date"
        )

    def index_keys(self, ft, batch):
        b, off = _bin_and_offset(self.binned, ft, self.dtg, batch)
        code = self.sfc.index(
            batch[self.geom + "__xmin"], batch[self.geom + "__ymin"], off,
            batch[self.geom + "__xmax"], batch[self.geom + "__ymax"], off,
        )
        return {"__xz3_bin": np.asarray(b, np.int32), "__xz3": code}

    def sort_order(self, cols):
        return np.lexsort((cols["__xz3"], cols["__xz3_bin"]))

    def fast_build(self, cols, force_shifts=None):
        fs = None if force_shifts is None else force_shifts.get("__xz3")
        bits = int(self.sfc.subtree_size[0]).bit_length()
        out = packsort.pack_sort(
            cols["__xz3"].astype(np.uint64), bits,
            prefix=cols["__xz3_bin"], force_shift=fs,
        )
        if out is None:
            return None
        perm, cq, bins_sorted, shift = out
        return perm, {"__xz3_bin": bins_sorted, "__xz3": cq}, {"__xz3": shift}

    def plan(self, ft, f):
        geoms = ir.extract_geometries(f, self.geom)
        intervals = ir.extract_intervals(f, self.dtg)
        if geoms.disjoint or intervals.disjoint:
            return KeyPlan(self, disjoint=True)
        if intervals.is_empty:
            return None
        CLAMP = 2**45
        iv = [(max(lo, -CLAMP), min(hi, CLAMP)) for lo, hi in intervals.values]
        bins = np.unique(
            np.concatenate([self.binned.bins_between(lo, hi) for lo, hi in iv])
        )
        if geoms.is_empty:
            bbox = (-180.0, -90.0, 180.0, 90.0)
        else:
            bs = np.asarray([g.bounds() for g in geoms.values])
            bbox = (bs[:, 0].min(), bs[:, 1].min(), bs[:, 2].max(), bs[:, 3].max())
        ranges = self.sfc.ranges(
            (bbox[0], bbox[2]), (bbox[1], bbox[3]),
            (0.0, float(self.binned.max_offset_ms)),
        )
        total = self.sfc.subtree_size[0]
        span = sum(r.hi - r.lo + 1 for r in ranges)
        lo, hi = range_arrays(ranges)
        return KeyPlan(self, lo=lo, hi=hi, bins=bins.astype(np.int32),
                       coverage=span / total)

    def resolve_windows(self, plan, shard_cols, n):
        bins_col = shard_cols["__xz3_bin"]
        code_col = shard_cols["__xz3"]
        sh = _shift_of(shard_cols, "__xz3")
        bins = plan.bins
        if len(bins) > 8:  # xz windows multiply per bin; collapse earlier
            s = np.searchsorted(bins_col, bins[0], side="left")
            e = np.searchsorted(bins_col, bins[-1], side="right")
            return np.asarray([s], np.int64), np.asarray([e], np.int64)
        starts, ends = [], []
        ranges = plan.ranges
        seg_lo, seg_hi = _bin_segments(bins_col, bins)
        for s, e in zip(seg_lo.tolist(), seg_hi.tolist()):
            if e <= s:
                continue
            seg = code_col[s:e]
            for r in ranges:
                s2 = s + np.searchsorted(seg, r.lo >> sh, side="left")
                e2 = s + np.searchsorted(seg, r.hi >> sh, side="right")
                if e2 > s2:
                    starts.append(s2)
                    ends.append(e2)
        if not starts:
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        return _cap_windows(
            np.asarray(starts, np.int64), np.asarray(ends, np.int64), MAX_WINDOW_BINS
        )


class S2KeySpace(KeySpace):
    """S2 cell-id keys over point geometry (reference S2Index / S2SFC.scala:17,
    which wraps Google S2; cell math in geomesa_tpu.curves.s2)."""

    name = "s2"
    kind = "s2"

    def __init__(self, geom: str):
        self.geom = geom
        from geomesa_tpu.curves.s2 import S2SFC

        self.sfc = S2SFC(max_cells=64)
        self.key_cols = ("__s2",)

    def supports(self, ft):
        return ft.has(self.geom) and ft.attr(self.geom).is_point

    def index_keys(self, ft, batch):
        return {
            "__s2": self.sfc.index(batch[self.geom + "__x"], batch[self.geom + "__y"])
        }

    def sort_order(self, cols):
        return np.argsort(cols["__s2"], kind="stable")

    def fast_build(self, cols, force_shifts=None):
        fs = None if force_shifts is None else force_shifts.get("__s2")
        out = packsort.pack_sort(cols["__s2"], 64, force_shift=fs)
        if out is None:
            return None
        perm, cq, _, shift = out
        return perm, {"__s2": cq}, {"__s2": shift}

    def plan(self, ft, f):
        geoms = ir.extract_geometries(f, self.geom)
        if geoms.disjoint:
            return KeyPlan(self, disjoint=True)
        if geoms.is_empty:
            return KeyPlan(self, full_scan=True)
        bs = np.asarray([g.bounds() for g in geoms.values])
        bbox = (bs[:, 0].min(), bs[:, 1].min(), bs[:, 2].max(), bs[:, 3].max())
        ranges = self.sfc.ranges(*bbox)
        span = sum(r.hi - r.lo + 1 for r in ranges)
        lo, hi = range_arrays(ranges, np.uint64)  # cell ids use all 64 bits
        return KeyPlan(self, lo=lo, hi=hi, coverage=span / float(6 << 60))

    def resolve_windows(self, plan, shard_cols, n):
        col = shard_cols["__s2"]
        sh = _shift_of(shard_cols, "__s2")
        starts, ends = [], []
        for r in plan.ranges:
            s = np.searchsorted(col, np.uint64(r.lo >> sh), side="left")
            e = np.searchsorted(col, np.uint64(r.hi >> sh), side="right")
            if e > s:
                starts.append(s)
                ends.append(e)
        if not starts:
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        return _cap_windows(
            np.asarray(starts, np.int64), np.asarray(ends, np.int64), MAX_WINDOW_BINS
        )


class S3KeySpace(KeySpace):
    """(time bin, S2 cell id) keys: the reference's S3Index (S2 space +
    BinnedTime period bins)."""

    name = "s3"
    kind = "s3"

    def __init__(self, geom: str, dtg: str, period: "str | TimePeriod" = TimePeriod.WEEK):
        self.geom = geom
        self.dtg = dtg
        from geomesa_tpu.curves.s2 import S2SFC

        self.sfc = S2SFC(max_cells=64)
        self.binned = BinnedTime(period)
        self.key_cols = ("__s3_bin", "__s3")

    def supports(self, ft):
        return (
            ft.has(self.geom) and ft.attr(self.geom).is_point
            and ft.has(self.dtg) and ft.attr(self.dtg).type == "date"
        )

    def index_keys(self, ft, batch):
        bin_col = self.dtg + "__bin"
        if bin_col in batch and ft.time_period == self.binned.period:
            b = batch[bin_col]
        else:
            b, _ = self.binned.to_bin_and_offset(batch[self.dtg])
        return {
            "__s3_bin": np.asarray(b, np.int32),
            "__s3": self.sfc.index(batch[self.geom + "__x"], batch[self.geom + "__y"]),
        }

    def sort_order(self, cols):
        return np.lexsort((cols["__s3"], cols["__s3_bin"]))

    def fast_build(self, cols, force_shifts=None):
        fs = None if force_shifts is None else force_shifts.get("__s3")
        out = packsort.pack_sort(
            cols["__s3"], 64, prefix=cols["__s3_bin"], force_shift=fs
        )
        if out is None:
            return None
        perm, cq, bins_sorted, shift = out
        return perm, {"__s3_bin": bins_sorted, "__s3": cq}, {"__s3": shift}

    def plan(self, ft, f):
        geoms = ir.extract_geometries(f, self.geom)
        intervals = ir.extract_intervals(f, self.dtg)
        if geoms.disjoint or intervals.disjoint:
            return KeyPlan(self, disjoint=True)
        if intervals.is_empty:
            return None
        CLAMP = 2**45
        iv = [(max(lo, -CLAMP), min(hi, CLAMP)) for lo, hi in intervals.values]
        bins = np.unique(
            np.concatenate([self.binned.bins_between(lo, hi) for lo, hi in iv])
        )
        if geoms.is_empty:
            return KeyPlan(self, bins=bins.astype(np.int32), coverage=1.0)
        bs = np.asarray([g.bounds() for g in geoms.values])
        bbox = (bs[:, 0].min(), bs[:, 1].min(), bs[:, 2].max(), bs[:, 3].max())
        ranges = self.sfc.ranges(*bbox)
        span = sum(r.hi - r.lo + 1 for r in ranges)
        cov = span / float(6 << 60)
        lo, hi = range_arrays(ranges, np.uint64)
        return KeyPlan(self, lo=lo, hi=hi, bins=bins.astype(np.int32),
                       coverage=cov)

    def resolve_windows(self, plan, shard_cols, n):
        bins_col = shard_cols["__s3_bin"]
        col = shard_cols["__s3"]
        sh = _shift_of(shard_cols, "__s3")
        bins = plan.bins
        if len(bins) > 8 or not len(plan.lo):
            s = np.searchsorted(bins_col, bins[0], side="left")
            e = np.searchsorted(bins_col, bins[-1], side="right")
            return np.asarray([s], np.int64), np.asarray([e], np.int64)
        starts, ends = [], []
        ranges = plan.ranges
        seg_lo, seg_hi = _bin_segments(bins_col, bins)
        for s, e in zip(seg_lo.tolist(), seg_hi.tolist()):
            if e <= s:
                continue
            seg = col[s:e]
            for r in ranges:
                s2_ = s + np.searchsorted(seg, np.uint64(r.lo >> sh), side="left")
                e2_ = s + np.searchsorted(seg, np.uint64(r.hi >> sh), side="right")
                if e2_ > s2_:
                    starts.append(s2_)
                    ends.append(e2_)
        if not starts:
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        return _cap_windows(
            np.asarray(starts, np.int64), np.asarray(ends, np.int64), MAX_WINDOW_BINS
        )


class IdKeySpace(KeySpace):
    """Feature-id index (reference IdIndex), hash-keyed: rows sort by a
    64-bit hash of the fid instead of the string bytes — string argsorts
    don't scale to bulk loads, and id lookups only need *locatable* rows:
    the window for hash(fid) is a superset (collisions included) and the
    IdIn mask applies exact fid equality on the window rows."""

    name = "id"
    kind = "id"
    key_cols = ("__idhash",)

    def supports(self, ft):
        return True

    def index_keys(self, ft, batch):
        return {"__idhash": packsort.fid_hash64(batch["__fid__"])}

    def sort_order(self, cols):
        return np.argsort(cols["__idhash"], kind="stable")

    def fast_build(self, cols, force_shifts=None):
        fs = None if force_shifts is None else force_shifts.get("__idhash")
        out = packsort.pack_sort(cols["__idhash"], 64, force_shift=fs)
        if out is None:
            return None
        perm, hq, _, shift = out
        return perm, {"__idhash": hq}, {"__idhash": shift}

    def plan(self, ft, f):
        ids = ir.extract_ids(f)
        if ids is None:
            return None
        plan = KeyPlan(self, coverage=0.0)
        plan._ids = sorted(ids)
        return plan

    def resolve_windows(self, plan, shard_cols, n):
        col = shard_cols["__idhash"]
        sh = _shift_of(shard_cols, "__idhash")
        starts, ends = [], []
        for fid in plan._ids:
            h = packsort.fid_hash64_one(fid) >> sh
            s = np.searchsorted(col, np.uint64(h), side="left")
            e = np.searchsorted(col, np.uint64(h), side="right")
            if e > s:
                starts.append(s)
                ends.append(e)
        if not starts:
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        return np.asarray(starts, np.int64), np.asarray(ends, np.int64)


class AttributeKeySpace(KeySpace):
    """Per-attribute sorted index (reference AttributeIndex + tiered keyspace;
    the z-curve tiebreak plays the reference's secondary-tier role)."""

    kind = "attr"

    #: attribute-type name -> numpy dtype of the stored column
    _NP_TYPES = {
        "int32": np.int32, "int64": np.int64, "float32": np.float32,
        "float64": np.float64, "date": np.int64, "bool": np.bool_,
    }

    def __init__(self, attr: str, geom: Optional[str] = None,
                 attr_type: Optional[str] = None):
        self.attr = attr
        self.geom = geom
        self.attr_type = attr_type
        self.name = f"attr:{attr}"
        self.key_cols = (f"__attr_{attr}",)

    @property
    def sort_col(self) -> str:
        return f"__attr_{self.attr}"

    def supports(self, ft):
        return ft.has(self.attr) and not ft.attr(self.attr).is_geom

    def index_keys(self, ft, batch):
        a = ft.attr(self.attr)
        vals = batch[self.attr]
        if a.type == "string":
            # codes are re-ranked to value order at table build (store step);
            # raw codes stored here, rank column computed on flush.
            return {self.sort_col: vals.astype(np.int64)}
        return {self.sort_col: vals}

    def sort_order(self, cols):
        if self.geom and "__z2" in cols:
            return np.lexsort((cols["__z2"], cols[self.sort_col]))
        return np.argsort(cols[self.sort_col], kind="stable")

    def fast_build(self, cols, force_shifts=None):
        col = cols[self.sort_col]
        if self.attr_type == "string":
            # rank column (small ints; -1 = null sorts first as 0)
            key = (col.astype(np.int64) + 1).astype(np.uint64)
            bits = packsort.bits_for(int(key.max()) + 1) if len(key) else 1
        else:
            try:
                key, bits = packsort.to_ordered_u64(col)
            except TypeError:
                return None
        tb, tb_bits = None, 0
        if self.geom and "__z2" in cols:
            tb = cols["__z2"].astype(np.uint64) << np.uint64(2)  # 62 bits -> top
            tb_bits = 16  # spatial-locality tiebreak, best-effort
        fs = None if force_shifts is None else force_shifts.get(self.sort_col)
        out = packsort.pack_sort(
            key, bits, tiebreak=tb, tiebreak_bits=tb_bits, force_shift=fs
        )
        if out is None:
            return None
        perm, kq, _, shift = out
        return perm, {self.sort_col: kq}, {self.sort_col: shift}

    # string attrs re-rank their dictionary on growth and the z2 tiebreak
    # is a second sort key: appends always fully rebuild
    can_insert = False

    def plan(self, ft, f):
        bounds = ir.extract_attr_bounds(f, self.attr)
        if bounds.disjoint:
            return KeyPlan(self, disjoint=True)
        if bounds.is_empty:
            return None
        plan = KeyPlan(self, coverage=0.1)  # refined by stats in the decider
        plan._bounds = bounds.values
        plan._ft = ft
        return plan

    def resolve_windows(self, plan, shard_cols, n):
        col = shard_cols[self.sort_col]
        a = plan._ft.attr(self.attr)
        shifts = shard_cols.get("__shifts__") or {}
        # fast-built tables store the ordered-u64 QUANTIZED key; bounds go
        # through the same transform (presence in shifts marks the path,
        # since shift can legitimately be 0)
        fastq = self.sort_col in shifts
        sh = shifts.get(self.sort_col, 0)
        np_type = self._NP_TYPES.get(a.type)
        starts, ends = [], []
        for lo, hi in plan._bounds:
            if a.type == "string":
                # bounds are raw strings; map through the rank dictionary
                # attached by the store at resolve time
                rank = shard_cols.get("__rank_lookup__")
                if rank is None:
                    return np.zeros(1, np.int64), np.full(1, n, np.int64)
                lo2 = rank(lo, "lo") if lo is not None else None
                hi2 = rank(hi, "hi") if hi is not None else None
                if fastq:
                    lo2 = None if lo2 is None else np.uint64((lo2 + 1) >> sh)
                    hi2 = None if hi2 is None else np.uint64((hi2 + 1) >> sh)
            elif fastq:
                lo2 = (
                    None if lo is None
                    else np.uint64(packsort.ordered_u64_scalar(lo, np_type) >> sh)
                )
                hi2 = (
                    None if hi is None
                    else np.uint64(packsort.ordered_u64_scalar(hi, np_type) >> sh)
                )
            else:
                lo2, hi2 = lo, hi
                if a.type == "date":
                    lo2 = None if lo is None else np.int64(lo)
                    hi2 = None if hi is None else np.int64(hi)
            s = 0 if lo2 is None else int(np.searchsorted(col, lo2, side="left"))
            e = n if hi2 is None else int(np.searchsorted(col, hi2, side="right"))
            if e > s:
                starts.append(s)
                ends.append(e)
        if not starts:
            return np.zeros(1, np.int64), np.zeros(1, np.int64)
        return _cap_windows(
            np.asarray(starts, np.int64), np.asarray(ends, np.int64), MAX_WINDOW_BINS
        )


def _cap_windows(starts: np.ndarray, ends: np.ndarray, cap: int):
    """Merge overlapping row windows; if more than ``cap`` remain, union the
    smallest gaps to fit (over-covering; fine filter restores exactness).
    Row windows are half-open, so only true overlap merges (adjacency 0)."""
    return _merge_cap(starts, ends, cap, adjacent=0)


def keyspaces_for_schema(ft: FeatureType) -> List[KeySpace]:
    """Pick indices from the schema shape (GeoMesaFeatureIndexFactory.indices
    analog, reference GeoMesaDataStore.preSchemaCreate:116). The
    ``geomesa.indices`` user-data key overrides the defaults with an explicit
    comma-separated list of index kinds (z3,z2,xz3,xz2,s2,s3,id,attr)."""
    geom = ft.geom_field
    dtg = ft.dtg_field
    period = ft.time_period

    explicit = ft.user_data.get("geomesa.indices")
    if explicit:
        wanted = [k.strip().lower() for k in explicit.split(",") if k.strip()]
    else:
        wanted = []
        if geom is not None:
            if ft.attr(geom).is_point:
                if dtg is not None:
                    wanted.append("z3")
                wanted.append("z2")
            else:
                if dtg is not None:
                    wanted.append("xz3")
                wanted.append("xz2")
        wanted += ["id", "attr"]

    out: List[KeySpace] = []
    for kind in wanted:
        if kind == "z3" and geom and dtg:
            out.append(Z3KeySpace(geom, dtg, period))
        elif kind == "z2" and geom:
            out.append(Z2KeySpace(geom))
        elif kind == "xz3" and geom and dtg:
            out.append(XZ3KeySpace(geom, dtg, period))
        elif kind == "xz2" and geom:
            out.append(XZ2KeySpace(geom))
        elif kind == "s2" and geom:
            out.append(S2KeySpace(geom))
        elif kind == "s3" and geom and dtg:
            out.append(S3KeySpace(geom, dtg, period))
        elif kind == "id":
            out.append(IdKeySpace())
        elif kind == "attr":
            for a in ft.attributes:
                if a.indexed and not a.is_geom and a.type != "json":
                    out.append(AttributeKeySpace(a.name, geom, a.type))
    if not any(isinstance(k, IdKeySpace) for k in out):
        out.append(IdKeySpace())
    return [k for k in out if k.supports(ft)]
