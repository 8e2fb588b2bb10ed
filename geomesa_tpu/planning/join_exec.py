"""SFC co-partitioned spatial-join executor (docs/JOIN.md).

The device analog of the reference's grid-partitioned Spark join
(GeoMesaJoinRelation + RelationUtils.gridPartition) in the shape "Adaptive
Geospatial Joins for Modern Hardware" (PAPERS.md) shows wins on throughput
hardware: a cheap grid filter prunes candidate pairs, then an exact test
runs on the survivors. Both join sides co-partition by SFC cell — the same
2^level x 2^level lon/lat grid the aggregate cache decomposes to
(cache/cells.py; a cell's identity is its z2 prefix via ``interleave2``) —
so only same-cell (plus boundary-strip) pairs ever reach the device:
candidate work is O(pairs-in-same-cell), never O(N*M).

Build/probe contract:

* the **build** (left) side lands in exactly one cell — the one containing
  its point;
* the **probe** (right) side replicates into every cell its predicate
  reach box ``point ± (reach + margin)`` touches (the *boundary strip*;
  the margin is ``cache.cells.CLASSIFY_MARGIN``, the same f32-safety
  machinery ``classify_cells`` uses, so an f32-rounded pair that passes
  the exact predicate can never hide in an unprobed neighbor cell);
* a candidate pair is tested iff the build row's cell is among the probe
  row's covered cells — each surviving pair is tested exactly ONCE,
  because the build cell is unique. No dedup pass exists or is needed.

Adaptive strategy selection (docs/JOIN.md §5): after co-partitioning, each
joint cell routes to the cheapest executor from its own (n_left, n_right)
statistics — the shape "Adaptive Geospatial Joins for Modern Hardware"
picks per-cell:

* **pairwise** — dense, balanced cells chunk into tiles for the bucketed
  [Cp, Bp, Pp] pairwise kernel (the only strategy when
  ``geomesa.join.adaptive`` is off);
* **brute** — sparse cells (``n_left * n_right`` at most
  ``geomesa.join.adaptive.brute.pairs``) gather into ONE flat 1-D
  candidate-pair list and skip tile padding entirely;
* **split.l / split.r** — skewed cells (one side ≫ the other) land in an
  orientation-specific section whose short-axis padding buckets
  independently, so a 3 x 500 cell pads to (4, tile) instead of the dense
  section's (Bp, Pp).

Strategy routing only decides WHICH executor tests a candidate pair —
every executor runs the SAME ``kernels.join.pair_mask`` f32 arithmetic and
the merged pair set surfaces in canonical row-major order, so the adaptive
join is bit-identical to the single-strategy path and to the numpy N*M
reference by construction (CI-gated).

Device execution: per-cell blocks chunk into **tiles** of at most
``geomesa.join.tile`` rows per side, both tile axes pow2-bucketed and the
tile count bucketed per dispatch, so the bucketed pairwise kernel's
registry key — ``(site, Bp, Pp, Cp, predicate)``, predicate *parameters*
ride as traced f32 scalars — is version-stable: repeated joins over fresh
data of similar size NEVER recompile (CI-gated recompiles==0). The
strategy lives in the key's ``site`` ("join.pairs" / "join.pairs.split" /
"join.brute" / "join.poly"), never in traced data, so strategy mixes
cannot recompile each other.

Sharded fan-out: each section's tile axis splits into one contiguous
slice per usable device (``parallel.devices.scan_devices``); counts merge
via the documented :func:`~geomesa_tpu.parallel.devices.tree_merge` order
and pair blocks concatenate in slice order before the canonical row-major
sort, so the sharded join is bit-identical to the single-device (and
numpy brute-force) result by construction. Per-slice failures degrade
under ``resilience.allow_partial()`` with exact survivor totals (the
skipped tile ranges are recorded; completed tiles' pairs/counts are
exact).

Polygon-dataset joins (docs/JOIN.md §7): :func:`run_polygon_join` joins a
point side against a POLYGON dataset side by classifying each occupied
point cell against each candidate polygon row with
``kernels.join.classify_cells`` + ``CLASSIFY_MARGIN`` — interior cells
match wholesale with ZERO pairwise work, outside cells are skipped, and
only boundary cells pay the polygon kernel.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from geomesa_tpu import config, metrics, tracing, utilization
from geomesa_tpu.cache.cells import CLASSIFY_MARGIN
from geomesa_tpu.kernels import join as kjoin
from geomesa_tpu.kernels.registry import KernelRegistry
from geomesa_tpu.resilience import check_deadline, partial_allowed, record_skip

#: one process-wide registry for join kernels: the pairwise kernel is pure
#: in (shapes, predicate kind) — no store, no dictionary — so it is
#: version-stable trivially and shared across every dataset in the process
_REGISTRY: Optional[KernelRegistry] = None
_REGISTRY_LOCK = threading.Lock()

#: fixed section order — part of the bit-identity contract: sections
#: execute in this order, pairs concatenate in section/slice order, and
#: the canonical row-major sort at the end makes the surfaced set
#: independent of the routing anyway
SECTION_ORDER = ("pairwise", "split.l", "split.r")


def join_registry() -> KernelRegistry:
    """The process-wide join-kernel registry (recompile accounting for the
    bench/CI ``join_recompiles`` gate reads ``.traces('join.pairs')``)."""
    global _REGISTRY
    with _REGISTRY_LOCK:
        if _REGISTRY is None:
            _REGISTRY = KernelRegistry()
        return _REGISTRY


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _tile() -> int:
    t = config.JOIN_TILE.to_int()
    return 64 if t is None else max(int(t), 8)


def _brute_max() -> int:
    v = config.JOIN_ADAPTIVE_BRUTE_PAIRS.to_int()
    return 256 if v is None else max(int(v), 0)


def _skew_ratio() -> int:
    v = config.JOIN_ADAPTIVE_SKEW_RATIO.to_int()
    return 8 if v is None else max(int(v), 2)


@dataclass
class JoinStats:
    """The explain/audit account of one co-partitioned join (docs/JOIN.md):
    how much the grid filter pruned vs the naive N*M, and which strategy
    each joint cell routed to."""

    level: int = 0
    n_left: int = 0
    n_right: int = 0
    cells_left: int = 0
    cells_right: int = 0
    #: cells populated on BOTH sides (only these dispatch)
    cells_joint: int = 0
    #: exact pairwise tests dispatched (same-cell + strip candidates)
    candidate_pairs: int = 0
    #: probe rows replicated beyond their home cell (the boundary strip)
    strip_entries: int = 0
    tiles: int = 0
    matched: int = 0
    devices: int = 1
    #: tile ranges skipped under allow_partial (exact survivor totals)
    skipped: List[str] = field(default_factory=list)
    #: whether per-cell strategy selection ran (vs the single-strategy A/B)
    adaptive: bool = False
    #: adaptive decision trail: joint cells per strategy (pairwise / brute
    #: / split.l / split.r; polygon joins: interior / boundary incidences)
    strategy_cells: Dict[str, int] = field(default_factory=dict)
    #: candidate pairs per strategy as estimated at classification time
    #: (the statistic each routing decision read)
    est_pairs: Dict[str, int] = field(default_factory=dict)
    #: pair slots actually dispatched per strategy AFTER padding — the
    #: estimated-vs-actual gap is exactly the padding the routing saved
    dispatched_pairs: Dict[str, int] = field(default_factory=dict)
    #: polygon-join pairs matched wholesale from INTERIOR cells — zero
    #: pairwise kernel work, by the CLASSIFY_MARGIN contract
    wholesale_pairs: int = 0
    #: lake window-pushdown side-scan account (api.dataset join pushdown):
    #: groups/bytes loaded vs skipped by per-cell footer pruning
    pushdown: Dict[str, int] = field(default_factory=dict)

    @property
    def naive_pairs(self) -> int:
        return self.n_left * self.n_right

    @property
    def candidate_fraction(self) -> float:
        return self.candidate_pairs / max(self.naive_pairs, 1)

    @property
    def strip_fraction(self) -> float:
        """Fraction of probe-side cell memberships that are strip
        replicas (0 = every probe row stayed in its home cell)."""
        total = self.n_right + self.strip_entries
        return self.strip_entries / max(total, 1)


def choose_level(n_left: int, n_right: int, reach: float,
                 bounds: Optional[Tuple[float, float, float, float]]) -> int:
    """Adaptive co-partition level: fine enough that the denser side
    averages ~tile rows per occupied cell over its extent, coarse enough
    that a probe reach box spans at most 2 cells per axis (cell span >=
    2 * reach keeps the boundary strip at most one neighbor ring)."""
    tile = _tile()
    max_level = config.JOIN_MAX_LEVEL.to_int() or 12
    if bounds is None:
        span = 360.0
    else:
        span = max(bounds[2] - bounds[0], (bounds[3] - bounds[1]) * 2, 1e-6)
    target_axis = float(np.sqrt(max(n_left, n_right, 1) / tile))
    target_axis = min(max(target_axis, 1.0), 1024.0)
    want_span = max(span / target_axis, 1e-9)
    level_data = int(np.ceil(np.log2(360.0 / want_span)))
    reach = max(float(reach), 0.0) + CLASSIFY_MARGIN
    level_reach = int(np.floor(np.log2(360.0 / max(2.0 * reach, 1e-9))))
    return int(np.clip(min(level_data, level_reach), 1, max_level))


def _cell_ids(ix: np.ndarray, iy: np.ndarray) -> np.ndarray:
    """Absolute cell identity: the z2 curve prefix (interleave2), the same
    identity the aggregate cache keys cells by (cache/cells.cell_prefix)."""
    from geomesa_tpu.curves.zorder import interleave2

    return interleave2(ix.astype(np.uint64), iy.astype(np.uint64))


@dataclass
class TileSection:
    """One strategy's padded tile blocks: [C, Bp] / [C, Pp] global row
    positions (0-padded; valid counts mask), pow2-bucketed independently
    of every other section — the skew win is exactly that a split
    section's short axis pads to ITS OWN maximum, not the dense
    section's."""

    strategy: str  # "pairwise" | "split.l" | "split.r"
    site: str  # kernel registry site ("join.pairs" / "join.pairs.split")
    l_rows: np.ndarray
    r_rows: np.ndarray
    l_valid: np.ndarray  # [C] int32
    r_valid: np.ndarray  # [C] int32
    Bp: int
    Pp: int

    @property
    def n_tiles(self) -> int:
        return len(self.l_rows)


@dataclass
class JoinPlan:
    """Host-side co-partition product: per-strategy tile sections ready
    for the bucketed pairwise kernel, plus the flat brute-force candidate
    list for sparse cells. All index arrays are int32 positions into the
    caller's left/right row sets."""

    predicate: str
    p0: np.float32
    p1: np.float32
    stats: JoinStats
    sections: List[TileSection] = field(default_factory=list)
    #: flat sparse-cell candidate pairs (global row positions, aligned)
    brute_l: Optional[np.ndarray] = None
    brute_r: Optional[np.ndarray] = None

    @property
    def n_tiles(self) -> int:
        return sum(s.n_tiles for s in self.sections)

    @property
    def n_brute(self) -> int:
        return 0 if self.brute_l is None else len(self.brute_l)

    @property
    def Bp(self) -> int:
        return max((s.Bp for s in self.sections), default=0)

    @property
    def Pp(self) -> int:
        return max((s.Pp for s in self.sections), default=0)


def co_partition(lx, ly, rx, ry, predicate: str, reach_x,
                 reach_y: float, level: Optional[int] = None,
                 p0=None, p1=None, wrap_x: bool = False,
                 adaptive: Optional[bool] = None) -> JoinPlan:
    """Group both sides by SFC cell at ``level`` (adaptive when None),
    classify each joint cell's strategy from its (n_left, n_right), and
    chunk into per-strategy padded tile sections plus the flat brute
    list. Pure host numpy — the grouping is two argsorts plus a bounded
    neighbor expansion.

    ``adaptive`` None reads ``geomesa.join.adaptive``; False forces every
    joint cell through the single "pairwise" section — exactly the
    pre-adaptive plan, the A/B baseline the CI speedup gate compares
    against.

    ``reach_x`` may be a per-probe-row array (``dwithin_meters``: the lon
    reach needed for ``d`` meters grows with |latitude|). ``wrap_x``
    wraps the probe reach box across the antimeridian (modular lon
    cells) — a great-circle predicate matches across lon ±180, so its
    strip must too; the planar predicates keep the clipped grid."""
    lx = np.asarray(lx, np.float64)
    ly = np.asarray(ly, np.float64)
    rx = np.asarray(rx, np.float64)
    ry = np.asarray(ry, np.float64)
    # level choice uses the TYPICAL reach (per-row reach_x arrays rank by
    # their minimum — high-latitude rows widen their own windows instead
    # of coarsening every cell)
    rx_typ = (float(np.min(reach_x)) if np.ndim(reach_x) and len(reach_x)
              else float(reach_x) if not np.ndim(reach_x) else 0.0)
    reach = max(rx_typ, float(reach_y))
    if level is None:
        n_l, n_r = len(lx), len(rx)
        bounds = None
        if n_l and n_r:
            bounds = (
                min(lx.min(), rx.min()), min(ly.min(), ry.min()),
                max(lx.max(), rx.max()), max(ly.max(), ry.max()),
            )
        level = choose_level(n_l, n_r, reach, bounds)
    if adaptive is None:
        adaptive = config.JOIN_ADAPTIVE.to_bool()
        adaptive = True if adaptive is None else bool(adaptive)
    stats = JoinStats(level=level, n_left=len(lx), n_right=len(rx),
                      adaptive=bool(adaptive))
    plan = JoinPlan(predicate=predicate, p0=p0, p1=p1, stats=stats)
    if not len(lx) or not len(rx):
        return plan
    n = 1 << level
    sx, sy = 360.0 / n, 180.0 / n

    def cell_of(x, y):
        ix = np.clip(np.floor((x + 180.0) / sx), 0, n - 1).astype(np.int64)
        iy = np.clip(np.floor((y + 90.0) / sy), 0, n - 1).astype(np.int64)
        return ix, iy

    lix, liy = cell_of(lx, ly)
    lcell = _cell_ids(lix, liy)
    stats.cells_left = len(np.unique(lcell))

    # probe reach box, inflated by the classify margin (module docstring):
    # every cell the box touches gets a membership
    mx = np.asarray(reach_x, np.float64) + CLASSIFY_MARGIN
    my = float(reach_y) + CLASSIFY_MARGIN
    if wrap_x:
        # modular lon: the window spans [ix0, ix1] mod n, capped at one
        # full wrap (a reach past 180° of longitude covers every column)
        ix0 = np.floor((rx - mx + 180.0) / sx).astype(np.int64)
        ix1 = np.floor((rx + mx + 180.0) / sx).astype(np.int64)
        wx = np.minimum(ix1 - ix0 + 1, n).astype(np.int64)
    else:
        ix0 = np.clip(np.floor((rx - mx + 180.0) / sx), 0, n - 1).astype(np.int64)
        ix1 = np.clip(np.floor((rx + mx + 180.0) / sx), 0, n - 1).astype(np.int64)
        wx = (ix1 - ix0 + 1).astype(np.int64)
    iy0 = np.clip(np.floor((ry - my + 90.0) / sy), 0, n - 1).astype(np.int64)
    iy1 = np.clip(np.floor((ry + my + 90.0) / sy), 0, n - 1).astype(np.int64)
    wy = (iy1 - iy0 + 1).astype(np.int64)
    w = wx * wy
    rid = np.repeat(np.arange(len(rx), dtype=np.int64), w)
    # per-membership (dx, dy) offsets within each row's window, row-major
    off = np.arange(int(w.sum()), dtype=np.int64) - np.repeat(
        np.cumsum(w) - w, w
    )
    gx = ix0[rid] + off % wx[rid]
    if wrap_x:
        gx %= n  # python modulo: non-negative for ix0 < 0
    gy = iy0[rid] + off // wx[rid]
    rcell = _cell_ids(gx, gy)
    rhome = _cell_ids(*cell_of(rx, ry))
    stats.cells_right = len(np.unique(rhome))

    # keep only memberships whose cell holds build rows (the joint cells)
    ucell, linv = np.unique(lcell, return_inverse=True)
    pos = np.searchsorted(ucell, rcell)
    pos_c = np.minimum(pos, len(ucell) - 1)
    keep = ucell[pos_c] == rcell
    rid, rcell_k, pos_c = rid[keep], rcell[keep], pos_c[keep]
    stats.strip_entries = int((rhome[rid] != rcell_k).sum())
    if not len(rid):
        return plan

    # group both sides by joint-cell index (stable order: row order within
    # a cell, cells in ucell order — deterministic for any input)
    lorder = np.argsort(linv, kind="stable")
    lsorted = lorder.astype(np.int32)
    lcounts = np.bincount(linv, minlength=len(ucell))
    rorder = np.argsort(pos_c, kind="stable")
    rsorted = rid[rorder].astype(np.int32)
    rcounts = np.bincount(pos_c, minlength=len(ucell))
    joint = (lcounts > 0) & (rcounts > 0)
    stats.cells_joint = int(joint.sum())
    stats.candidate_pairs = int(
        (lcounts[joint].astype(np.int64) * rcounts[joint]).sum()
    )
    lstart = np.concatenate(([0], np.cumsum(lcounts)))
    rstart = np.concatenate(([0], np.cumsum(rcounts)))

    # per-cell strategy classification (module docstring): sparse cells
    # gather flat, skewed cells bucket in their own orientation section so
    # the short axis pads narrow, dense balanced cells tile as before.
    # Adaptive-mode tile shapes are STATIC per strategy — (Tp, Tp),
    # (Tp, SPLIT_SHORT), (SPLIT_SHORT, Tp) — never derived from data
    # maxima, so fresh data of any distribution re-lands on the warmed
    # kernels (the recompiles==0 contract holds across strategy mixes);
    # single-strategy mode keeps the legacy exact-maxima padding — it IS
    # the A/B baseline and must stay byte-for-byte the old plan
    T = _tile()
    Tp = _pow2(T)
    brute_max = _brute_max() if adaptive else 0
    skew = _skew_ratio()
    # fixed short-axis chunk for split sections: skewed cells chunk their
    # SHORT side at this step too, so the section pads to exactly
    # (Tp, SPLIT_SHORT) — ~Tp/SPLIT_SHORT x less padded work than the
    # dense section would spend on the same cell
    split_short = min(8, Tp)
    bl_list: List[np.ndarray] = []
    br_list: List[np.ndarray] = []
    # strategy -> [tl_rows, tr_rows, tl_valid, tr_valid, max_b, max_p]
    buckets: Dict[str, list] = {}
    for c in np.nonzero(joint)[0]:
        lrows = lsorted[lstart[c]: lstart[c + 1]]
        rrows = rsorted[rstart[c]: rstart[c + 1]]
        nl, nr = len(lrows), len(rrows)
        if adaptive and nl * nr <= brute_max:
            strat = "brute"
            # flat candidate list, left-major (matches the reference's
            # row-major nonzero order; the global sort re-establishes it
            # across strategies anyway)
            bl_list.append(np.repeat(lrows, nr))
            br_list.append(np.tile(rrows, nl))
        elif adaptive and max(nl, nr) >= skew * max(min(nl, nr), 1) \
                and max(nl, nr) > T:
            strat = "split.l" if nl >= nr else "split.r"
        else:
            strat = "pairwise"
        stats.strategy_cells[strat] = stats.strategy_cells.get(strat, 0) + 1
        stats.est_pairs[strat] = stats.est_pairs.get(strat, 0) + nl * nr
        if strat == "brute":
            continue
        if strat == "split.l":
            tb, tp = T, split_short
        elif strat == "split.r":
            tb, tp = split_short, T
        else:
            tb = tp = T
        bucket = buckets.setdefault(strat, [[], [], [], [], 1, 1])
        tl_rows, tr_rows, tl_valid, tr_valid = bucket[0], bucket[1], \
            bucket[2], bucket[3]
        for bl in range(0, nl, tb):
            lchunk = lrows[bl: bl + tb]
            for pl in range(0, nr, tp):
                rchunk = rrows[pl: pl + tp]
                tl_rows.append(lchunk)
                tr_rows.append(rchunk)
                tl_valid.append(len(lchunk))
                tr_valid.append(len(rchunk))
                bucket[4] = max(bucket[4], len(lchunk))
                bucket[5] = max(bucket[5], len(rchunk))
    for strat in SECTION_ORDER:
        if strat not in buckets:
            continue
        tl_rows, tr_rows, tl_valid, tr_valid, max_b, max_p = buckets[strat]
        C = len(tl_rows)
        if not adaptive:
            Bp, Pp = _pow2(max_b), _pow2(max_p)  # legacy exact padding
        elif strat == "split.l":
            Bp, Pp = Tp, split_short
        elif strat == "split.r":
            Bp, Pp = split_short, Tp
        else:
            Bp = Pp = Tp
        l_rows = np.zeros((C, Bp), np.int32)
        r_rows = np.zeros((C, Pp), np.int32)
        for i in range(C):
            l_rows[i, : tl_valid[i]] = tl_rows[i]
            r_rows[i, : tr_valid[i]] = tr_rows[i]
        site = "join.pairs" if strat == "pairwise" else "join.pairs.split"
        plan.sections.append(TileSection(
            strategy=strat, site=site, l_rows=l_rows, r_rows=r_rows,
            l_valid=np.asarray(tl_valid, np.int32),
            r_valid=np.asarray(tr_valid, np.int32), Bp=Bp, Pp=Pp,
        ))
        stats.tiles += C
        stats.dispatched_pairs[strat] = C * Bp * Pp
    if bl_list:
        plan.brute_l = np.concatenate(bl_list)
        plan.brute_r = np.concatenate(br_list)
        stats.dispatched_pairs["brute"] = len(plan.brute_l)
    return plan


# ---------------------------------------------------------------------------
# Bucketed pairwise kernels (the version-stable registry half)
# ---------------------------------------------------------------------------

def _pairs_kernel(site: str, Bp: int, Pp: int, Cp: int, predicate: str):
    """Registry-cached jitted kernel: [Cp, Bp, Pp] bool verdict mask plus
    [Cp] int32 per-tile match counts. Predicate parameters are traced f32
    scalars (kernel data), so distances never recompile. ``site`` is the
    strategy's registry site ("join.pairs" / "join.pairs.split") — the
    strategy lives in the KEY, so mixing strategies never recompiles."""
    reg = join_registry()
    key = (site, Bp, Pp, Cp, predicate)
    go = reg.get(key)
    if go is not None:
        return go
    import jax
    import jax.numpy as jnp

    def _mask(m, lvalid, rvalid):
        iota_b = jnp.arange(Bp, dtype=jnp.int32)[None, :, None]
        iota_p = jnp.arange(Pp, dtype=jnp.int32)[None, None, :]
        m = m & (iota_b < lvalid[:, None, None]) \
              & (iota_p < rvalid[:, None, None])
        return m, m.sum(axis=(1, 2), dtype=jnp.int32)

    if predicate == kjoin.JOIN_DWITHIN_METERS:
        # unit-vector operands: three coordinate planes per side
        @jax.jit
        def go(lxb, lyb, lzb, rxb, ryb, rzb, lvalid, rvalid, p0, p1):
            m = kjoin.pair_mask(
                lxb[:, :, None], lyb[:, :, None],
                rxb[:, None, :], ryb[:, None, :],
                predicate, p0, p1, jnp,
                lz=lzb[:, :, None], rz=rzb[:, None, :],
            )
            return _mask(m, lvalid, rvalid)
    else:
        @jax.jit
        def go(lxb, lyb, rxb, ryb, lvalid, rvalid, p0, p1):
            m = kjoin.pair_mask(
                lxb[:, :, None], lyb[:, :, None],
                rxb[:, None, :], ryb[:, None, :],
                predicate, p0, p1, jnp,
            )
            return _mask(m, lvalid, rvalid)

    reg.put(key, go)
    return go


def _brute_kernel(Kp: int, predicate: str):
    """Registry-cached jitted kernel for the flat sparse-cell strategy:
    1-D [Kp] gathered candidate pairs, bool verdict + int32 match count.
    Same ``pair_mask`` f32 arithmetic as the tiled kernel — elementwise
    instead of broadcast, so each tested pair decides identically."""
    reg = join_registry()
    key = ("join.brute", Kp, predicate)
    go = reg.get(key)
    if go is not None:
        return go
    import jax
    import jax.numpy as jnp

    def _mask(m, kvalid):
        m = m & (jnp.arange(Kp, dtype=jnp.int32) < kvalid)
        return m, m.sum(dtype=jnp.int32)

    if predicate == kjoin.JOIN_DWITHIN_METERS:
        @jax.jit
        def go(lxv, lyv, lzv, rxv, ryv, rzv, kvalid, p0, p1):
            m = kjoin.pair_mask(lxv, lyv, rxv, ryv, predicate, p0, p1,
                                jnp, lz=lzv, rz=rzv)
            return _mask(m, kvalid)
    else:
        @jax.jit
        def go(lxv, lyv, rxv, ryv, kvalid, p0, p1):
            m = kjoin.pair_mask(lxv, lyv, rxv, ryv, predicate, p0, p1, jnp)
            return _mask(m, kvalid)

    reg.put(key, go)
    return go


def _devices(prefer_device: bool):
    """Devices for the join tile fan-out (same stand-down rules as the
    sharded partitioned scan), or None for the single default device."""
    if not prefer_device:
        return None
    from geomesa_tpu.parallel import devices as pdev

    return pdev.scan_devices()


def _pad_tiles(sec: TileSection, lo: int, hi: int, lx32, ly32, rx32, ry32,
               lz32=None, rz32=None):
    """One device slice's padded kernel operands: tile rows [Cp, Bp/Pp]
    gathered into coordinate blocks, Cp = pow2 bucket of the slice.
    ``lz32``/``rz32`` (dwithin_meters unit vectors) gather to z blocks."""
    C = hi - lo
    Cp = _pow2(C)
    lrows = np.zeros((Cp, sec.Bp), np.int32)
    rrows = np.zeros((Cp, sec.Pp), np.int32)
    lval = np.zeros(Cp, np.int32)
    rval = np.zeros(Cp, np.int32)
    lrows[:C] = sec.l_rows[lo:hi]
    rrows[:C] = sec.r_rows[lo:hi]
    lval[:C] = sec.l_valid[lo:hi]
    rval[:C] = sec.r_valid[lo:hi]
    lzb = None if lz32 is None else lz32[lrows]
    rzb = None if rz32 is None else rz32[rrows]
    return (lx32[lrows], ly32[lrows], rx32[rrows], ry32[rrows],
            lval, rval, Cp, C, lzb, rzb)


def _slices(n: int, n_dev: int) -> List[Tuple[int, int]]:
    edges = np.linspace(0, n, n_dev + 1).astype(int)
    return [(int(a), int(b)) for a, b in zip(edges[:-1], edges[1:])
            if b > a]


def execute(plan: JoinPlan, lx, ly, rx, ry, prefer_device: bool = True,
            want_pairs: bool = True, lz=None, rz=None):
    """Run every strategy section (and the flat brute list) over the
    device mesh. Returns ``(pairs, total)``: matched global (left, right)
    row positions as int64 [K, 2] sorted row-major (None when
    ``want_pairs`` is False) and the exact match total over completed
    work. Per-slice failures degrade under ``resilience.allow_partial()``
    (recorded in ``plan.stats.skipped``); totals stay exact over
    survivors. For ``dwithin_meters``, the coordinate operands are the
    sides' precomputed f32 unit vectors ((lx, ly, lz) / (rx, ry, rz) —
    kernels.join.unit_vectors)."""
    stats = plan.stats
    if plan.n_tiles == 0 and plan.n_brute == 0:
        return (np.zeros((0, 2), np.int64) if want_pairs else None), 0
    lx32 = np.asarray(lx, np.float32)
    ly32 = np.asarray(ly, np.float32)
    rx32 = np.asarray(rx, np.float32)
    ry32 = np.asarray(ry, np.float32)
    lz32 = None if lz is None else np.asarray(lz, np.float32)
    rz32 = None if rz is None else np.asarray(rz, np.float32)
    use_device = prefer_device and _jax_ok()
    devs = _devices(prefer_device) if use_device else None
    n_dev = len(devs) if devs else 1
    stats.devices = n_dev
    from geomesa_tpu.resilience import QueryTimeoutError

    # contiguous tile slices per section, one per device (bit-identity:
    # pairs concat in section/slice order, then the canonical sort; counts
    # tree-merge in the same order)
    import functools

    jobs = []
    di = 0
    # fan each section out proportionally to its tile share: a full
    # n_dev split of every section multiplies launch count by the
    # number of strategies, and per-launch overhead — not slot math —
    # is what the sparse/skewed strategies are saving. Single-section
    # plans (adaptive off) keep the exact n_dev split.
    total_tiles = sum(s.n_tiles for s in plan.sections)
    for sec in plan.sections:
        fan = max(1, round(n_dev * sec.n_tiles / total_tiles)) \
            if total_tiles else 1
        for lo, hi in _slices(sec.n_tiles, fan):
            dev = devs[di % len(devs)] if devs else None
            di += 1
            jobs.append((f"tiles[{lo}:{hi}]", functools.partial(
                _run_slice, plan, lo, hi, lx32, ly32, rx32, ry32,
                use_device, dev, want_pairs, lz32=lz32, rz32=rz32,
                sec=sec)))
    if plan.n_brute:
        # fixed-size brute chunks: every dispatch (including the final
        # partial one) pads to the SAME pow2 length — four dense tiles'
        # worth of slots — so the registry holds exactly one
        # ("join.brute", Kp, predicate) entry no matter how many sparse
        # pairs fresh data produces (the recompiles==0 contract). The
        # chunk is sized so launch overhead, not padding, sets the cost:
        # a 16k-slot flat kernel is still far cheaper than one tile.
        bchunk = 4 * _pow2(_tile()) ** 2
        for lo in range(0, plan.n_brute, bchunk):
            hi = min(lo + bchunk, plan.n_brute)
            dev = devs[di % len(devs)] if devs else None
            di += 1
            jobs.append((f"brute[{lo}:{hi}]", functools.partial(
                _run_brute_slice, plan, lo, hi, lx32, ly32, rx32, ry32,
                use_device, dev, want_pairs, lz32=lz32, rz32=rz32,
                Kp=bchunk)))
    # multi-device: overlap the per-slice dispatch+fetch across worker
    # threads (each slice blocks on its own device; serializing them
    # leaves n_dev-1 devices idle per launch). Deadline checks and
    # partial-degradation accounting stay on THIS thread — both are
    # thread-local scopes — by collecting results in submission order,
    # which is also what keeps pairs/count merge order deterministic.
    partials = []
    if use_device and n_dev > 1 and len(jobs) > 1:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=n_dev,
                                thread_name_prefix="geomesa-join") as pool:
            futs = [(label, pool.submit(fn)) for label, fn in jobs]
            for label, fut in futs:
                try:
                    check_deadline()
                    partials.append(fut.result())
                except BaseException as e:
                    if isinstance(e, QueryTimeoutError) \
                            or not partial_allowed():
                        raise
                    record_skip("join", label, e, phase="pairs")
                    stats.skipped.append(label)
                    partials.append(None)
    else:
        for label, fn in jobs:
            try:
                check_deadline()
                partials.append(fn())
            except BaseException as e:
                if isinstance(e, QueryTimeoutError) or not partial_allowed():
                    raise
                record_skip("join", label, e, phase="pairs")
                stats.skipped.append(label)
                partials.append(None)
    from geomesa_tpu.parallel.devices import tree_merge

    total = tree_merge(
        [None if p is None else p[1] for p in partials],
        lambda a, b: a + b,
    )
    total = int(total or 0)
    stats.matched = total
    if not want_pairs:
        return None, total
    blocks = [p[0] for p in partials if p is not None and len(p[0])]
    if not blocks:
        return np.zeros((0, 2), np.int64), total
    pairs = np.concatenate(blocks, axis=0)
    # canonical row-major order == the brute-force reference's nonzero
    # order: the bit-identity contract is on the SET, surfaced sorted —
    # this is also what makes the adaptive routing invisible in results
    order = np.lexsort((pairs[:, 1], pairs[:, 0]))
    return pairs[order], total


def _run_slice(plan: JoinPlan, lo: int, hi: int, lx32, ly32, rx32, ry32,
               use_device: bool, dev, want_pairs: bool,
               lz32=None, rz32=None, sec: Optional[TileSection] = None):
    """One tile slice: (pairs int64 [k, 2] in tile order, match count)."""
    if sec is None:
        sec = plan.sections[0]
    (lxb, lyb, rxb, ryb, lval, rval, Cp, C, lzb, rzb) = _pad_tiles(
        sec, lo, hi, lx32, ly32, rx32, ry32, lz32, rz32
    )
    if use_device:
        import jax

        go = _pairs_kernel(sec.site, sec.Bp, sec.Pp, Cp, plan.predicate)
        if plan.predicate == kjoin.JOIN_DWITHIN_METERS:
            ops = (lxb, lyb, lzb, rxb, ryb, rzb, lval, rval,
                   np.float32(plan.p0), np.float32(plan.p1))
        else:
            ops = (lxb, lyb, rxb, ryb, lval, rval,
                   np.float32(plan.p0), np.float32(plan.p1))
        if dev is not None:
            ops = tuple(jax.device_put(o, dev) for o in ops)
        with tracing.span("scan.join.pairs", tiles=C, device=getattr(
                dev, "id", None)):
            metrics.inc(metrics.EXEC_DEVICE_DISPATCH)
            utilization.dispatched(getattr(dev, "id", 0) or 0)
            m, counts = go(*ops)
        m = np.asarray(m)
        counts = np.asarray(counts)
        utilization.settle()
    else:
        m = kjoin.pair_mask(
            lxb[:, :, None], lyb[:, :, None],
            rxb[:, None, :], ryb[:, None, :],
            plan.predicate, plan.p0, plan.p1, np,
            lz=None if lzb is None else lzb[:, :, None],
            rz=None if rzb is None else rzb[:, None, :],
        )
        iota_b = np.arange(sec.Bp, dtype=np.int32)[None, :, None]
        iota_p = np.arange(sec.Pp, dtype=np.int32)[None, None, :]
        m = m & (iota_b < lval[:, None, None]) & (iota_p < rval[:, None, None])
        counts = m.sum(axis=(1, 2), dtype=np.int32)
    n = int(counts[:C].sum())
    if not want_pairs:
        return np.zeros((0, 2), np.int64), n
    c, b, p = np.nonzero(m[:C])
    lrows = sec.l_rows[lo:hi]
    rrows = sec.r_rows[lo:hi]
    pairs = np.stack([
        lrows[c, b].astype(np.int64), rrows[c, p].astype(np.int64)
    ], axis=1)
    return pairs, n


def _run_brute_slice(plan: JoinPlan, lo: int, hi: int, lx32, ly32,
                     rx32, ry32, use_device: bool, dev, want_pairs: bool,
                     lz32=None, rz32=None, Kp: Optional[int] = None):
    """One flat brute-force slice: the sparse-cell candidate pairs
    [lo:hi) gathered into 1-D operands — no tile padding at all, just a
    fixed length bucket (``Kp``, from the caller's chunking; pow2 of the
    slice length when not given). Returns (pairs int64 [k, 2], count)."""
    bl = plan.brute_l[lo:hi]
    br = plan.brute_r[lo:hi]
    K = hi - lo
    if Kp is None:
        Kp = _pow2(K)
    lidx = np.zeros(Kp, np.int32)
    ridx = np.zeros(Kp, np.int32)
    lidx[:K] = bl
    ridx[:K] = br
    lxv, lyv = lx32[lidx], ly32[lidx]
    rxv, ryv = rx32[ridx], ry32[ridx]
    lzv = None if lz32 is None else lz32[lidx]
    rzv = None if rz32 is None else rz32[ridx]
    if use_device:
        import jax

        go = _brute_kernel(Kp, plan.predicate)
        if plan.predicate == kjoin.JOIN_DWITHIN_METERS:
            ops = (lxv, lyv, lzv, rxv, ryv, rzv, np.int32(K),
                   np.float32(plan.p0), np.float32(plan.p1))
        else:
            ops = (lxv, lyv, rxv, ryv, np.int32(K),
                   np.float32(plan.p0), np.float32(plan.p1))
        if dev is not None:
            ops = tuple(jax.device_put(o, dev) for o in ops)
        with tracing.span("scan.join.brute", pairs=K, device=getattr(
                dev, "id", None)):
            metrics.inc(metrics.EXEC_DEVICE_DISPATCH)
            utilization.dispatched(getattr(dev, "id", 0) or 0)
            m, n = go(*ops)
        m = np.asarray(m)
        n = int(n)
        utilization.settle()
    else:
        m = kjoin.pair_mask(lxv, lyv, rxv, ryv, plan.predicate,
                            plan.p0, plan.p1, np, lz=lzv, rz=rzv)
        m = m & (np.arange(Kp, dtype=np.int32) < K)
        n = int(m.sum())
    if not want_pairs:
        return np.zeros((0, 2), np.int64), n
    k = np.nonzero(m[:K])[0]
    pairs = np.stack([bl[k].astype(np.int64), br[k].astype(np.int64)],
                     axis=1)
    return pairs, n


def _jax_ok() -> bool:
    try:
        import jax  # noqa: F401

        return True
    except Exception:  # pragma: no cover — jax is baked into the image
        return False


def meters_reach_deg(distance_m: float, lat) -> Tuple[np.ndarray, float]:
    """Conservative lon/lat reach (degrees) of ``distance_m`` meters of
    great-circle distance around probe rows at latitudes ``lat`` —
    ``(reach_x [per-row], reach_y)`` for the dwithin_meters strip
    (docs/JOIN.md §10: latitude-dependent lon reach). The lat reach is
    the central angle exactly; the lon reach is the maximal longitude
    span of the spherical circle, ``arcsin(sin θ / cos φ)``, going full
    wrap (360°) where the circle reaches a pole (sin θ >= cos φ) — the
    only regime where a partner's longitude is unconstrained."""
    theta = float(distance_m) / kjoin.EARTH_RADIUS_M  # central angle, rad
    reach_y = float(np.degrees(theta))
    if theta >= np.pi / 2:
        return np.full(np.shape(lat), 360.0), reach_y
    cphi = np.cos(np.deg2rad(np.asarray(lat, np.float64)))
    s = np.sin(theta)
    safe = s < cphi
    reach_x = np.where(
        safe,
        np.degrees(np.arcsin(np.minimum(s / np.maximum(cphi, 1e-300), 1.0))),
        360.0,
    )
    return reach_x, reach_y


def run_join(lx, ly, rx, ry, predicate: str, distance=None, dx=None,
             dy=None, level: Optional[int] = None,
             prefer_device: bool = True, want_pairs: bool = True,
             adaptive: Optional[bool] = None):
    """Full co-partitioned join: plan + execute. Returns
    ``(pairs, total, stats)``. ``predicate``: ``"bbox"`` (half-widths
    ``dx``/``dy``), ``"dwithin"`` (planar degree ``distance``), or
    ``"dwithin_meters"`` (haversine great-circle ``distance`` meters) —
    see :func:`geomesa_tpu.kernels.join.pair_mask` for the exact
    semantics. ``adaptive`` None reads ``geomesa.join.adaptive``; False
    is the single-strategy A/B baseline (bit-identical results)."""
    p0, p1 = kjoin.pair_params(predicate, distance=distance, dx=dx, dy=dy)
    wrap_x = False
    if predicate == kjoin.JOIN_BBOX:
        reach_x, reach_y = float(p0), float(p1)
    elif predicate == kjoin.JOIN_DWITHIN_METERS:
        # latitude-dependent lon reach; the great circle wraps the
        # antimeridian, so the strip does too
        reach_x, reach_y = meters_reach_deg(float(distance), ry)
        wrap_x = True
    else:
        reach_x = reach_y = float(distance)
    with tracing.span("scan.join.partition"):
        plan = co_partition(lx, ly, rx, ry, predicate, reach_x, reach_y,
                            level=level, p0=p0, p1=p1, wrap_x=wrap_x,
                            adaptive=adaptive)
    st = plan.stats
    metrics.inc(metrics.JOIN_CELLS, st.cells_joint)
    metrics.inc(metrics.JOIN_CANDIDATE_PAIRS, st.candidate_pairs)
    tracing.add_cost("join_cells", float(st.cells_joint))
    tracing.add_cost("join_candidate_pairs", float(st.candidate_pairs))
    for s, k in st.strategy_cells.items():
        metrics.inc(metrics.JOIN_CELLS_STRATEGY + s, k)
    pairs, total = execute_predicate(plan, lx, ly, rx, ry, predicate,
                                     prefer_device=prefer_device,
                                     want_pairs=want_pairs)
    metrics.inc(metrics.JOIN_PAIRS, total)
    return pairs, total, st


def execute_predicate(plan: JoinPlan, lx, ly, rx, ry, predicate: str,
                      prefer_device: bool = True, want_pairs: bool = True):
    """:func:`execute` with the predicate's operand convention applied:
    ``dwithin_meters`` runs on precomputed f32 unit vectors — host trig
    once, shared by kernel and reference (kernels.join.unit_vectors) —
    every other predicate passes lon/lat straight through. The one
    dispatch both :func:`run_join` and ``explain_join(analyze=True)``
    share, so they cannot drift."""
    if predicate == kjoin.JOIN_DWITHIN_METERS:
        lux, luy, luz = kjoin.unit_vectors(lx, ly)
        rux, ruy, ruz = kjoin.unit_vectors(rx, ry)
        return execute(plan, lux, luy, rux, ruy,
                       prefer_device=prefer_device,
                       want_pairs=want_pairs, lz=luz, rz=ruz)
    return execute(plan, lx, ly, rx, ry, prefer_device=prefer_device,
                   want_pairs=want_pairs)


# ---------------------------------------------------------------------------
# Polygon-dataset joins (docs/JOIN.md §7): point side x POLYGON side
# ---------------------------------------------------------------------------

def _polygon_level(n_points: int, bnds: np.ndarray) -> int:
    """Cell level for a polygon join: the median polygon should span a
    few cells per axis — fine enough that INTERIOR cells exist (the
    wholesale win), coarse enough that per-polygon candidate cell counts
    stay bounded."""
    max_level = config.JOIN_MAX_LEVEL.to_int() or 12
    spans = np.maximum(
        np.maximum(bnds[:, 2] - bnds[:, 0], (bnds[:, 3] - bnds[:, 1]) * 2.0),
        1e-9,
    )
    med = float(np.median(spans))
    level = int(np.round(np.log2(360.0 / max(med / 4.0, 1e-9))))
    return int(np.clip(level, 1, max_level))


def _poly_kernel(Np: int, Ep: int, Pfp: int, Rp: int, predicate: str):
    """Registry-cached jitted polygon-join kernel: [Np, Rp] bool verdict
    matrix for a slice of boundary-cell points against the padded polygon
    tables (kernels.join.polygon_tables/polygon_mask). Every axis is a
    pow2 bucket in the key; the tables ride as traced operands."""
    reg = join_registry()
    key = ("join.poly", Np, Ep, Pfp, Rp, predicate)
    go = reg.get(key)
    if go is not None:
        return go
    import jax
    import jax.numpy as jnp

    @jax.jit
    def go(pxv, pyv, x1, y1, x2, y2, part_id, part_row, boxes):
        t = {"x1": x1, "y1": y1, "x2": x2, "y2": y2,
             "part_id": part_id, "part_row": part_row, "boxes": boxes,
             "n_parts_padded": Pfp, "n_rows_padded": Rp}
        return kjoin.polygon_mask(pxv, pyv, t, predicate, jnp)

    reg.put(key, go)
    return go


def run_polygon_join(px, py, geoms, predicate: str,
                     level: Optional[int] = None,
                     prefer_device: bool = True, want_pairs: bool = True):
    """Join a point side against a polygon-dataset side. Returns
    ``(pairs, total, stats)``: matched (point_row, polygon_row) positions
    in canonical row-major order, bit-identical to
    :func:`kernels.join.polygon_brute_force` by construction.

    The adaptive core: occupied point cells classify against each
    candidate polygon via ``classify_cells`` + ``CLASSIFY_MARGIN`` —

    * INTERIOR cells match **wholesale**: every point in the cell is at
      least the margin inside (exact f64), so the f32 kernel verdict is
      True for all of them — zero pairwise work dispatched;
    * OUTSIDE cells are skipped for the symmetric reason;
    * BOUNDARY cells pay the polygon kernel (the same
      ``polygon_mask`` f32 arithmetic as the reference), so near-edge
      points decide exactly as the reference decides them.

    ``predicate``: ``"pip"`` (even-odd point-in-polygon; holes and
    multipolygon parts per ``polygon_mask``) or ``"poly_bbox"`` (point in
    the row's bounds, inclusive edges — classification runs against the
    bounds rectangle)."""
    from geomesa_tpu.cache import cells as gcells
    from geomesa_tpu.utils import geometry as geo

    px = np.asarray(px, np.float64)
    py = np.asarray(py, np.float64)
    geoms = list(geoms)
    stats = JoinStats(n_left=len(px), n_right=len(geoms), adaptive=True)
    empty = np.zeros((0, 2), np.int64)
    if not len(px) or not len(geoms):
        return (empty if want_pairs else None), 0, stats
    bnds = np.asarray([g.bounds() for g in geoms], np.float64)  # [R, 4]
    if level is None:
        level = _polygon_level(len(px), bnds)
    stats.level = level
    ix, iy = gcells.point_cells(px, py, level)
    cell = _cell_ids(ix, iy)
    order = np.argsort(cell, kind="stable")
    sorted_cells = cell[order]
    ucell, starts = np.unique(sorted_cells, return_index=True)
    ends = np.concatenate([starts[1:], [len(order)]])
    stats.cells_left = len(ucell)
    stats.cells_right = len(geoms)
    boxes = gcells.cell_boxes(level, ix[order][starts], iy[order][starts])
    m = CLASSIFY_MARGIN

    wholesale_blocks: List[np.ndarray] = []
    R = len(geoms)
    boundary_pts = np.zeros(len(px), bool)
    # per-polygon boundary cell lists (classified lazily into the mask
    # AFTER the boundary point set is known)
    boundary_cells: List[np.ndarray] = []
    interior_cells = boundary_count = 0
    for j, g in enumerate(geoms):
        bx0, by0, bx1, by1 = bnds[j]
        cand = np.nonzero(
            (boxes[:, 0] <= bx1 + m) & (boxes[:, 2] >= bx0 - m)
            & (boxes[:, 1] <= by1 + m) & (boxes[:, 3] >= by0 - m)
        )[0]
        if not len(cand):
            boundary_cells.append(cand)
            continue
        stats.cells_joint += len(cand)
        target = g if predicate == kjoin.JOIN_PIP \
            else geo.bbox_polygon(bx0, by0, bx1, by1)
        cls = kjoin.classify_cells(boxes[cand], target, CLASSIFY_MARGIN)
        interior = cand[cls == kjoin.CELL_INTERIOR]
        boundary = cand[cls == kjoin.CELL_BOUNDARY]
        interior_cells += len(interior)
        boundary_count += len(boundary)
        for u in interior:
            rows = order[starts[u]: ends[u]]
            wholesale_blocks.append(np.stack([
                rows.astype(np.int64),
                np.full(len(rows), j, np.int64),
            ], axis=1))
        for u in boundary:
            boundary_pts[order[starts[u]: ends[u]]] = True
        boundary_cells.append(boundary)
    stats.strategy_cells["interior"] = interior_cells
    stats.strategy_cells["boundary"] = boundary_count
    wholesale = (np.concatenate(wholesale_blocks, axis=0)
                 if wholesale_blocks else empty)
    stats.wholesale_pairs = len(wholesale)

    # boundary phase: unique boundary points x candidate polygons through
    # the polygon kernel (the only pairwise work in the whole join)
    brows = np.nonzero(boundary_pts)[0]
    matched_blocks: List[np.ndarray] = []
    kernel_total = 0
    if len(brows):
        # candmask[b, j]: point b's cell is a boundary cell of polygon j —
        # interior cells are EXCLUDED (already matched wholesale)
        bpos = np.full(len(px), -1, np.int64)
        bpos[brows] = np.arange(len(brows))
        candmask = np.zeros((len(brows), R), bool)
        for j, bcells in enumerate(boundary_cells):
            for u in bcells:
                rows = order[starts[u]: ends[u]]
                candmask[bpos[rows], j] = True
        stats.candidate_pairs = int(candmask.sum())
        tables = kjoin.polygon_tables(geoms)
        Ep = _pow2(tables["n_edges"])
        Pfp = _pow2(tables["n_parts"])
        Rp = _pow2(tables["n_rows"])
        tables = kjoin.polygon_tables(geoms, pad_edges=Ep, pad_parts=Pfp,
                                      pad_rows=Rp)
        px32 = px.astype(np.float32)
        py32 = py.astype(np.float32)
        use_device = prefer_device and _jax_ok()
        devs = _devices(prefer_device) if use_device else None
        n_dev = len(devs) if devs else 1
        stats.devices = n_dev
        from geomesa_tpu.resilience import QueryTimeoutError

        for i, (lo, hi) in enumerate(_slices(len(brows), n_dev)):
            check_deadline()
            dev = devs[i % len(devs)] if devs else None
            try:
                verdict = _run_poly_slice(
                    brows[lo:hi], px32, py32, tables, predicate,
                    use_device, dev, Ep, Pfp, Rp,
                )
                hit = verdict[:, :R] & candmask[lo:hi]
                kernel_total += int(hit.sum())
                b, j = np.nonzero(hit)
                if len(b):
                    matched_blocks.append(np.stack([
                        brows[lo:hi][b].astype(np.int64),
                        j.astype(np.int64),
                    ], axis=1))
            except BaseException as e:
                if isinstance(e, QueryTimeoutError) or not partial_allowed():
                    raise
                record_skip("join", f"poly[{lo}:{hi}]", e, phase="pairs")
                stats.skipped.append(f"poly[{lo}:{hi}]")
    total = len(wholesale) + kernel_total
    stats.matched = total
    metrics.inc(metrics.JOIN_CELLS, stats.cells_joint)
    metrics.inc(metrics.JOIN_CANDIDATE_PAIRS, stats.candidate_pairs)
    for s, k in stats.strategy_cells.items():
        metrics.inc(metrics.JOIN_CELLS_STRATEGY + s, k)
    metrics.inc(metrics.JOIN_PAIRS, total)
    if not want_pairs:
        return None, total, stats
    blocks = [b for b in ([wholesale] + matched_blocks) if len(b)]
    if not blocks:
        return empty, total, stats
    pairs = np.concatenate(blocks, axis=0)
    order2 = np.lexsort((pairs[:, 1], pairs[:, 0]))
    return pairs[order2], total, stats


def _run_poly_slice(rows: np.ndarray, px32, py32, tables, predicate: str,
                    use_device: bool, dev, Ep: int, Pfp: int, Rp: int):
    """One boundary-point slice: [len(rows) padded to Np, Rp] verdicts
    from the polygon kernel (device) or the same ``polygon_mask`` on the
    host — identical f32 arithmetic either way."""
    K = len(rows)
    Np = _pow2(K)
    idx = np.zeros(Np, np.int64)
    idx[:K] = rows
    pxv = px32[idx]
    pyv = py32[idx]
    if use_device:
        import jax

        go = _poly_kernel(Np, Ep, Pfp, Rp, predicate)
        ops = (pxv, pyv, tables["x1"], tables["y1"], tables["x2"],
               tables["y2"], tables["part_id"], tables["part_row"],
               tables["boxes"])
        if dev is not None:
            ops = tuple(jax.device_put(o, dev) for o in ops)
        with tracing.span("scan.join.poly", points=K, device=getattr(
                dev, "id", None)):
            metrics.inc(metrics.EXEC_DEVICE_DISPATCH)
            utilization.dispatched(getattr(dev, "id", 0) or 0)
            verdict = np.asarray(go(*ops))
        utilization.settle()
    else:
        verdict = kjoin.polygon_mask(pxv, pyv, tables, predicate, np)
    return verdict[:K]
