"""Query executor: run a QueryPlan against an IndexTable.

The runtime role of the reference's scan/reduce pipeline
(QueryPlanner.runQuery -> plan.scan -> resultsToFeatures -> reducer,
QueryPlan.scala:30-94): resolve scan windows, build the fused mask (coarse
window mask & compiled predicate & validity), and run the aggregation kernel —
all inside one jit when the predicate's columns are device-resident, falling
back to vectorized numpy when the filter needs host-only columns (feature-id
strings, exact 64-bit values).
"""

from __future__ import annotations

import contextlib
import logging
import os
from typing import Any, Dict, Optional

import numpy as np

from geomesa_tpu import config, metrics, tracing, utilization
from geomesa_tpu.index.store import FeatureStore, IndexTable, device_view
from geomesa_tpu.kernels import density as kdensity
from geomesa_tpu.kernels import knn as kknn
from geomesa_tpu.kernels import masks as kmasks
from geomesa_tpu.kernels import stats_scan as kstats
from geomesa_tpu.kernels.registry import (
    KernelRegistry, dict_fingerprint, enable_persistent_cache,
)
from geomesa_tpu.planning.planner import QueryPlan
from geomesa_tpu.planning.viewcache import caches_of
from geomesa_tpu.schema.columns import ColumnBatch
from geomesa_tpu.stats import sketches as sk


# QueryTimeoutError is defined in the resilience layer (resilience.py) and
# re-exported here: the deadline primitive moved there so remote edges can
# propagate the remaining budget, while existing callers keep importing the
# error (and query_deadline) from this module.
from geomesa_tpu.resilience import (  # noqa: E402  (re-export)
    QueryTimeoutError, check_deadline, deadline_scope,
)


# -- window-compacted scan layout -------------------------------------------
# Device scatter costs ~6.7 ns per TOUCHED row regardless of masking
# (docs/SCALE.md cost model), so a density scan over the full padded table
# pays for every row even when the z-windows admit a few percent. The
# compacted path gathers ONLY the window rows — as chunked slabs, because
# slice-sized gathers run at HBM bandwidth (~100 GiB/s measured) while
# per-element gathers crawl at ~7.5 ns/element — and aggregates over the
# [C, B] compact layout. Selective queries then scale with rows *scanned*,
# not rows *stored* (the same property the reference gets from range scans:
# AbstractBatchScan.scala:32 only ever reads the planned ranges).
_SLAB_GATHER_FNS: Dict[int, Any] = {}

#: Range-cover budget of the compact path's fine (gap-union-free) window
#: resolution (:meth:`Executor._fine_windows`); a planner budget
#: (geomesa.scan.ranges.target) at or above it turns the fine pass off.
FINE_COVER = 32768


def _slab_gather_fn(B: int):
    """jit'd [C]-chunk slab gather (vmapped dynamic_slice of length B),
    named ``slab_gather`` in the device trace."""
    fn = _SLAB_GATHER_FNS.get(B)
    if fn is None:
        import jax

        def slab_gather(flat, gstart):
            return jax.vmap(
                lambda s: jax.lax.dynamic_slice(flat, (s,), (B,))
            )(gstart)

        fn = _SLAB_GATHER_FNS[B] = jax.jit(slab_gather)
    return fn


def _named(fn, name: str):
    """Give a function about to be jit'd the name its module carries in
    the device trace (``jit_<name>``)."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def _admitted(starts, ends) -> int:
    """Rows a window set admits."""
    return int(np.maximum(ends - starts, 0).sum())


@contextlib.contextmanager
def _sync_span():
    """``scan.sync``: the host reads a device result. On exit the calling
    thread's dispatch stamps close (utilization: in flight until here)."""
    try:
        with tracing.span("scan.sync"):
            yield
    finally:
        utilization.settle()


@contextlib.contextmanager
def query_deadline(timeout_s: "Optional[float]"):
    """Scope a wall-clock deadline over a query's scan phases (built on
    ``resilience.deadline_scope``). Checked between per-shard host passes,
    around device dispatches, and per partition — kernels themselves are not
    interruptible, so enforcement is at phase granularity (the same guarantee
    the reference's killer thread gives a blocking scan). Remote edges
    (sidecar client) read ``resilience.current_deadline()`` to tighten their
    per-call timeouts to the remaining budget."""
    with deadline_scope(timeout_s):
        yield


class Executor:
    def __init__(self, store: FeatureStore, mesh=None, prefer_device: bool = True,
                 kernel_fns: Optional[Dict] = None, version_source=None,
                 device=None):
        self.store = store
        self.mesh = mesh
        self.prefer_device = prefer_device
        #: optional jax device PIN (mutually exclusive with ``mesh``): every
        #: column/window/schedule placement commits to this one device, so
        #: the sharded partitioned scan can run partition i on device d and
        #: the serving pool can give each dispatch thread its own device
        #: (one jit thread per device — docs/SCALE.md, docs/SERVING.md).
        #: Kernel registry keys stay device-free: one traced callable
        #: serves every device (jax specializes the executable per device
        #: internally without re-tracing), so pinning never recompiles.
        self.device = device
        #: jitted-kernel LRU shared ACROSS stores (time partitions of one
        #: parent store execute the same plan: one trace/compile, many tables)
        self.kernel_fns = kernel_fns
        #: object hosting the shared kernel registry and version-keyed host
        #: caches (the parent store for partition children). Kernel KEYS are
        #: version-stable (a mutation never recompiles — docs/PERF.md);
        #: window/verdict DATA caches stay keyed by ``.version``.
        self.version_source = version_source or store
        enable_persistent_cache()  # idempotent

    # -- helpers -----------------------------------------------------------
    def _table(self, plan: QueryPlan) -> IndexTable:
        return self.store.tables[plan.index_name]

    def kernel_registry(self) -> KernelRegistry:
        """The shared compiled-kernel LRU: one per parent store, shared by
        every partition child and every aggregate-cache cell query (the
        ROADMAP per-cell kernel-token item)."""
        if self.kernel_fns is not None:
            return self.kernel_fns
        reg = self.version_source.__dict__.get("_kernel_registry")
        if reg is None:
            reg = KernelRegistry()
            self.version_source.__dict__["_kernel_registry"] = reg
        return reg

    @staticmethod
    def _plan_registry(plan: QueryPlan) -> KernelRegistry:
        """Token-less (raw-IR) plans cache kernels on the plan itself —
        still LRU-managed so pagination/benchmark loops never hit the old
        clear-on-overflow wipe."""
        reg = plan.__dict__.get("_kernel_fns")
        if reg is None:
            reg = plan.__dict__["_kernel_fns"] = KernelRegistry()
        return reg

    def _dict_fp(self):
        """Dictionary-growth fingerprint: the ONLY store change that can
        invalidate a compiled predicate closure (string codes are resolved
        at compile time). Replaces the store version in kernel keys."""
        return dict_fingerprint(self.store.dicts)

    def _scan_setup(self, plan: QueryPlan, extra_cols=()):
        """Resolve windows + choose device/host path. Returns a dict bundle."""
        table = self._table(plan)
        if table.n == 0 or plan.is_empty:
            return None
        # Resolved windows are pure in (key_plan, table contents): cache
        # them so re-running the query — same plan object (pagination,
        # benchmarks, kNN radius loop) or a fresh plan of the same text
        # (cache_token) — skips the per-shard searchsorted sweep, which at
        # 20M rows costs ~90 ms/query, dwarfing the device kernel it feeds.
        rkey = ("win", self.store.uid, self.store.version, plan.index_name,
                plan.__dict__.get("window_token"),
                config.COMPACT_BUCKETING.to_bool(),
                config.COMPACT_BUCKET_FLOOR.to_int())
        cache, rkey = self._resolve_cache(plan, rkey)
        hit = cache.get(rkey)
        if hit is not None:
            starts, ends = hit
            scanned = _admitted(starts, ends)
        else:
            with tracing.span("scan.windows") as sp:
                starts, ends = table.windows(plan.key_plan)
                scanned = _admitted(starts, ends)
                sp.set(rows=scanned)
            cache.put(rkey, (starts, ends))
        counts = np.diff(table.shard_bounds).astype(np.int32)
        L = table.shard_len
        needed = list(dict.fromkeys(list(plan.compiled.columns) + list(extra_cols)))
        # sample_by is meaningless without a sampling rate.
        if plan.hints.sample_by and not plan.hints.sampling:
            raise ValueError("sample_by requires sampling (the 1-in-n rate)")
        # per-key sampling device modes (sort-free by design — device sort
        # compiles pathologically on this TPU toolchain):
        #   "exact": dictionary-coded key with a small vocabulary — one
        #     cumsum pass per code, exact per-key counters;
        #   "hash":  any other device-resident int32 key (large vocab,
        #     Integer attrs) — keys hash into SAMPLE_HASH_BUCKETS groups
        #     sharing counters (documented approximation; the host twin
        #     hashes identically so results are backend-independent).
        # float/int64/object keys stay on the host's exact counter (float
        # keys would merge distinct values at f32).
        sb = plan.hints.sample_by
        sb_mode, sb_off, sb_span_vocab = None, 0, 0
        if sb and table.has_column(sb) and not table.is_host_only(sb) \
                and table.dtype_of(sb) == np.int32:
            if sb in self.store.dicts:
                if 0 < len(self.store.dicts[sb]) <= 256:
                    sb_mode = "exact"
                elif self.prefer_device \
                        and (config.SAMPLE_HASH_BUCKETS.to_int() or 0) > 0:
                    # the approximation only buys anything when a device
                    # scan runs; host-only stores keep the exact counter
                    sb_mode = "hash"
            else:
                # raw int keys: a small VALUE SPAN runs the exact
                # per-code kernel on offset values (preserving the
                # reference's exact per-key counters); wide key spaces
                # hash-bucket. min/max cached per store version.
                span_cache = caches_of(self.store).sample_spans
                skey = (sb, plan.index_name, self.version_source.version)
                rng = span_cache.get(skey)
                if rng is None:
                    col = table.col_sorted(sb)
                    rng = span_cache.put(skey, (int(col.min()), int(col.max()))
                                         if len(col) else (0, -1))
                lo_v, hi_v = rng
                if 0 <= hi_v - lo_v < 256:
                    sb_mode, sb_off = "exact-span", lo_v
                    sb_span_vocab = hi_v - lo_v + 1
                elif self.prefer_device \
                        and (config.SAMPLE_HASH_BUCKETS.to_int() or 0) > 0:
                    sb_mode = "hash"
        sb_device = sb_mode is not None
        if sb_device:
            needed = list(dict.fromkeys(needed + [sb]))
        host_only = [
            c for c in needed
            if not table.has_column(c) or table.is_host_only(c)
        ]
        # extent-geometry refinement (exact spatial predicates) runs on the
        # host __wkt columns, so the whole mask must be host-resident before
        # aggregation — route such plans through the host path
        use_device = (
            self.prefer_device and not host_only
            and (sb is None or sb_device)
            and (
                plan.compiled.refine is None
                or plan.compiled.refine_only_if_band
            )
        )
        # refine-bearing plans (extent geometries, >2^24 int64 predicates)
        # can still run their COARSE mask on device: the heavy dense scan
        # stays a TPU kernel, the host only refines coarse-true candidates
        # (AggregatingScan.scala:82-116 validate-then-aggregate, split
        # across the device/host boundary)
        coarse_device = (
            self.prefer_device and not host_only
            and plan.compiled.refine is not None
        )
        # selectivity instrumentation: rows the coarse windows admit vs the
        # table size. The audit event pairs this with `hits` so over-scan
        # (candidates >> matches) is visible per query instead of silent.
        plan.__dict__["scanned_rows"] = scanned
        plan.__dict__["table_rows"] = int(table.n)
        # the partition prefetcher stages exactly this column set for the
        # NEXT partition while this one executes (partitioned_exec.py)
        plan.__dict__["needed_cols"] = tuple(needed)
        return {
            "table": table, "starts": starts, "ends": ends, "counts": counts,
            "L": L, "needed": needed, "use_device": use_device,
            "coarse_device": coarse_device, "sb_mode": sb_mode,
            "sb_off": sb_off, "sb_span_vocab": sb_span_vocab,
        }

    def _compact_candidates(self, plan: QueryPlan, setup):
        """Window set + chunk size for a compacted scan: (starts, ends, B,
        lens), or None when no window set admits chunking.

        Steady-state cost is per PADDED row, so the chunk size minimizes
        padding (preferring the largest B within 10% — fewer, larger slabs
        gather faster on the one-time pass), over BOTH window resolutions:
        the fine (gap-union-free) set usually admits fewer rows AND gives
        spatially tight chunks (the density pair lists depend on that), so
        it wins any near-tie (the 0.77 bias). Shared by the single-chip
        and mesh compaction descriptors."""
        L = setup["L"]
        ladder = [b for b in (128, 256, 512, 1024, 2048, 4096) if b <= L]

        def _choose(starts, ends):
            """(B, rows, lens) minimizing padded rows for one window set."""
            lens = np.maximum(ends - starts, 0).astype(np.int64)
            if int(lens.sum()) == 0 or not ladder:
                return None
            flat = lens.reshape(-1)
            rows_at = {
                Bc: int((-(-flat // Bc)).sum()) * Bc for Bc in ladder
            }
            floor_rows = min(rows_at.values())
            B = max(b for b, r in rows_at.items() if r <= 1.10 * floor_rows)
            return B, rows_at[B], lens

        cands = []
        coarse = _choose(setup["starts"], setup["ends"])
        if coarse is not None:
            cands.append(
                (coarse[1], 1, setup["starts"], setup["ends"], coarse[0],
                 coarse[2])
            )
        fs, fe = self._fine_windows(plan, setup)
        if fs is not None:
            fine = _choose(fs, fe)
            if fine is not None:
                cands.append(
                    (int(fine[1] * 0.77), 0, fs, fe, fine[0], fine[2])
                )
        if not cands:
            return None
        cands.sort(key=lambda c: (c[0], c[1]))
        _, _, starts, ends, B, lens = cands[0]
        return starts, ends, B, lens

    def _maybe_compact(self, plan: QueryPlan, setup, allowed: bool) -> None:
        """Decide the window-compacted layout for this scan. Sets
        ``setup['compact']`` to a chunk-descriptor dict (or None).

        Chunks are B-row slabs (B = pow2 bucket of the typical window
        length) covering every window, ordered by global position so the
        deterministic sampling counter sees matches in the same order as
        the padded path. ``lo`` handles the end-of-table dynamic_slice
        clamp: valid rows of chunk c live at [lo, lo+valid) and map to
        global rows cstart + lo + i."""
        if "compact" in setup:
            return
        setup["compact"] = None
        if (
            not allowed
            or not setup["use_device"]
            or self.mesh is not None
            or not config.COMPACT_ENABLED.to_bool()
        ):
            return
        table = setup["table"]
        if table.n < (config.COMPACT_MIN_ROWS.to_int() or 0):
            return
        # the descriptor is pure in (resolved windows, table, knobs):
        # memoize it so repeat queries skip the ~1.5 ms argsort/repeat
        # rebuild (it dwarfs the per-call jit dispatch on cached plans)
        ckey = ("compact_desc", self.store.uid, self.store.version,
                plan.index_name, plan.__dict__.get("window_token"),
                config.COMPACT_FRACTION.to_float())
        ccache, ckey = self._resolve_cache(plan, ckey)
        chit = ccache.get(ckey)
        if chit is not None:
            setup["compact"] = chit or None
            return
        with tracing.span("scan.compact") as sp:
            self._build_compact(plan, setup, table, ccache, ckey, sp)

    def _build_compact(self, plan: QueryPlan, setup, table, ccache, ckey,
                       sp) -> None:
        """The descriptor-cache miss of :meth:`_maybe_compact`: candidate
        windows, the shared-descriptor lookup, the argsort/repeat build."""
        L = setup["L"]
        chosen = self._compact_candidates(plan, setup)
        if chosen is None:
            ccache.put(ckey, False)
            return
        starts, ends, B, lens = chosen
        S, K = starts.shape
        flat_lens = lens.reshape(-1)
        nc = -(-flat_lens // B)
        C = int(nc.sum())
        sp.set(B=int(B), C=C, rows=C * int(B))
        # content-addressed descriptor share (docs/PERF.md "Shared
        # descriptors"): the built descriptor is pure in (resolved window
        # BYTES, B bucket, padded layout), so any other jit site / query
        # text / plan token that resolves the same windows reuses the
        # ~1.5 ms argsort/repeat build instead of duplicating it. Keyed
        # by the bytes, never their hash — a collision would silently
        # scan another query's rows, and equality is the correctness
        # contract (the arrays are small next to the slabs they index).
        share = caches_of(self.store).shared_descriptors
        skey = ("flat", B, S, L, starts.tobytes(), ends.tobytes())
        shit = share.get(skey)
        if shit is not None:
            metrics.inc(metrics.COMPACT_DESC_SHARED)
            ccache.put(ckey, shit)
            setup["compact"] = shit or None
            return
        frac = config.COMPACT_FRACTION.to_float()
        if C * B >= table.n * (0.5 if frac is None else frac):
            # windows admit most of the table: compaction can't win
            ccache.put(ckey, False)
            share.put(skey, False)
            return
        win = np.repeat(np.arange(S * K), nc)
        j = np.arange(C) - np.repeat(np.cumsum(nc) - nc, nc)
        s_of = win // K
        gstart = (
            s_of * L + starts.reshape(-1)[win] + j * B
        ).astype(np.int64)
        valid = np.minimum(flat_lens[win] - j * B, B).astype(np.int32)
        order = np.argsort(gstart, kind="stable")
        gstart, valid = gstart[order], valid[order]
        cstart = np.minimum(gstart, S * L - B)
        lo = (gstart - cstart).astype(np.int32)
        # bucket the chunk count (shared ladder with the MXU pair padding),
        # so partitions of one store reuse few kernel shapes without pow2's
        # 2x row padding (scatter pays per padded row, masked or not)
        from geomesa_tpu.kernels.density_mxu import ladder8

        Cp = ladder8(C)
        if Cp != C:
            pad = Cp - C
            cstart = np.concatenate([cstart, np.zeros(pad, np.int64)])
            lo = np.concatenate([lo, np.zeros(pad, np.int32)])
            valid = np.concatenate([valid, np.zeros(pad, np.int32)])
        desc = {
            "B": B,
            "C": Cp,
            "cstart": cstart.astype(np.int32),
            "lo": lo,
            "valid": valid,
            "whash": hash((starts.tobytes(), ends.tobytes())),
        }
        ccache.put(ckey, desc)
        share.put(skey, desc)
        setup["compact"] = desc

    # -- mesh-sharded window compaction -----------------------------------
    def _plain_shard_mesh(self):
        """The mesh, when 'shard' is its only non-trivial axis (the
        binspace 2-D layout has its own path)."""
        m = self.mesh
        if m is None or "shard" not in m.axis_names:
            return None
        other = int(np.prod([
            m.shape[a] for a in m.axis_names if a != "shard"
        ])) if len(m.axis_names) > 1 else 1
        return m if other == 1 else None

    def _mesh_compact_desc(self, plan: QueryPlan, setup, D: int):
        """Per-device compact descriptors for a 'shard'-meshed scan:
        [D, Cp] (cstart, lo, valid) arrays with a UNIFORM padded chunk
        count Cp, chunk starts local to each device's [S/D, L] block —
        every device slab-gathers only its own windows' rows, so a
        multi-chip selective scan costs per row SCANNED per chip, exactly
        like the single-chip compact path. False = compaction can't win
        for these windows (cached)."""
        ckey = ("compact_mesh", self.store.uid, self.store.version,
                plan.index_name, plan.__dict__.get("window_token"), D,
                config.COMPACT_FRACTION.to_float())
        cache, ckey = self._resolve_cache(plan, ckey)
        hit = cache.get(ckey)
        if hit is not None:
            return hit or None
        with tracing.span("scan.compact") as sp:
            return self._build_mesh_compact(plan, setup, D, cache, ckey, sp)

    def _build_mesh_compact(self, plan: QueryPlan, setup, D: int, cache,
                            ckey, sp):
        """The descriptor-cache miss of :meth:`_mesh_compact_desc`."""
        table = setup["table"]
        L = setup["L"]
        chosen = self._compact_candidates(plan, setup)
        out = False
        share = caches_of(self.store).shared_descriptors
        skey = None
        if chosen is not None:
            starts, ends, B, lens = chosen
            S, K = starts.shape
            # content-addressed share, bucket-aware (docs/PERF.md "Shared
            # descriptors"): same resolved windows + same (B, S, D)
            # layout => same [D, Cp] descriptor, whatever site/plan asked
            # (keyed by the window BYTES — equality is the correctness
            # contract; S pins the (S, K) factorization of those bytes)
            skey = ("mesh", B, D, S, L, starts.tobytes(), ends.tobytes())
            shit = share.get(skey)
            if shit is not None:
                metrics.inc(metrics.COMPACT_DESC_SHARED)
                cache.put(ckey, shit)
                return shit or None
            Sd = S // D
            flat_lens = lens.reshape(-1)
            nc = -(-flat_lens // B)
            C = int(nc.sum())
            sp.set(B=int(B), C=C, rows=C * int(B))
            c_dev = nc.reshape(D, Sd * K).sum(axis=1)
            from geomesa_tpu.kernels.density_mxu import ladder8

            Cp = ladder8(int(c_dev.max())) if C else 0
            frac = config.COMPACT_FRACTION.to_float()
            frac = 0.5 if frac is None else frac
            if C and Cp * B * D < table.n * frac:
                win = np.repeat(np.arange(S * K), nc)
                j = np.arange(C) - np.repeat(np.cumsum(nc) - nc, nc)
                s_of = win // K
                d_of = s_of // Sd
                gstart = (
                    (s_of - d_of * Sd) * L + starts.reshape(-1)[win] + j * B
                ).astype(np.int64)
                valid = np.minimum(flat_lens[win] - j * B, B).astype(np.int32)
                cstart = np.minimum(gstart, Sd * L - B)
                lo = (gstart - cstart).astype(np.int32)
                # pack into [D, Cp]: chunks of device d land at row d in
                # their global (shard-major) order
                slot = np.arange(C) - np.repeat(
                    np.concatenate(([0], np.cumsum(c_dev)[:-1])), c_dev
                )
                a_cstart = np.zeros((D, Cp), np.int32)
                a_lo = np.zeros((D, Cp), np.int32)
                a_valid = np.zeros((D, Cp), np.int32)
                a_cstart[d_of, slot] = cstart.astype(np.int32)
                a_lo[d_of, slot] = lo
                a_valid[d_of, slot] = valid
                out = {
                    "B": B, "Cp": Cp,
                    "cstart": a_cstart, "lo": a_lo, "valid": a_valid,
                    "whash": hash((starts.tobytes(), ends.tobytes())),
                }
        cache.put(ckey, out)
        if skey is not None:
            share.put(skey, out)
        return out or None

    def _compact_mesh_run(self, plan: QueryPlan, setup, agg_fn, agg_cols,
                          cache_key, extra):
        """Additive aggregate over per-device compacted windows on the
        plain-'shard' mesh (shard_map slab-gather + fused mask + psum).
        None when the layout does not apply (caller falls through to the
        padded GSPMD path)."""
        mesh = self._plain_shard_mesh()
        table = setup["table"]
        if (
            mesh is None
            or not config.COMPACT_ENABLED.to_bool()
            or plan.hints.sampling  # the 1-in-n counter is global
            or table.n < (config.COMPACT_MIN_ROWS.to_int() or 0)
            or table.n_shards % mesh.shape["shard"] != 0
        ):
            return None
        D = mesh.shape["shard"]
        d = self._mesh_compact_desc(plan, setup, D)
        if d is None:
            return None
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        B, Cp = d["B"], d["Cp"]
        compiled = plan.compiled
        names = tuple(dict.fromkeys(list(setup["needed"]) + list(agg_cols)))
        dev_cols = table.device_columns(names, self._sharding())
        token = plan.__dict__.get("cache_token")
        if token is not None and cache_key is not None:
            fn_cache = self.kernel_registry()
            fn_key = ("compact_mesh", cache_key, B, Cp, D, token,
                      plan.index_name, self._dict_fp())
        else:
            fn_cache = self._plan_registry(plan)
            fn_key = ("compact_mesh", cache_key, B, Cp, D)
        go = fn_cache.get(fn_key)
        if go is None:
            col_names = sorted(names)

            def local(cols, cstart, lo, valid, extra):
                gather = jax.vmap(
                    lambda flat, s: jax.lax.dynamic_slice(flat, (s,), (B,)),
                    in_axes=(None, 0),
                )
                ccols = {
                    k: gather(cols[k].reshape(-1), cstart[0])
                    for k in col_names
                }
                iota = jnp.arange(B, dtype=jnp.int32)[None, :]
                m = (iota >= lo[0][:, None]) & (iota < (lo[0] + valid[0])[:, None])
                m = m & compiled(ccols, jnp)
                if compiled.band is not None:
                    m = m & ~compiled.band(ccols, jnp)
                return jax.lax.psum(agg_fn(ccols, m, jnp, *extra), "shard")

            sm = jax.shard_map(
                local, mesh=mesh,
                in_specs=(
                    {k: P("shard", None) for k in col_names},
                    P("shard", None), P("shard", None), P("shard", None),
                    P(),
                ),
                out_specs=P(),
            )
            site = str(cache_key[0]) if cache_key else "agg"
            go = jax.jit(_named(sm, f"compact_mesh_{site}"))
            fn_cache.put(fn_key, go)
        wcache = caches_of(self.store).device_windows
        wkey = ("mesh_win", d["whash"], B, Cp, D, self.store.uid,
                self.store.version)
        win = wcache.get(wkey)
        if win is None:
            sh = self._sharding()
            win = wcache.put(wkey, tuple(
                jax.device_put(d[k], sh) for k in ("cstart", "lo", "valid")
            ))
        with self._kernel_span(str(cache_key[0]) if cache_key else None,
                               setup, compact=True, rows=D * Cp * B):
            return go(
                {k: dev_cols[k] for k in sorted(names)}, *win, tuple(extra)
            )

    def _resolve_cache(self, plan: QueryPlan, key):
        """Window-resolution cache host: store-level keyed by the plan's
        cache token when the plan is reproducible from query text (so a
        fresh plan of the same query hits), else the plan itself."""
        token = plan.__dict__.get("cache_token")
        if token is not None:
            return caches_of(self.store).resolved, key + (token,)
        return caches_of(plan).resolved, key

    def _fine_windows(self, plan: QueryPlan, setup):
        """Scan windows re-resolved from a RE-COVERED key plan under a much
        larger range budget, with the per-shard window cap lifted to match.

        The planner's default cover (~2000 ranges) leaves each range a
        degrees-wide span of the curve — fine for the padded path, whose
        cost is per stored row, but the compacted path costs per ADMITTED
        row and the MXU density kernel wants spatially TIGHT chunks, so a
        16-64x finer cover pays for itself immediately. Cover + resolve
        run once per (plan, store version) and are cached on the plan.
        (None, None) when the planner's own budget is already at least
        :data:`FINE_COVER` or the keyspace can't re-plan."""
        from geomesa_tpu.index import keyspace as ksmod

        if FINE_COVER <= (config.SCAN_RANGES_TARGET.to_int() or 2000):
            return None, None
        rkey = ("fine", self.store.uid, self.store.version,
                plan.index_name, plan.__dict__.get("window_token"),
                config.COMPACT_BUCKETING.to_bool(),
                config.COMPACT_BUCKET_FLOOR.to_int())
        cache, rkey = self._resolve_cache(plan, rkey)
        hit = cache.get(rkey)
        if hit is not None:
            return hit
        out = (None, None)
        try:
            table = setup["table"]
            with tracing.span("scan.windows.fine") as sp, \
                    config.SCAN_RANGES_TARGET.scoped(FINE_COVER), \
                    ksmod.window_cap(FINE_COVER):
                with tracing.span("scan.cover") as cover_sp:
                    fine_kp = table.keyspace.plan(self.store.ft, plan.filter)
                    if fine_kp is not None:
                        cover_sp.set(ranges=len(fine_kp.lo))
                if fine_kp is not None:
                    out = table.windows(fine_kp)
                    if sp is not tracing.NOOP:
                        sp.set(rows=_admitted(*out), ranges=len(fine_kp.lo))
        except Exception:
            logging.getLogger(__name__).warning(
                "fine window resolution failed; using the planner windows",
                exc_info=True,
            )
        return cache.put(rkey, out)

    def _compact_cols(self, setup, names):
        """Window rows of ``names`` as device [C, B] slabs, gathered from
        the (cached) padded device columns and cached per (windows, store
        version, device pin)."""
        d = setup["compact"]
        B, Cp = d["B"], d["C"]
        cache = caches_of(self.store).slabs
        key0 = (d["whash"], self.store.uid, self.store.version, B, Cp,
                self._devkey())
        out, missing = {}, []
        for n in names:
            hit = cache.get(key0 + (n,))
            (out.__setitem__(n, hit) if hit is not None else missing.append(n))
        if missing:
            with tracing.span("scan.device_put", compact=True):
                full = setup["table"].device_columns(
                    tuple(missing), self._sharding()
                )
                g = self._put(d["cstart"])
                gather = _slab_gather_fn(B)
                with tracing.span("scan.gather", columns=len(missing),
                                  rows=Cp * B):
                    utilization.dispatched(self._devkey() or 0)
                    for n in missing:
                        out[n] = gather(full[n].reshape(-1), g)
                cache.put_all({key0 + (n,): out[n] for n in missing})
        return out

    def _device_compact_agg(self, plan: QueryPlan, setup, agg_fn, agg_cols=(),
                            cache_key=None, extra=(), site=None):
        """Mask + aggregation in one jit over the compacted [C, B] layout.
        Same caching contract as :meth:`_device_mask_and_agg`; band rows are
        always excised (the compact path only serves the exact device
        path), their correction is additive host-side. The jit is named
        ``compact_<site>`` in the device trace."""
        import jax
        import jax.numpy as jnp

        d = setup["compact"]
        B, Cp = d["B"], d["C"]
        compiled = plan.compiled
        sampling = plan.hints.sampling
        sample_by = plan.hints.sample_by
        sb_mode = setup["sb_mode"]
        sb_off = setup["sb_off"]
        if sb_mode == "exact-span":
            sb_vocab = setup["sb_span_vocab"]
        else:
            sb_vocab = (
                len(self.store.dicts[sample_by])
                if sample_by and sample_by in self.store.dicts else 0
            )
        sb_buckets = config.SAMPLE_HASH_BUCKETS.to_int() or int(config.SAMPLE_HASH_BUCKETS.default)
        names = tuple(dict.fromkeys(list(setup["needed"]) + list(agg_cols)))
        cols = self._compact_cols(setup, names)
        token = plan.__dict__.get("cache_token")
        fn_cache = fn_key = None
        if cache_key is not None:
            if token is not None:
                fn_cache = self.kernel_registry()
                # sb_vocab is baked static below: it belongs in the key now
                # that the store version no longer stands in for it
                fn_key = ("compact", cache_key, B, Cp, sampling, sample_by,
                          sb_mode, sb_off, sb_vocab, sb_buckets, token,
                          plan.index_name, self._dict_fp())
            else:
                fn_cache = self._plan_registry(plan)
                fn_key = ("compact", cache_key, B, Cp, sampling, sample_by,
                          sb_mode, sb_off, sb_vocab, sb_buckets)
        go = fn_cache.get(fn_key) if fn_cache is not None else None
        site = site or (str(cache_key[0]) if cache_key else "agg")
        if go is None:

            def go(cols, lo, valid, extra):
                iota = jnp.arange(B, dtype=jnp.int32)[None, :]
                m = (iota >= lo[:, None]) & (iota < (lo + valid)[:, None])
                m = m & compiled(cols, jnp)
                if compiled.band is not None:
                    m = m & ~compiled.band(cols, jnp)
                if sampling and sample_by and sb_mode == "hash":
                    m = kmasks.sampling_mask_by_key_hash(
                        m, sampling, cols[sample_by], sb_buckets, jnp
                    )
                elif sampling and sample_by:
                    m = kmasks.sampling_mask_by_key_device(
                        m, sampling, cols[sample_by] - sb_off, sb_vocab,
                        jnp
                    )
                elif sampling:
                    m = kmasks.sampling_mask(m, sampling, jnp)
                return agg_fn(cols, m, jnp, *extra)

            go = jax.jit(_named(go, f"compact_{site}"))
            if fn_cache is not None:
                fn_cache.put(fn_key, go)
                self._note(plan, kernel="trace")
        elif fn_cache is not None:
            self._note(plan, kernel="hit")
        wcache = caches_of(self.store).device_windows
        wkey = ("compact_win", d["whash"], B, Cp, self.store.uid,
                self.store.version, self._devkey())
        win = wcache.get(wkey)
        if win is None:
            win = wcache.put(wkey, (self._put(d["lo"]), self._put(d["valid"])))
        with self._kernel_span(site, setup, compact=True, rows=Cp * B):
            return go(cols, win[0], win[1], tuple(extra))

    def _expand_compact_mask(self, setup, cmask) -> np.ndarray:
        """[C, B] compact mask -> [S, L] padded mask (host, vectorized —
        the chunk count can reach tens of thousands under the fine cover,
        so a per-chunk Python loop would cost more than the scan)."""
        d = setup["compact"]
        table = setup["table"]
        S, L = table.n_shards, setup["L"]
        B = d["B"]
        out = np.zeros(S * L, bool)
        cm = np.asarray(cmask)
        cstart = d["cstart"].astype(np.int64)
        lo, valid = d["lo"].astype(np.int64), d["valid"].astype(np.int64)
        n = int(valid.sum())
        if n == 0:
            return out.reshape(S, L)
        # flat positions of every valid (chunk, row) cell, in chunk order
        c_of = np.repeat(np.arange(len(valid)), valid)
        r_of = np.arange(n) - np.repeat(np.cumsum(valid) - valid, valid)
        out[cstart[c_of] + lo[c_of] + r_of] = cm[c_of, lo[c_of] + r_of]
        return out.reshape(S, L)

    def _device_coarse_mask(self, plan: QueryPlan, setup) -> np.ndarray:
        """Window mask ∧ coarse predicate as ONE device kernel, packed
        8 rows/byte on device so the host download is n/8 bytes. Returns
        the unpacked [S, L] numpy mask for host refinement."""
        import time as _time

        L = setup["L"]
        Lp = -(-L // 8) * 8

        def agg(cols, m, xp):
            import jax.numpy as jnp

            mp = jnp.pad(m, ((0, 0), (0, Lp - L))) if Lp != L else m
            bits = mp.reshape(m.shape[0], Lp // 8, 8).astype(jnp.uint8)
            w = (2 ** jnp.arange(8, dtype=jnp.uint8))[None, None, :]
            return (bits * w).sum(axis=-1).astype(jnp.uint8)

        t0 = _time.perf_counter()
        packed = np.asarray(
            self._device_mask_and_agg(plan, setup, agg,
                                      cache_key=("coarse_mask",),
                                      apply_sampling=False,
                                      excise_band=False)
        )
        plan.__dict__["device_coarse_ms"] = (
            plan.__dict__.get("device_coarse_ms", 0.0)
            + (_time.perf_counter() - t0) * 1e3
        )
        bits = np.unpackbits(packed, axis=1, bitorder="little")
        return bits[:, :L].astype(bool)

    def _band_info(self, plan: QueryPlan, setup):
        """f32-uncertainty resolution for the device path. The device
        kernel always runs on ``mask ∧ ¬band`` (band rows excised), which
        is exact for every non-band row. This host pass — one vectorized
        sweep over the rows the scan windows admit, per (plan token, store
        version, windows), cached — finds the band rows among them,
        evaluates the EXACT f64 predicate on those, and returns the kept
        rows' sorted-order positions (usually an empty array: at 20M
        uniform doubles a round query bound collides with ~2-3 rows).
        Additive aggregates add these rows' contribution to the device
        partial; other ops fall back when any survive."""
        compiled = plan.compiled
        if compiled.band is None:
            return None
        token = plan.__dict__.get("cache_token")
        vc = caches_of(
            self.version_source if token is not None else plan
        ).band_verdicts
        # the verdict depends on the SCAN WINDOWS too (kNN reuses one token
        # across expanding boxes): fingerprint them into the key
        vkey = (
            token, self.store.uid, self.store.version,
            hash((setup["starts"].tobytes(), setup["ends"].tobytes())),
        )
        hit = vc.get(vkey)
        if hit is not None:
            return hit
        table = setup["table"]
        names = list(dict.fromkeys(
            list(compiled.columns) + list(compiled.refine_columns or [])
        ))
        # sorted-order positions of the admitted rows (the windows of a
        # shard are disjoint), not the whole table: a cold view's first
        # visit would otherwise gather every column of every row
        starts = setup["starts"] + table.shard_bounds[:-1, None]
        lens = np.maximum(setup["ends"] - setup["starts"], 0).reshape(-1)
        first = np.cumsum(lens) - lens
        pos = (np.repeat(starts.reshape(-1) - first, lens)
               + np.arange(int(lens.sum())))
        cols = {
            n: table.col_sorted_at(n, pos) for n in names
            if table.has_column(n)
        }
        band = np.broadcast_to(
            np.asarray(compiled.band(cols, np)).reshape(-1), pos.shape
        )
        order = np.argsort(pos[band])
        idx = pos[band][order]
        if len(idx):
            rows = {n: v[band][order] for n, v in cols.items()}
            # master columns for names stored only via the permutation
            keep = np.asarray(
                (compiled.refine or compiled.fn)(rows, np)
            ).reshape(-1)
            if keep.ndim == 0:
                keep = np.full(len(idx), bool(keep))
            idx = idx[keep.astype(bool)]
        # sorted-order row positions, maybe empty
        return vc.put(vkey, idx.astype(np.int64))

    def _band_correction(self, plan: QueryPlan, setup, info, agg_fn_host,
                         agg_cols, extra):
        """Exact contribution of the surviving band rows, shaped for
        additive combination with the device partial."""
        if info is None or len(info) == 0:
            return None
        table = setup["table"]
        names = dict.fromkeys(
            list(setup["needed"]) + list(agg_cols)
        )
        rows = {}
        master_rows = table.order[info]
        for n in names:
            kc = table.key_columns.get(n)
            if kc is not None:
                v = kc[info]
            elif table.has_column(n):
                v = table._master[n][master_rows]
            else:
                continue
            # membership was decided exactly; the aggregate reads the row
            # as the kernel reads every certain row (f32 coordinates), so
            # an edge row lands in the density cell the device would bin
            dv = device_view(v)
            rows[n] = (v if dv is None else dv)[None, :]
        mask = np.ones((1, len(info)), bool)
        return agg_fn_host(rows, mask, np, *extra)

    def _coarse_or_none(self, plan: QueryPlan, setup) -> Optional[np.ndarray]:
        """Device coarse mask when the plan is eligible, else None (host
        computes the full mask). Falls back loudly, honoring STRICT_DEVICE."""
        if not setup.get("coarse_device"):
            return None
        try:
            return self._device_coarse_mask(plan, setup)
        except Exception as e:
            if os.environ.get("GEOMESA_TPU_STRICT_DEVICE"):
                raise
            logging.getLogger(__name__).warning(
                "device coarse scan failed, computing mask on host: %r", e
            )
            return None

    def _host_mask(self, plan: QueryPlan, setup,
                   coarse: Optional[np.ndarray] = None) -> np.ndarray:
        """[S, L] mask on the host (numpy). ``coarse`` short-circuits the
        window+predicate passes with a device-computed coarse mask."""
        table = setup["table"]
        if coarse is not None:
            mask = coarse
        else:
            wm = kmasks.window_mask_np(
                setup["starts"], setup["ends"], setup["counts"], setup["L"]
            )
            S, L = wm.shape
            pm = np.zeros((S, L), dtype=bool)
            needed = setup["needed"]
            for s in range(table.n_shards):
                check_deadline()
                sl = table.shard_slice(s)
                cols = table.shard_cols(needed, s)
                pm[s, : sl.stop - sl.start] = np.asarray(plan.compiled(cols, np))
            mask = wm & pm
        # band-bearing coarse masks evaluate at f32 on BOTH backends (so
        # device and host mean the same thing); the exact-f64 refine pass
        # always restores boundary exactness on candidates
        mask = self._apply_refine(plan, setup, mask)
        S, L = mask.shape
        if plan.hints.sampling and plan.hints.sample_by:
            key = plan.hints.sample_by
            if not table.has_column(key):
                raise KeyError(f"sample-by attribute {key!r} not found")
            col = table.col_sorted(key)
            if setup.get("sb_mode") == "hash":
                # backend parity: keys the DEVICE would hash-bucket are
                # hash-bucketed here too (same mixer, xp=numpy), so a
                # host fallback never changes which rows are sampled
                stacked = np.zeros((S, L), dtype=np.int32)
                for s in range(table.n_shards):
                    sl = table.shard_slice(s)
                    stacked[s, : sl.stop - sl.start] = col[sl]
                mask = kmasks.sampling_mask_by_key_hash(
                    mask, plan.hints.sampling, stacked,
                    config.SAMPLE_HASH_BUCKETS.to_int() or int(config.SAMPLE_HASH_BUCKETS.default), np,
                )
            else:
                # exact distinct-value codes for ANY dtype (float
                # truncation or object hashing would merge distinct keys)
                _, codes = np.unique(col, return_inverse=True)
                stacked = np.zeros((S, L), dtype=np.int64)
                for s in range(table.n_shards):
                    sl = table.shard_slice(s)
                    stacked[s, : sl.stop - sl.start] = codes[sl]
                mask = kmasks.sampling_mask_by_key(
                    mask, plan.hints.sampling, stacked
                )
        elif plan.hints.sampling:
            mask = kmasks.sampling_mask(mask, plan.hints.sampling, np)
        return mask

    def _apply_refine(self, plan: QueryPlan, setup, mask: np.ndarray) -> np.ndarray:
        """Exact-predicate refinement pass (FastFilterFactory.scala:395
        parity): re-evaluate the exact filter tree on coarse-true candidate
        rows using the host ``__wkt`` columns. Only clears mask bits, so
        fused visibility/window masks are preserved. Runs before sampling —
        the 1-in-n counter must see exact matches only."""
        ref = plan.compiled.refine
        if ref is None:
            return mask
        table = setup["table"]
        names = list(dict.fromkeys(
            list(plan.compiled.columns) + list(plan.compiled.refine_columns or [])
        ))
        for s in range(table.n_shards):
            check_deadline()
            sl = table.shard_slice(s)
            row = mask[s, : sl.stop - sl.start]
            if not row.any():
                continue
            idx = np.nonzero(row)[0]
            cols = table.shard_rows_cols(names, s, idx)
            keep = plan.compiled.refine_rows(cols, len(idx))
            row[idx[~keep]] = False
        return mask

    def _device_mask_and_agg(self, plan: QueryPlan, setup, agg_fn, agg_cols=(),
                             cache_key=None, apply_sampling=True, extra=(),
                             excise_band=True, site=None):
        """Run mask + aggregation in one jit. ``agg_fn(cols, mask, xp,
        *extra)`` — ``extra`` values are TRACED jit arguments (scalar query
        parameters like a kNN origin), so one compiled kernel serves every
        value instead of baking them in as constants.

        ``cache_key`` caches the jitted kernel on the plan so re-running the
        same plan (benchmarks, pagination) skips retracing."""
        import jax
        import jax.numpy as jnp

        table = setup["table"]
        with tracing.span("scan.device_put"):
            dev_cols = table.device_columns(
                tuple(setup["needed"]) + tuple(agg_cols), self._sharding()
            )
        L = setup["L"]
        compiled = plan.compiled
        # coarse-mask kernels must NOT sample: sampling runs once on the
        # host, AFTER refinement (the 1-in-n counter sees exact matches)
        sampling = plan.hints.sampling if apply_sampling else None
        sample_by = plan.hints.sample_by if apply_sampling else None
        sb_mode = setup["sb_mode"] if apply_sampling else None
        sb_off = setup["sb_off"]
        if sb_mode == "exact-span":
            sb_vocab = setup["sb_span_vocab"]
        else:
            sb_vocab = (
                len(self.store.dicts[sample_by])
                if sample_by and sample_by in self.store.dicts else 0
            )
        sb_buckets = config.SAMPLE_HASH_BUCKETS.to_int() or int(config.SAMPLE_HASH_BUCKETS.default)

        # Two caches with different lifetimes:
        # 1. the jitted kernel — reusable across API calls (same predicate
        #    text + auths, via cache_token), across time-partition tables
        #    of one store (same plan, same bucketed shapes), and across
        #    aggregate-cache cell queries. Keys are VERSION-STABLE: the
        #    compiled closure depends only on structure (shapes, predicate,
        #    sampling mode) plus the dictionary fingerprint (string codes
        #    are baked at compile time), so a store mutation never forces a
        #    recompile.
        # 2. the device-resident window arrays — strictly per (store,
        #    version): windows differ per partition and per mutation.
        token = plan.__dict__.get("cache_token")
        fn_cache = fn_key = None
        if cache_key is not None:
            K = setup["starts"].shape[1]
            if token is not None:
                fn_cache = self.kernel_registry()
                fn_key = (cache_key, L, K, sampling, sample_by, sb_mode,
                          sb_off, sb_vocab, sb_buckets, token,
                          plan.index_name, self._dict_fp())
            else:  # raw-IR plan: cache on the plan (shared across partitions)
                fn_cache = self._plan_registry(plan)
                fn_key = (cache_key, L, K, sampling, sample_by, sb_mode,
                          sb_off, sb_vocab, sb_buckets)
            self._note(plan, shape_bucket=(L, K))
        go = fn_cache.get(fn_key) if fn_cache is not None else None
        site = site or (str(cache_key[0]) if cache_key else "agg")
        if go is None:

            def go(cols, starts, ends, counts, extra):
                m = kmasks.window_mask(starts, ends, counts, L)
                m = m & compiled(cols, jnp)
                if compiled.band is not None and excise_band:
                    # excise f32-uncertain rows: the kernel result is then
                    # exact over every row it counts; the few band rows are
                    # added back host-side from their f64 values. COARSE
                    # masks keep them (they are the refinement candidates).
                    m = m & ~compiled.band(cols, jnp)
                if sampling and sample_by and sb_mode == "hash":
                    m = kmasks.sampling_mask_by_key_hash(
                        m, sampling, cols[sample_by], sb_buckets, jnp
                    )
                elif sampling and sample_by:
                    m = kmasks.sampling_mask_by_key_device(
                        m, sampling, cols[sample_by] - sb_off, sb_vocab,
                        jnp
                    )
                elif sampling:
                    m = kmasks.sampling_mask(m, sampling, jnp)
                return agg_fn(cols, m, jnp, *extra)

            go = jax.jit(_named(go, f"padded_{site}"))
            if fn_cache is not None:
                fn_cache.put(fn_key, go)
                self._note(plan, kernel="trace")
        elif fn_cache is not None:
            self._note(plan, kernel="hit")
        # pre-placed window arrays: repeated same-plan runs (pagination,
        # benchmarks) shouldn't re-upload per call — host link latency can
        # dwarf the kernel. Unlike the jitted fn, window DATA is plan- and
        # store-specific: token-less fn_keys carry no plan identity, so
        # their windows must live on the plan (keyed by store uid), never
        # in a store-level cache another plan could hit.
        win = None
        if fn_key is not None:
            # window_token lets plans that share a kernel but differ in
            # their scan windows (knn radius expansion) key window arrays
            # separately without forcing a retrace
            wtoken = plan.__dict__.get("window_token", token)
            wcache = caches_of(
                self.store if token is not None else plan
            ).device_windows
            wkey = (fn_key, wtoken, self.store.uid, self.store.version,
                    self._devkey())
            win = wcache.get(wkey)
        if win is None:
            win = (
                self._put(setup["starts"]),
                self._put(setup["ends"]),
                self._put(setup["counts"]),
            )
            if fn_key is not None:
                wcache.put(wkey, win)
        d_starts, d_ends, d_counts = win
        from geomesa_tpu.kernels import pallas_kernels as pk

        # trace-time context: under a sharded mesh, polygon pallas kernels
        # re-dispatch through an inner shard_map over the mesh (bare
        # pallas_call has no GSPMD partitioning rule)
        with pk.sharded_execution(self.mesh), \
                self._kernel_span(site, setup,
                                  rows=int(table.n_shards) * L):
            return go(dev_cols, d_starts, d_ends, d_counts, tuple(extra))

    def _sharding(self):
        if self.mesh is None:
            if self.device is None:
                return None
            # process-wide singleton per device: the prefetch thread's
            # device_put overlap must present the SAME sharding object
            # (device_columns keys its cache by id(sharding))
            from geomesa_tpu.parallel.devices import device_sharding

            return device_sharding(self.device)
        # cached: device_columns keys its upload cache by id(sharding), so a
        # fresh NamedSharding per call would re-upload every column per query
        sh = self.__dict__.get("_sharding_cache")
        if sh is None:
            from jax.sharding import NamedSharding, PartitionSpec

            sh = NamedSharding(self.mesh, PartitionSpec("shard", None))
            self.__dict__["_sharding_cache"] = sh
        return sh

    def _put(self, x):
        """``jax.device_put`` honoring the executor's device pin (window
        arrays, compact descriptors, density schedules — operands that are
        NOT mesh-sharded; mesh placements keep their own shardings)."""
        import jax

        if self.mesh is None and self.device is not None:
            return jax.device_put(x, self._sharding())
        return jax.device_put(x)

    def _devkey(self):
        """Cache-key component for device-RESIDENT data (window arrays,
        compact slabs, schedules): a pinned executor must never hit
        another device's arrays — mixing committed devices in one jit is
        an error. Compiled-KERNEL keys deliberately omit it (one trace
        serves every device)."""
        return None if self.device is None else self.device.id

    # -- bin-space (sequence) parallelism ---------------------------------
    def _binspace_mesh(self):
        """The mesh, when it has a 'bin' axis (time-bin sequence axis)."""
        m = self.mesh
        if m is not None and "bin" in m.axis_names and "shard" in m.axis_names:
            return m
        return None

    def _binspace_run(self, plan: QueryPlan, setup, agg_fn, agg_cols,
                      cache_key):
        """Additive aggregate over the 2-D (shard, bin) mesh; None if the
        layout does not fit (caller falls through to the GSPMD path)."""
        from geomesa_tpu.parallel import binspace

        mesh = self._binspace_mesh()
        table = setup["table"]
        if (
            mesh is None
            or plan.hints.sampling  # sampling's running index is global
            or table.n_shards % mesh.shape["shard"] != 0
        ):
            return None
        import jax

        stream = config.BIN_STREAM_CHUNKS.to_int() or 1
        n_bin = mesh.shape["bin"]
        starts, ends = binspace.pad_windows(
            setup["starts"], setup["ends"], n_bin * stream
        )
        # cached shardings: device_columns keys its upload cache by
        # id(sharding) — fresh NamedShardings would re-upload per query
        sh = self.__dict__.get("_binspace_placements")
        if sh is None:
            sh = binspace.placements(mesh)
            self.__dict__["_binspace_placements"] = sh
        col_sh, win_sh, cnt_sh = sh
        names = tuple(dict.fromkeys(list(setup["needed"]) + list(agg_cols)))
        dev_cols = table.device_columns(names, col_sh)
        L = setup["L"]
        token = plan.__dict__.get("cache_token")
        if token is not None and cache_key is not None:
            cache = self.kernel_registry()
            key = ("binspace", cache_key, L, starts.shape[1], stream, token,
                   plan.index_name, self._dict_fp())
        else:  # token-less plan: cache on the plan (pagination, benchmarks)
            cache = self._plan_registry(plan)
            key = ("binspace", cache_key, L, starts.shape[1], stream)
        fn = cache.get(key)
        if fn is None:
            compiled = plan.compiled
            if compiled.band is not None:
                # same band excision as the GSPMD kernel: binspace counts
                # only f32-certain rows; the correction adds the rest
                inner_fn, inner_band = compiled.fn, compiled.band

                def predicate(cols, xp):
                    return inner_fn(cols, xp) & ~inner_band(cols, xp)
            else:
                predicate = compiled
            fn = binspace.build_bin_parallel(
                mesh, sorted(dev_cols), L, predicate, agg_fn, stream
            )
            cache.put(key, fn)
        with self._kernel_span(str(cache_key[0]) if cache_key else None,
                               setup, rows=int(table.n_shards) * L):
            return fn(
                {k: dev_cols[k] for k in sorted(dev_cols)},
                jax.device_put(starts.astype(np.int32), win_sh),
                jax.device_put(ends.astype(np.int32), win_sh),
                jax.device_put(setup["counts"].astype(np.int32), cnt_sh),
            )

    def _density_ladder(self, setup, bbox, width, height):
        """The density kernel ladder's choice for one view of the compact
        layout: ``(kernel, schedule, P, C)``, from what it observes.
        ``grouped`` (the pallas kernel) when pallas runs here and the
        view's ``TILE``-square (chunk, tile) candidates P are within
        ``MAX_DUP`` times its real chunks C; else ``mxu`` (the XLA einsum
        pair kernel); else ``scatter``, with no schedule (when the index
        has no morton key). Built on the host and its arrays placed on the
        device once per (windows, grid, store version, device pin), in a
        ``scan.schedule`` span; a cache hit returns the same P and C."""
        from geomesa_tpu.kernels import density_mxu as _dm
        from geomesa_tpu.kernels import density_pallas as _dp
        from geomesa_tpu.kernels import pallas_kernels as pk

        d = setup["compact"]
        table = setup["table"]
        pallas = pk.use_pallas()
        caches = caches_of(self.store)
        key = (d["whash"], tuple(bbox), width, height, d["B"], d["C"],
               pallas, self.store.uid, self.store.version, self._devkey())
        hit = caches.ladder.get(key)
        if hit is not None:
            return hit
        with tracing.span("scan.schedule") as sp:
            boxes = caches.chunk_boxes
            args = (d, table, table.keyspace, bbox, width, height)
            cand = _dm.pair_candidates(*args, _dp.TILE, _dp.TILE, boxes,
                                       self.store.version)
            # budget against the REAL chunk count: len(valid) is the
            # ladder8-padded count, which would loosen it by up to ~25%
            C = int((d["valid"] > 0).sum())
            P = 0 if cand is None else int(cand["P"])
            kernel, sched, put = "scatter", None, ()
            if cand is not None and pallas and P <= _dp.MAX_DUP * C:
                kernel, sched = "grouped", _dp.build_grouped(cand, d["B"])
                put = ("sc", "row", "tile", "ox", "oy")
            elif cand is not None:
                sched = _dm.build_pairs(*args, box_cache=boxes,
                                        version=self.store.version)
                if sched is not None:
                    kernel = "mxu"
                    put = ("chunk", "px0", "py0", "tile", "pvalid")
            for k in put:
                sched[k] = self._put(sched[k])
            sp.set(kernel=kernel, pairs=P, chunks=C)
        return caches.ladder.put(key, (kernel, sched, P, C))

    def _kernel_span(self, site, setup=None, **attrs):
        """The ``scan.kernel`` span of one executor kernel dispatch, after
        counting it (``exec.device.dispatch``, one observable unit of
        device work that the serving fusion gate counts; docs/SERVING.md)
        and opening its device busy stamp, which stays open until the host
        holds the result (the device.busy.<id> gauge and the per-query
        device_ms cost). A density dispatch also counts
        ``exec.density.kernel.<kernel>`` and names on its span the ladder's
        ``density_kernel`` with the ``pairs`` and ``chunks`` its budget
        tested (``scatter``, 0, 0 where the ladder did not run: the padded,
        mesh and fused batch paths)."""
        metrics.inc(metrics.EXEC_DEVICE_DISPATCH)
        utilization.dispatched(self._devkey() or 0)
        if site in ("density", "density_batch"):
            kernel, pairs, chunks = ((setup or {}).get("density_kernel")
                                     or ("scatter", 0, 0))
            metrics.inc(f"{metrics.EXEC_DENSITY_KERNEL}.{kernel}")
            attrs.update(density_kernel=kernel, pairs=pairs, chunks=chunks)
        return tracing.span("scan.kernel", site=site, **attrs)

    @staticmethod
    def _note(plan: QueryPlan, **kw) -> None:
        """Record which execution path served (part of) this query in
        ``plan.exec_path`` — surfaced by explain(analyze=True) and the
        audit log so silent fallbacks (device -> host, pallas -> XLA,
        mesh -> single-chip) are visible per query instead of only as a
        perf cliff."""
        plan.__dict__.setdefault("exec_path", {}).update(kw)

    def _run(self, plan: QueryPlan, agg_fn_dev, agg_fn_host, agg_cols=(),
             cache_key=None, additive=False, extra=(), compactable=True,
             compact_agg=None, band_merge=None, site=None):
        check_deadline()
        setup = self._scan_setup(plan, agg_cols)
        if setup is None:
            return None
        self._note(
            plan,
            sampling=setup["sb_mode"] if plan.hints.sample_by else None,
            mesh=(None if self.mesh is None
                  else dict(zip(self.mesh.axis_names,
                                self.mesh.devices.shape))),
        )
        from geomesa_tpu.kernels import pallas_kernels as _pk

        _pk.take_dispatch()  # drop records a prior query's trace left
        try:
            return self._run_inner(
                plan, setup, agg_fn_dev, agg_fn_host, agg_cols, cache_key,
                additive, extra, compactable, compact_agg, band_merge, site,
            )
        finally:
            disp = _pk.take_dispatch()
            if disp:
                self._note(plan, **{f"kernel:{k}": v
                                    for k, v in disp.items()})

    def _run_inner(self, plan, setup, agg_fn_dev, agg_fn_host, agg_cols,
                   cache_key, additive, extra, compactable, compact_agg,
                   band_merge, site):
        corr = None
        band_rows = 0
        if setup["use_device"] and plan.compiled.band is not None:
            info = self._band_info(plan, setup)
            band_rows = 0 if info is None else len(info)
            if band_rows:
                if (additive or band_merge) and not plan.hints.sampling:
                    # device aggregates the certain rows; the band rows'
                    # exact f64 contribution merges in (added, or through
                    # the caller's ``band_merge`` for non-additive partials)
                    corr = self._band_correction(
                        plan, setup, info, agg_fn_host, agg_cols, extra
                    )
                else:
                    setup["use_device"] = False  # exact host evaluation
        merge = band_merge or (lambda a, b: a + b)
        if setup["use_device"]:
            if additive:
                try:
                    out = self._binspace_run(
                        plan, setup, agg_fn_dev, agg_cols, cache_key
                    )
                    if out is not None:
                        self._note(plan, scan="device-binspace",
                                   band_rows=band_rows)
                        return out if corr is None else merge(out, corr)
                except Exception as e:
                    if os.environ.get("GEOMESA_TPU_STRICT_DEVICE"):
                        raise
                    # binspace-specific failure: the 1-D GSPMD device path
                    # below is still viable — don't drop to the host runner
                    logging.getLogger(__name__).warning(
                        "binspace scan failed, trying GSPMD path: %r", e
                    )
            try:
                if additive and compactable and self.mesh is not None:
                    out = self._compact_mesh_run(
                        plan, setup, agg_fn_dev, agg_cols, cache_key, extra
                    )
                    if out is not None:
                        self._note(plan, scan="device-compact-mesh",
                                   band_rows=band_rows)
                        return out if corr is None else merge(out, corr)
                self._maybe_compact(plan, setup, compactable)
                if setup["compact"] is not None:
                    agg_use, extra_use, ckey = agg_fn_dev, extra, cache_key
                    if compact_agg is not None:
                        alt = compact_agg(setup)
                        if alt is not None:
                            agg_use, alt_extra, suffix = alt
                            extra_use = tuple(extra) + tuple(alt_extra)
                            ckey = (cache_key or ()) + suffix
                    out = self._device_compact_agg(
                        plan, setup, agg_use, agg_cols, ckey,
                        extra=extra_use, site=site,
                    )
                    self._note(plan, scan="device-compact",
                               B=setup["compact"]["B"], band_rows=band_rows)
                else:
                    out = self._device_mask_and_agg(
                        plan, setup, agg_fn_dev, agg_cols, cache_key,
                        extra=extra, site=site,
                    )
                    self._note(plan, scan="device-padded",
                               band_rows=band_rows)
                return out if corr is None else merge(out, corr)
            except Exception as e:
                if os.environ.get("GEOMESA_TPU_STRICT_DEVICE"):
                    raise
                # graceful degradation (the reference's remoteFilter=false /
                # Bigtable path): fall back to the host runner — loudly, so a
                # permanent fallback is never an invisible perf cliff
                logging.getLogger(__name__).warning(
                    "device scan failed, falling back to host: %r", e
                )
                self._note(plan, device_error=repr(e)[:200])
        coarse = self._coarse_or_none(plan, setup)
        self._note(
            plan,
            scan=("host+device-coarse" if coarse is not None else "host"),
            band_rows=band_rows,
        )
        with tracing.span("scan.host"):
            mask = self._host_mask(plan, setup, coarse)
            table = setup["table"]
            cols = {}
            for c in set(list(setup["needed"]) + list(agg_cols)):
                if table.has_column(c):
                    L = setup["L"]
                    full = table.col_sorted(c)
                    stacked = np.zeros((table.n_shards, L), dtype=full.dtype)
                    for s in range(table.n_shards):
                        sl = table.shard_slice(s)
                        stacked[s, : sl.stop - sl.start] = full[sl]
                    cols[c] = stacked
            return agg_fn_host(cols, mask, np, *extra)

    # -- public operations --------------------------------------------------
    def count_partial(self, plan: QueryPlan):
        """:meth:`count` WITHOUT the device sync: the additive partial
        (device scalar or host value; None = empty scan) the sharded
        partitioned scan merges after every device has been dispatched."""
        return self._run(
            plan,
            lambda cols, m, xp: m.sum(),
            lambda cols, m, xp: m.sum(),
            cache_key=("count",),
            additive=True,
        )

    def count(self, plan: QueryPlan) -> int:
        out = self.count_partial(plan)
        if out is None:
            return 0
        with _sync_span():
            return int(out)

    def features(self, plan: QueryPlan) -> ColumnBatch:
        """Matching rows as a host ColumnBatch (sort/limit applied by caller)."""
        setup = self._scan_setup(plan)
        if setup is None:
            return ColumnBatch({}, 0)
        mask = None
        band_clean = True
        if setup["use_device"] and plan.compiled.band is not None:
            info = self._band_info(plan, setup)
            band_clean = info is None or len(info) == 0
        if setup["use_device"] and band_clean:
            try:
                self._maybe_compact(plan, setup, True)
                if setup["compact"] is not None:
                    cmask = self._device_compact_agg(
                        plan, setup, lambda cols, m, xp: m,
                        cache_key=("mask",),
                    )
                    with _sync_span():
                        mask = self._expand_compact_mask(setup, cmask)
                else:
                    dmask = self._device_mask_and_agg(
                        plan, setup, lambda cols, m, xp: m,
                        cache_key=("mask",),
                    )
                    with _sync_span():
                        mask = np.asarray(dmask)
            except Exception as e:
                if os.environ.get("GEOMESA_TPU_STRICT_DEVICE"):
                    raise
                # same graceful degradation as _run(): loud host fallback
                logging.getLogger(__name__).warning(
                    "device scan failed, falling back to host: %r", e
                )
        if mask is None:
            mask = self._host_mask(
                plan, setup, self._coarse_or_none(plan, setup)
            )
        names = None
        if plan.hints.properties:
            # projection pushdown into the gather (ColumnGroups analog):
            # sort keys must survive for the caller's post-sort
            names = list(plan.hints.properties) + [
                a for a, _ in (plan.hints.sort_by or [])
            ]
        return setup["table"].host_gather(mask.reshape(-1), names)

    def features_iter(self, plan: QueryPlan, batch_rows: Optional[int] = None):
        """Matching rows as a stream of ColumnBatch chunks (ArrowScan's
        batched-yield contract, AggregatingScan.scala:82-116). A single
        table materializes its result once and re-slices it — the streaming
        value on an unpartitioned store is wire chunking, not peak memory."""
        batch_rows = batch_rows or int(
            os.environ.get("GEOMESA_ARROW_BATCH_ROWS", 1_000_000)
        )
        out = self.features(plan)
        n = out.n
        if plan.hints.max_features is not None and not plan.hints.sort_by:
            n = min(n, plan.hints.max_features)
        for lo in range(0, n, batch_rows):
            hi = min(lo + batch_rows, n)
            yield ColumnBatch(
                {k: v[lo:hi] for k, v in out.columns.items()}, hi - lo
            )

    def density(self, plan: QueryPlan, bbox, width: int, height: int,
                weight: Optional[str] = None, as_numpy: bool = True):
        """Density grid. ``as_numpy=False`` leaves the grid on device (no
        host transfer) — for benchmark loops and device-side composition."""
        geom = self.store.ft.geom_field
        xc, yc = geom + "__x", geom + "__y"
        agg_cols = [xc, yc] + ([weight] if weight else [])

        def agg(cols, m, xp):
            w = cols.get(weight) if weight else None
            return kdensity.density_grid(
                cols[xc], cols[yc], m, bbox, width, height, w, xp
            )

        def mxu_agg(setup):
            # device kernel ladder over the compacted layout: pallas
            # grouped one-hot matmul (kernels/density_pallas.py), the XLA
            # einsum pair kernel (kernels/density_mxu.py), or the scatter
            # agg (None)
            kernel, sched, P, C = self._density_ladder(setup, bbox, width,
                                                       height)
            setup["density_kernel"] = (kernel, P, C)
            if kernel == "grouped":
                self._note(plan, density_kernel="pallas-grouped-mxu")
                from geomesa_tpu.kernels import density_pallas as kdp

                Bc, n_pairs = sched["B"], sched["n_pairs"]
                gntx, gnty = sched["ntx"], sched["nty"]

                def gagg(cols, m, xp, sc, row, tile, ox, oy):
                    return kdp.density_grid_grouped(
                        cols[xc], cols[yc], m, bbox, width, height,
                        cols.get(weight) if weight else None,
                        sc, row, tile, ox, oy,
                        Bc, gntx, gnty, n_pairs,
                    )

                extra = tuple(sched[k] for k in ("sc", "row", "tile", "ox",
                                                 "oy"))
                return gagg, extra, ("grouped", n_pairs, Bc, gntx, gnty)
            if kernel == "scatter":
                self._note(plan, density_kernel="scatter")
                return None
            self._note(plan, density_kernel="mxu-einsum")
            from geomesa_tpu.kernels import density_mxu as kmxu

            PB, ntx, nty = sched["PB"], sched["ntx"], sched["nty"]
            TY, TX = sched["TY"], sched["TX"]

            def pagg(cols, m, xp, pc, p0, p1, pt, pv):
                return kmxu.density_grid_pairs(
                    cols[xc], cols[yc], m, bbox, width, height,
                    cols.get(weight) if weight else None,
                    pc, p0, p1, pt, pv, PB, ntx, nty, TY, TX, xp,
                )

            extra = tuple(sched[k] for k in ("chunk", "px0", "py0", "tile",
                                             "pvalid"))
            return pagg, extra, ("mxu", sched["P"], PB, TX, TY)

        out = self._run(
            plan, agg, agg, agg_cols,
            cache_key=("density", tuple(bbox), width, height, weight),
            additive=True,
            compact_agg=mxu_agg,
        )
        if out is None:
            return np.zeros((height, width), np.float32)
        if not as_numpy:
            return out
        with _sync_span():
            return np.asarray(out)

    # -- curve-aligned density (the index-native heatmap) ------------------
    def _curve_positions(self, plan: QueryPlan, level: int, block_window):
        """Host-side: padded-flat CDF positions of every morton block in the
        crop window. Each level-``level`` block is ONE contiguous range of
        the z2-sorted order, so its masked count is a 2-gather CDF
        difference — no scatter. Cached per (store version, level, crop)."""
        table = self._table(plan)
        key = ("curve_pos", table.keyspace.name, self.store.version, level,
               tuple(block_window))
        cache = caches_of(self.store).curve_positions
        hit = cache.get(key)
        if hit is not None:
            return hit
        from geomesa_tpu.curves.zorder import interleave2

        ix0, iy0, ix1, iy1 = block_window
        nx, ny = ix1 - ix0 + 1, iy1 - iy0 + 1
        jj, ii = np.meshgrid(
            np.arange(iy0, iy1 + 1, dtype=np.uint64),
            np.arange(ix0, ix1 + 1, dtype=np.uint64),
            indexing="ij",
        )
        codes = interleave2(ii.ravel(), jj.ravel())
        shift_bits = 2 * (31 - level)
        z_lo = codes << np.uint64(shift_bits)
        z_hi = (codes + np.uint64(1)) << np.uint64(shift_bits)
        z_col = table.key_columns["__z2"]
        sh = 0 if table.key_shifts is None else table.key_shifts.get("__z2", 0)
        if sh > shift_bits:
            raise ValueError(
                f"z2 keys quantized below level {level} blocks "
                f"(shift {sh} > {shift_bits}); use the scatter density path"
            )
        g0 = np.searchsorted(z_col, (z_lo >> np.uint64(sh)).astype(z_col.dtype))
        g1 = np.searchsorted(z_col, (z_hi >> np.uint64(sh)).astype(z_col.dtype))
        # global sorted position -> padded [S, L] flat position
        bounds = table.shard_bounds
        L = table.shard_len

        def pad_pos(g):
            s = np.clip(
                np.searchsorted(bounds, g, side="right") - 1,
                0, table.n_shards - 1,
            )
            return (s * L + (g - bounds[s])).astype(np.int32)

        p0, p1 = pad_pos(g0), pad_pos(g1)
        # pad the block count to a pow2 bucket so one compiled kernel
        # serves every crop of similar size (padding diffs are 0)
        B = len(p0)
        Bp = 1 << max(B - 1, 0).bit_length()
        if Bp != B:
            p0 = np.concatenate([p0, np.zeros(Bp - B, np.int32)])
            p1 = np.concatenate([p1, np.zeros(Bp - B, np.int32)])
        return cache.put(key, (p0, p1, B, nx, ny))

    def density_curve_raw(self, plan: QueryPlan, level: int, block_window,
                          weight: Optional[str] = None):
        """:meth:`density_curve` WITHOUT the final host transfer:
        ``(partial_or_None, B, nx, ny)``. The sharded partitioned scan
        dispatches one of these per partition (each async, on its own
        device) and decodes via :meth:`decode_curve` only after every
        device is busy."""
        p0, p1, B, nx, ny = self._curve_positions(plan, level, block_window)
        agg_cols = [weight] if weight else []

        def agg(cols, m, xp, p0_, p1_):
            if weight is None:
                w = m.reshape(-1).astype(xp.int32)
            else:
                w = xp.where(
                    m.reshape(-1),
                    cols[weight].reshape(-1).astype(xp.float32),
                    xp.float32(0),
                )
            c = xp.concatenate([xp.zeros(1, w.dtype), xp.cumsum(w)])
            # counts stay int32 end-to-end: an f32 cast here would round
            # blocks holding >2^24 rows
            return c[p1_] - c[p0_]

        out = self._run(
            plan, agg, agg, agg_cols,
            cache_key=("density_curve", level, len(p0), weight),
            extra=(p0, p1),
            compactable=False,  # CDF positions index the padded layout
        )
        return out, B, nx, ny

    @staticmethod
    def decode_curve(raw) -> np.ndarray:
        """One :meth:`density_curve_raw` partial as the host f64 grid
        (zeros for an empty partial) — the per-partition decode the
        partitioned merge runs in pruned-bin order, identically on the
        serial and sharded paths."""
        out, B, nx, ny = raw
        if out is None:
            return np.zeros((ny, nx), np.float64)
        # float64 grid: cell counts are exact to 2^53 (an f32 grid would
        # round cells beyond 2^24 rows); weighted cells carry the f32
        # accumulation documented in density_curve_raw
        flat = np.asarray(out)[:B].astype(np.float64)
        # blocks were generated row-major over (j, i): reshape directly;
        # row 0 = ymin edge (RenderingGrid convention)
        return flat.reshape(ny, nx)

    def density_curve(self, plan: QueryPlan, level: int, block_window,
                      weight: Optional[str] = None) -> np.ndarray:
        """Exact density over a morton-block-aligned grid (XYZ/EPSG:4326
        tile pyramids align by construction): masked counts via one cumsum
        over the z2-sorted scan + two gathers per block. At 20M rows this
        is ~25x faster than the scatter path, because TPU scatter costs
        ~6.7 ns/row while cumsum runs at bandwidth (docs/SCALE.md).
        Unweighted counts accumulate in int32 (exact to 2^31 rows);
        weighted densities accumulate in f32."""
        return self.decode_curve(
            self.density_curve_raw(plan, level, block_window, weight)
        )

    def density_curve_batch_raw(self, plan: QueryPlan, level: int,
                                block_windows, weight: Optional[str] = None):
        """N curve-aligned density crops of ONE (plan, level) in a single
        device pass — the cross-query fusion entry point (docs/SERVING.md):
        concurrent tile clients share the mask + cumsum (the expensive
        O(rows) work) and each member costs only its own CDF gathers,
        stacked over the query axis as ``[M, P]`` position operands.

        Per-member results are bit-identical to :meth:`density_curve` run
        serially: the shared cumsum is the same array either way, and
        ``c[p1] - c[p0]`` gathers are exact. The kernel registry key pads
        the member axis to a power of two (``registry.bucket_batch``) next
        to the usual version-stable token, so batch sizes in one bucket
        share a compiled kernel. Returns the UNSYNCED ``(partial, infos)``
        pair (the sharded partitioned scan merges these across devices);
        :meth:`density_curve_batch` is the synchronous public form."""
        from geomesa_tpu.kernels.registry import bucket_batch

        infos = [
            self._curve_positions(plan, level, bw) for bw in block_windows
        ]
        if not infos:
            return None, []
        # stack the per-member CDF positions: members pad to a common P
        # (each is already pow2-padded, so P = max is a pow2) and the
        # member axis pads to its batch bucket. Padded cells gather
        # c[0] - c[0] = 0 and are sliced away below.
        P = max(len(i[0]) for i in infos)
        M = len(infos)
        Mp = bucket_batch(M)
        p0s = np.zeros((Mp, P), np.int32)
        p1s = np.zeros((Mp, P), np.int32)
        for i, (p0, p1, _B, _nx, _ny) in enumerate(infos):
            p0s[i, : len(p0)] = p0
            p1s[i, : len(p1)] = p1
        agg_cols = [weight] if weight else []

        def agg(cols, m, xp, p0_, p1_):
            if weight is None:
                w = m.reshape(-1).astype(xp.int32)
            else:
                w = xp.where(
                    m.reshape(-1),
                    cols[weight].reshape(-1).astype(xp.float32),
                    xp.float32(0),
                )
            # ONE cumsum serves every member; the [M, P] gather pair is
            # the only per-member work (same int32 exactness contract as
            # density_curve)
            c = xp.concatenate([xp.zeros(1, w.dtype), xp.cumsum(w)])
            return c[p1_] - c[p0_]

        out = self._run(
            plan, agg, agg, agg_cols,
            cache_key=("density_curve_batch", level, P, Mp, weight),
            extra=(p0s, p1s),
            compactable=False,  # CDF positions index the padded layout
        )
        return out, infos

    @staticmethod
    def decode_curve_batch(raw):
        """One :meth:`density_curve_batch_raw` partial as per-member host
        f64 grids (the per-partition decode of the sharded merge)."""
        out, infos = raw
        results = []
        arr = None if out is None else np.asarray(out)
        for i, (_p0, _p1, B, nx, ny) in enumerate(infos):
            if arr is None:
                results.append(np.zeros((ny, nx), np.float64))
            else:
                results.append(
                    arr[i, :B].astype(np.float64).reshape(ny, nx)
                )
        return results

    def density_curve_batch(self, plan: QueryPlan, level: int,
                            block_windows, weight: Optional[str] = None):
        """See :meth:`density_curve_batch_raw` — this is the synchronous
        public form, one ``[ny, nx]`` float64 grid per window, in order."""
        return self.decode_curve_batch(
            self.density_curve_batch_raw(plan, level, block_windows, weight)
        )

    def density_curve_filter_batch_raw(self, plans, spec, level: int,
                                       block_windows,
                                       weight: Optional[str] = None):
        """M DISTINCT-filter curve crops of one structural template in a
        single device dispatch (docs/SERVING.md "Query-axis batching",
        extended to the curve path): each member carries its OWN viewport
        literals (kernel data via ``spec``) AND its own crop window
        (stacked CDF gather positions). Unlike :meth:`density_curve_batch`
        — which shares one mask + cumsum across crops of ONE filter —
        every member here pays its own masked cumsum, but all M ride one
        kernel launch and one column residency. Per-member math is
        op-for-op the serial :meth:`density_curve` kernel (batched
        window_mask + literal-parameterized compare, then the identical
        int32/f32 cumsum + 2-gather CDF), so de-interleaved grids are
        bit-identical to query-at-a-time execution. Returns the unsynced
        ``(partials_or_None, infos)`` pair, or None when ineligible
        (caller degrades to per-member serial execution); members with
        surviving f32 band rows keep the serial path (band corrections
        are per-block additive host work the batch does not carry)."""
        check_deadline()
        agg_cols = [weight] if weight else []
        bs = self._batch_setups(plans, spec, agg_cols)
        if bs is None:
            return None
        infos = [
            self._curve_positions(plans[0], level, bw)
            for bw in block_windows
        ]
        if bs["empty"]:
            return (None, infos)
        # any member with SURVIVING f32 band rows keeps the serial path:
        # its correction is per-block additive host work this batch does
        # not carry (same posture as stats_batch)
        for plan, su in zip(plans, bs["setups"]):
            if su is None or plan.compiled.band is None:
                continue
            info = self._band_info(plan, su)
            if info is not None and len(info):
                return None
        P = max(len(i[0]) for i in infos)
        Mp = bs["Mp"]
        p0s = np.zeros((Mp, P), np.int32)
        p1s = np.zeros((Mp, P), np.int32)
        for m, (p0, p1, _B, _nx, _ny) in enumerate(infos):
            p0s[m, : len(p0)] = p0
            p1s[m, : len(p1)] = p1

        def member_agg(m, cols, mm, xp, p0_, p1_):
            if weight is None:
                w = mm.reshape(-1).astype(xp.int32)
            else:
                w = xp.where(
                    mm.reshape(-1),
                    cols[weight].reshape(-1).astype(xp.float32),
                    xp.float32(0),
                )
            # per-member cumsum (distinct masks), same exactness contract
            # as the serial density_curve kernel
            c = xp.concatenate([xp.zeros(1, w.dtype), xp.cumsum(w)])
            return c[p1_[m]] - c[p0_[m]]

        out = self._batch_device_agg(
            plans, spec, bs, member_agg, agg_cols,
            "density_curve_filter_batch", key_extras=(level, P, weight),
            extra_arrays=(p0s, p1s),
        )
        return (out, infos)

    @staticmethod
    def decode_curve_filter_batch(raw):
        """One :meth:`density_curve_filter_batch_raw` partial as
        per-member host f64 grids (the partitioned merge's decode)."""
        got, infos = raw
        results = []
        for m, (_p0, _p1, B, nx, ny) in enumerate(infos):
            if got is None:
                results.append(np.zeros((ny, nx), np.float64))
            else:
                results.append(
                    np.asarray(got[m])[:B].astype(np.float64).reshape(ny, nx)
                )
        return results

    def density_curve_filter_batch(self, plans, spec, level: int,
                                   block_windows,
                                   weight: Optional[str] = None):
        """M distinct-filter curve grids in one device dispatch (None =
        ineligible). Each member's grid equals its serial
        :meth:`density_curve` exactly — the CI-gated contract."""
        got = self.density_curve_filter_batch_raw(
            plans, spec, level, block_windows, weight
        )
        if got is None:
            return None
        return self.decode_curve_filter_batch(got)

    # -- query-axis batched aggregates (docs/SERVING.md "Query-axis
    # batching"): M *distinct* viewports in ONE device dispatch. The
    # batched kernel bakes the predicate SHAPE (the structural template's
    # residual + slot layout) but not the viewport literals — those ride
    # as [Mp, nf]/[Mp, ni] traced arrays — and the member axis pads to its
    # registry bucket (registry.bucket_batch), so batch sizes 3, 5, 7
    # share one compiled kernel at Mp=8 and a panning client never
    # recompiles. Each member's mask is op-for-op its serial kernel
    # (unrolled member loop, batched window_mask + literal-parameterized
    # compare with the identical f32/int32 values), so de-interleaved
    # results are bit-identical to query-at-a-time execution — the
    # CI-gated contract.
    def _batch_setups(self, plans, spec, agg_cols=()):
        """Per-member scan setups + stacked windows for one batch, or
        None when the batch cannot ride the device kernel (caller falls
        back to per-member serial execution). ``spec`` is the
        planning/batch.BatchSpec the API layer built."""
        if self.mesh is not None or not self.prefer_device:
            return None
        setups = []
        table = None
        for plan in plans:
            if plan.hints.sampling or plan.hints.sample_by:
                return None
            su = self._scan_setup(plan, agg_cols)
            if su is None:
                # empty member (disjoint key plan) or empty table: zero
                # windows, zero partial — uniform with serial zeros
                plan.__dict__.setdefault("scanned_rows", 0)
                plan.__dict__.setdefault("table_rows", 0)
                setups.append(None)
                continue
            if not su["use_device"] or su["sb_mode"] is not None:
                return None
            t = su["table"]
            if table is None:
                table = t
            elif t is not table:
                return None
            setups.append(su)
        if table is None:  # every member empty
            return {"empty": True, "setups": setups}
        if any(p.__dict__.get("cache_token") is None for p in plans):
            return None
        from geomesa_tpu.kernels.registry import bucket_batch

        S, L = table.n_shards, table.shard_len
        K = max(
            (su["starts"].shape[1] for su in setups if su is not None),
            default=1,
        )
        Mp = bucket_batch(len(plans))
        starts = np.zeros((Mp, S, K), np.int32)
        ends = np.zeros((Mp, S, K), np.int32)
        for m, su in enumerate(setups):
            if su is None:
                continue
            k = su["starts"].shape[1]
            starts[m, :, :k] = su["starts"]
            ends[m, :, :k] = su["ends"]
        counts = np.diff(table.shard_bounds).astype(np.int32)
        return {
            "empty": False, "setups": setups, "table": table, "L": L,
            "K": K, "Mp": Mp, "starts": starts, "ends": ends,
            "counts": counts,
        }

    def _batch_band_corrs(self, plans, bs, agg_fn_host, agg_cols,
                          extras=None):
        """Per-member exact f32-band corrections (None = member clean).
        The batched device kernel excises each member's band rows exactly
        like the serial kernel; this is the serial host-side correction,
        run per member off its own plan's compiled band."""
        corrs = []
        for m, (plan, su) in enumerate(zip(plans, bs["setups"])):
            if su is None or plan.compiled.band is None:
                corrs.append(None)
                continue
            info = self._band_info(plan, su)
            if info is None or len(info) == 0:
                corrs.append(None)
                continue
            extra = () if extras is None else extras[m]
            corrs.append(self._band_correction(
                plan, su, info, agg_fn_host, agg_cols, extra
            ))
        return corrs

    def _batch_device_agg(self, plans, spec, bs, member_agg, agg_cols,
                          site, key_extras=(), extra_arrays=()):
        """Mask + per-member aggregation in ONE jit over the stacked
        query axis. ``member_agg(m, cols, mm, xp, *extra_arrays)`` builds
        member ``m``'s partial from its mask (the loop unrolls at trace
        time — Mp is part of the kernel shape). Returns the UNSYNCED
        tuple of Mp partials."""
        import jax
        import jax.numpy as jnp

        table, L, K, Mp = bs["table"], bs["L"], bs["K"], bs["Mp"]
        bfn, bband = spec.bf.fn, spec.bf.band
        names = tuple(dict.fromkeys(
            list(spec.bf.columns) + list(agg_cols)
        ))
        fn_cache = self.kernel_registry()
        fn_key = ((site,) + tuple(key_extras), L, K, Mp, spec.token,
                  plans[0].index_name, self._dict_fp())
        go = fn_cache.get(fn_key)
        if go is None:

            @jax.jit
            def go(cols, starts, ends, counts, lf, li, extra):
                outs = []
                for m in range(Mp):
                    wm = kmasks.window_mask_batch(starts, ends, counts,
                                                  L, m)
                    mm = wm & bfn(cols, jnp, lf[m], li[m])
                    if bband is not None:
                        mm = mm & ~bband(cols, jnp, lf[m], li[m])
                    outs.append(member_agg(m, cols, mm, jnp, *extra))
                return tuple(outs)

            fn_cache.put(fn_key, go)
            for p in plans:
                self._note(p, kernel="trace")
        else:
            for p in plans:
                self._note(p, kernel="hit")
        with tracing.span("scan.device_put", batch=len(plans)):
            dev_cols = table.device_columns(names, self._sharding())
        wcache = caches_of(self.store).device_windows
        # keyed by the window BYTES, not their hash: a collision here
        # would silently serve another batch's scan ranges, and equality
        # is the correctness contract (the [Mp, S, K] arrays are far
        # smaller than the device windows this cache holds)
        wkey = ("batch_win", site, self.store.uid, self.store.version,
                K, Mp, bs["starts"].tobytes(), bs["ends"].tobytes(),
                self._devkey())
        win = wcache.get(wkey)
        if win is None:
            win = wcache.put(wkey, (self._put(bs["starts"]),
                                    self._put(bs["ends"]),
                                    self._put(bs["counts"])))
        for p in plans:
            self._note(p, scan="device-batch", batch=len(plans))
        # ONE observable unit of device work for the whole batch — the
        # distinct-fusion bench/CI gate counts these
        with self._kernel_span(site, batch=len(plans),
                               rows=int(table.n_shards) * L):
            return go(dev_cols, *win, spec.lits_f, spec.lits_i,
                      tuple(extra_arrays))

    def count_batch_partial(self, plans, spec):
        """Unsynced batched count: ``(partials_or_None, corrs)`` — one
        device scalar per member plus each member's exact band-row
        correction — or None when the batch is ineligible here (caller
        degrades to query-at-a-time)."""
        check_deadline()
        bs = self._batch_setups(plans, spec)
        if bs is None:
            return None
        corrs = [None] * len(plans)
        if bs["empty"]:
            return (None, corrs)
        corrs = self._batch_band_corrs(
            plans, bs, lambda cols, m, xp: m.sum(), ()
        )
        out = self._batch_device_agg(
            plans, spec, bs,
            lambda m, cols, mm, xp: mm.sum(),
            (), "count_batch",
        )
        return (out, corrs)

    def count_batch(self, plans, spec):
        """M distinct counts in one device dispatch (None = ineligible).
        Each member's value equals its serial :meth:`count` exactly."""
        got = self.count_batch_partial(plans, spec)
        if got is None:
            return None
        return self.decode_count_batch(got, len(plans))

    @staticmethod
    def decode_count_batch(got, n: int):
        """One :meth:`count_batch_partial` result as per-member host ints
        (the per-partition decode of the partitioned merge)."""
        out, corrs = got
        totals = []
        arr = None if out is None else [np.asarray(o) for o in out]
        for m in range(n):
            v = 0 if arr is None else int(arr[m])
            if corrs[m] is not None:
                v += int(corrs[m])
            totals.append(v)
        return totals

    def density_batch_partial(self, plans, spec, bboxes, width: int,
                              height: int, weight=None):
        """Unsynced batched density: ``(grids_or_None, corrs)`` — one
        device [height, width] f32 grid per member over that member's OWN
        bbox (traced grid parameters: one compiled kernel serves every
        viewport) — or None when ineligible."""
        check_deadline()
        geom = self.store.ft.geom_field
        xc, yc = geom + "__x", geom + "__y"
        agg_cols = [xc, yc] + ([weight] if weight else [])
        bs = self._batch_setups(plans, spec, agg_cols)
        if bs is None:
            return None
        corrs = [None] * len(plans)
        if bs["empty"]:
            return (None, corrs)
        Mp = bs["Mp"]
        gp = np.zeros((Mp, 4), np.float32)
        gp[:, 2:] = 1.0  # padded members: benign nonzero spans
        for m, bb in enumerate(bboxes):
            gp[m] = kdensity.grid_params(bb)

        def host_agg(m):
            def agg(cols, msk, xp):
                w = cols.get(weight) if weight else None
                return kdensity.density_grid(
                    cols[xc], cols[yc], msk, tuple(bboxes[m]),
                    width, height, w, xp,
                )

            return agg

        corrs = self._batch_band_corrs(
            plans, bs,
            # the member index rides through extras so each band
            # correction rasterizes into ITS member's grid
            lambda cols, msk, xp, m: host_agg(m)(cols, msk, xp),
            agg_cols,
            extras=[(m,) for m in range(len(plans))],
        )

        def member_agg(m, cols, mm, xp, gp_):
            w = cols.get(weight) if weight else None
            return kdensity.density_grid_at(
                cols[xc], cols[yc], mm,
                gp_[m, 0], gp_[m, 1], gp_[m, 2], gp_[m, 3],
                width, height, w, xp,
            )

        out = self._batch_device_agg(
            plans, spec, bs, member_agg, agg_cols, "density_batch",
            key_extras=(width, height, weight), extra_arrays=(gp,),
        )
        return (out, corrs)

    def density_batch(self, plans, spec, bboxes, width: int, height: int,
                      weight=None):
        """M distinct heatmaps in one device dispatch (None = ineligible).
        Unweighted grids are bit-identical to serial :meth:`density` (the
        cell values are exact integer counts); weighted grids match the
        serial padded-scatter path op-for-op."""
        got = self.density_batch_partial(plans, spec, bboxes, width,
                                         height, weight)
        if got is None:
            return None
        return self.decode_density_batch(got, len(plans), width, height)

    @staticmethod
    def decode_density_batch(got, n: int, width: int, height: int):
        """One :meth:`density_batch_partial` result as per-member host
        f32 grids."""
        out, corrs = got
        grids = []
        for m in range(n):
            g = (np.zeros((height, width), np.float32) if out is None
                 else np.asarray(out[m]))
            if corrs[m] is not None:
                g = g + np.asarray(corrs[m], np.float32)
            grids.append(g)
        return grids

    def stats_batch_partials(self, plans, spec, stats):
        """Unsynced batched stats partials: one
        :func:`~geomesa_tpu.kernels.stats_scan.device_update` pytree list
        per member — or None when ineligible. The batch merges no band
        partials (the serial path merges each band-bearing member's exact
        host partial into its device one), so ANY member with surviving
        band rows makes the batch ineligible here; descriptive leaves are
        excluded by
        :func:`~geomesa_tpu.kernels.stats_scan.batch_supported`."""
        check_deadline()
        if any(not kstats.batch_supported(s) for s in stats):
            return None
        bundle = self._stats_bundle(plans[0], stats[0])
        if bundle is None:
            return None
        agg_cols, vocab_sizes = bundle
        bs = self._batch_setups(plans, spec, agg_cols)
        if bs is None:
            return None
        if bs["empty"]:
            return (None,)
        for plan, su in zip(plans, bs["setups"]):
            if su is None or plan.compiled.band is None:
                continue
            info = self._band_info(plan, su)
            if info is not None and len(info):
                return None  # the serial path merges its band partial

        def member_agg(m, cols, mm, xp):
            # padded members reuse member 0's structure (same spec text)
            st = stats[m] if m < len(stats) else stats[0]
            return kstats.device_update(st, cols, mm, xp, vocab_sizes)

        out = self._batch_device_agg(
            plans, spec, bs, member_agg, agg_cols, "stats_batch",
            # the stat STRUCTURE is baked into the traced update (leaf
            # kinds, bins, attributes): it must key the kernel, or a
            # Count() batch and a MinMax() batch of one template would
            # collide on one compiled kernel
            key_extras=(self._stat_signature(stats[0]),),
        )
        return (out,)

    @staticmethod
    def _stat_signature(stat: sk.Stat) -> tuple:
        """Trace-shape signature of a stat tree: everything
        :func:`~geomesa_tpu.kernels.stats_scan.device_update` bakes."""
        sig = []
        for leaf in kstats._leaf_stats(stat):
            if isinstance(leaf, sk.DescriptiveStats):
                attrs = tuple(leaf.attributes)
            else:
                attrs = (getattr(leaf, "attribute", None),)
            extra = ()
            if leaf.kind == "histogram":
                extra = (leaf.bins, leaf.lo, leaf.hi)
            elif leaf.kind == "topk":
                extra = (getattr(leaf, "k", None),)
            sig.append((leaf.kind, attrs, extra))
        return tuple(sig)

    def stats_batch(self, plans, spec, stats):
        """M distinct stats scans in one device dispatch (None =
        ineligible). Mutates and returns ``stats`` in member order."""
        got = self.stats_batch_partials(plans, spec, stats)
        if got is None:
            return None
        self.absorb_stats_batch(got, stats, self.store.dicts)
        return stats

    @staticmethod
    def absorb_stats_batch(got, stats, dicts) -> None:
        """Fold one :meth:`stats_batch_partials` result into the member
        Stat objects (the per-partition absorb of the partitioned merge,
        in member order)."""
        (out,) = got
        if out is None:
            return
        for m, st in enumerate(stats):
            kstats.absorb_partials(st, out[m], dicts)

    def _stats_bundle(self, plan: QueryPlan, stat: sk.Stat):
        """(agg_cols, vocab_sizes) when every leaf of ``stat`` can update
        on device over this table, else None (the gather path serves)."""
        table = self._table(plan)
        host_only = {
            c for c in table.column_names() if table.is_host_only(c)
        }
        vocab_sizes = {a: max(len(d), 1) for a, d in self.store.dicts.items()}
        leaf_attrs = []
        for leaf in kstats._leaf_stats(stat):
            if isinstance(leaf, sk.DescriptiveStats):
                leaf_attrs.extend(leaf.attributes)
            elif getattr(leaf, "attribute", None) is not None:
                leaf_attrs.append(leaf.attribute)
        agg_cols = []
        for a in leaf_attrs:
            if table.has_column(a + "__x"):
                agg_cols += [a + "__x", a + "__y"]
            elif table.has_column(a):
                agg_cols.append(a)
        enum_ok = all(
            leaf.attribute in self.store.dicts
            for leaf in kstats._leaf_stats(stat)
            if leaf.kind in ("enumeration", "topk")
        )
        if not (kstats.device_supported(stat, host_only) and enum_ok):
            return None
        return agg_cols, vocab_sizes

    def stats_partials(self, plan: QueryPlan, stat: sk.Stat):
        """``(supported, partials)`` — the async device partial-update
        pytree for ``stat`` (the sharded partitioned scan absorbs these in
        pruned-bin order AFTER every device has been dispatched). Does NOT
        mutate ``stat``. ``supported=False`` means the stat tree needs the
        host gather path; ``partials`` may be None on an empty scan."""
        bundle = self._stats_bundle(plan, stat)
        if bundle is None:
            return False, None
        agg_cols, vocab_sizes = bundle

        def agg(cols, m, xp):
            return kstats.device_update(stat, cols, m, xp, vocab_sizes)

        return True, self._run(
            plan, agg, agg, agg_cols,
            # the stat STRUCTURE is baked into the traced update: it keys
            # the kernel, so a repeat of the query compiles nothing
            cache_key=("stats", self._stat_signature(stat),
                       tuple(sorted(vocab_sizes.items()))),
            band_merge=lambda dev, band: kstats.combine_partials(
                stat, dev, band),
            site="stats",
        )

    def stats(self, plan: QueryPlan, stat: sk.Stat) -> sk.Stat:
        supported, partials = self.stats_partials(plan, stat)
        if supported:
            if partials is not None:
                with _sync_span():
                    kstats.absorb_partials(stat, partials, self.store.dicts)
            return stat
        batch = self.features(plan)
        if batch.n:
            stat.observe(batch.columns)
            kstats.decode_enum_keys(stat, self.store.dicts)
        return stat

    def top_rows(self, plan: QueryPlan, attr: str, descending: bool,
                 k: int, include_ties: bool = False):
        """Flattened [S*L] positions of a SUPERSET of the top-k matched
        rows by one attribute (every boundary tie included) — the device
        half of a sorted+limited query (reference
        SortingSimpleFeatureIterator, done without a device sort, which
        compiles pathologically on this TPU toolchain). The caller sorts
        the gathered candidates exactly on host, so: for single-key
        sorts the final order is exact; for MULTI-key sorts this is
        called with the primary key, and tie inclusion guarantees every
        lexicographic top-k row is among the candidates.

        Two device strategies:
        - k <= 32, native f32 column: exact argmin iteration (r4 path);
        - otherwise: THRESHOLD SELECT — binary-search the k-th key value
          with masked count reductions (48 bandwidth-bound passes, one
          dispatch), then compact the <=threshold row positions into a
          k + tie-slack buffer with a sized nonzero. f64/int32 columns
          ride at f32: monotone rounding makes the selection a provable
          superset; the host's exact sort of the candidates restores f64
          order. Returns None when the column can't rank on device or
          the tie group overflows the buffer (caller sorts on host)."""
        table = self._table(plan)
        if (
            not table.has_column(attr)
            or table.is_host_only(attr)
            or attr in self.store.dicts  # codes rank by insertion order
            or table.dtype_of(attr) == np.bool_
        ):
            return None
        if include_ties or table.dtype_of(attr) != np.float32 or k > 32:
            # multi-key sorts REQUIRE tie inclusion: the argmin path
            # returns exactly k rows and would drop a boundary tie that
            # wins on a secondary key
            return self._top_rows_threshold(plan, attr, descending, k)

        def agg(cols, m, xp, *extra):
            v = cols[attr].reshape(-1).astype(xp.float32)
            # NaN keys are excluded here (argmin would select them first);
            # if that leaves fewer than k rows the caller falls back to the
            # host sort, which orders NaNs last — exact parity either way
            ok = m.reshape(-1) & ~xp.isnan(v)
            d = xp.where(ok, -v if descending else v, xp.inf)
            # argmin iteration (same tradeoff as kernels/knn.py): both
            # lax.top_k and sort-based top-k compile pathologically on
            # this TPU toolchain, so large k stays on the host
            idxs, vals = [], []
            for _ in range(k):
                i = xp.argmin(d)
                idxs.append(i)
                vals.append(-d[i] if descending else d[i])
                d = d.at[i].set(xp.inf)
            return xp.stack(idxs), xp.stack(vals)

        def agg_host(cols, m, xp, *extra):
            v = cols[attr].reshape(-1).astype(np.float64)
            v = np.where(m.reshape(-1), v if descending else -v, -np.inf)
            idx = np.argsort(-v, kind="stable")[:k]
            return idx, v[idx]

        out = self._run(
            plan, agg, agg_host, [attr],
            cache_key=("top", attr, bool(descending), int(k)),
            compactable=False,  # returned indices address the padded layout
        )
        if out is None:
            return np.zeros(0, np.int64)
        idx, vals = np.asarray(out[0]), np.asarray(out[1])
        idx = idx[np.isfinite(vals)].astype(np.int64)
        if len(idx) < k:
            # fewer finite matches than k: NaN-keyed or sparse matches may
            # exist that the device path excluded — let the host decide
            return None
        return idx

    def _top_rows_threshold(self, plan: QueryPlan, attr: str,
                            descending: bool, k: int):
        """Threshold-select top-k candidates (see :meth:`top_rows`)."""
        slack = config.TOPK_TIE_SLACK.to_int()
        if slack is None:
            slack = int(config.TOPK_TIE_SLACK.default)
        B = int(k + slack)
        desc = bool(descending)

        def agg(cols, m, xp, *extra):
            from jax import lax

            v = cols[attr].reshape(-1).astype(xp.float32)
            key = -v if desc else v
            ok = m.reshape(-1) & ~xp.isnan(v)
            kv = xp.where(ok, key, xp.inf)
            n_ok = ok.sum()
            lo = xp.min(kv)
            hi = xp.max(xp.where(ok, key, -xp.inf))

            # smallest t with count(key <= t) >= k: 48 halvings reach f32
            # resolution from any normal range
            def body(_, lohi):
                lo, hi = lohi
                mid = (lo + hi) * 0.5
                c = xp.sum(kv <= mid)
                ge = c >= k
                return xp.where(ge, lo, mid), xp.where(ge, mid, hi)

            lo, hi = lax.fori_loop(0, 48, body, (lo, hi))
            t = xp.where(n_ok <= k, xp.inf, hi)  # few matches: take all
            sel = ok & (kv <= t)
            cnt = sel.sum()
            idx = xp.nonzero(sel, size=B, fill_value=sel.shape[0])[0]
            return idx, cnt

        def agg_host(cols, m, xp, *extra):
            # host twin with the same superset-with-ties contract
            v = cols[attr].reshape(-1).astype(np.float64)
            ok = m.reshape(-1) & ~np.isnan(v)
            key = np.where(ok, -v if desc else v, np.inf)
            n_ok = int(ok.sum())
            out = np.full(B, len(key), np.int64)
            if n_ok == 0:
                return out, 0
            kk = min(k, n_ok)
            t = np.partition(key, kk - 1)[kk - 1]
            sel = np.nonzero(key <= t)[0]
            out[: min(len(sel), B)] = sel[:B]
            return out, len(sel)

        out = self._run(
            plan, agg, agg_host, [attr],
            cache_key=("topt", attr, desc, int(k), B),
            compactable=False,  # returned indices address the padded layout
        )
        if out is None:
            return np.zeros(0, np.int64)
        idx, cnt = np.asarray(out[0]), int(out[1])
        if cnt > B:
            return None  # tie group overflowed the buffer: host sorts
        if cnt < k:
            # fewer non-NaN matches than k: NaN-keyed matches (which sort
            # LAST, but still belong in an under-filled result) were
            # excluded here — let the host decide
            return None
        table = self._table(plan)
        total = int(table.n_shards * table.shard_len)
        return idx[idx < total].astype(np.int64)

    def knn(self, plan: QueryPlan, qx: float, qy: float, k: int, boxes=None):
        """k nearest to (qx, qy) among plan matches. ``boxes`` (optional):
        up to two (x0, y0, x1, y1) restriction boxes applied INSIDE the
        aggregation as traced scalars — the expanding-radius search passes
        its search box here (and via the plan's windows) instead of baking
        it into the compiled predicate, so one kernel serves every location
        and radius."""
        geom = self.store.ft.geom_field
        xc, yc = geom + "__x", geom + "__y"

        def agg(cols, m, xp, qx_, qy_, *bb):
            if bb:
                x, y = cols[xc], cols[yc]
                inb = None
                for i in range(0, len(bb), 4):
                    x0, y0, x1, y1 = bb[i:i + 4]
                    mi = (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)
                    inb = mi if inb is None else (inb | mi)
                m = m & inb
            return kknn.knn_indices(cols[xc], cols[yc], m, qx_, qy_, k, xp)

        extra = [np.float32(qx), np.float32(qy)]
        nb = 0
        if boxes:
            for x0, y0, x1, y1 in boxes:
                # round the box OUTWARD at f32: a nearest-rounded bound can
                # shrink the box half an ulp and drop an edge neighbor the
                # f64 termination proof assumed was inside
                extra.extend((
                    np.nextafter(np.float32(x0), np.float32(-np.inf)),
                    np.nextafter(np.float32(y0), np.float32(-np.inf)),
                    np.nextafter(np.float32(x1), np.float32(np.inf)),
                    np.nextafter(np.float32(y1), np.float32(np.inf)),
                ))
            nb = len(boxes)
        out = self._run(
            plan, agg, agg, [xc, yc], cache_key=("knn", int(k), nb),
            extra=tuple(extra),
            compactable=False,  # returned indices address the padded layout
        )
        if out is None:
            return np.zeros(0, np.int64), np.zeros(0)
        idx, d = np.asarray(out[0]), np.asarray(out[1])
        keep = np.isfinite(d)
        return idx[keep], d[keep]
