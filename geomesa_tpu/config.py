"""Three-scope configuration system (system / store / query).

Mirrors GeoMesa's ``SystemProperty`` pattern
(reference: geomesa-utils/.../conf/GeoMesaSystemProperties.scala:19-60 and
geomesa-index-api/.../conf/QueryProperties.scala:15-50): a named, typed tunable
with a default, overridable by environment variable or a thread-local scope.

Resolution order: thread-local override > environment variable > default.
Environment variable name = property name with ``.``/``-`` replaced by ``_``,
upper-cased (e.g. ``geomesa.scan.ranges.target`` -> ``GEOMESA_SCAN_RANGES_TARGET``).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional

_local = threading.local()

_REGISTRY: Dict[str, "SystemProperty"] = {}


def _overrides() -> Dict[str, str]:
    if not hasattr(_local, "overrides"):
        _local.overrides = {}
    return _local.overrides


class SystemProperty:
    """A named tunable with a default and typed accessors."""

    def __init__(self, name: str, default: Optional[str] = None):
        self.name = name
        self.default = default
        self.env_name = name.replace(".", "_").replace("-", "_").upper()
        _REGISTRY[name] = self

    def get(self) -> Optional[str]:
        ov = _overrides()
        if self.name in ov:
            return ov[self.name]
        if self.env_name in os.environ:
            return os.environ[self.env_name]
        return self.default

    def set(self, value: Optional[Any]) -> None:
        """Thread-local override (None clears)."""
        ov = _overrides()
        if value is None:
            ov.pop(self.name, None)
        else:
            ov[self.name] = str(value)

    class _Scope:
        def __init__(self, prop: "SystemProperty", value: Any):
            self.prop, self.value = prop, value

        def __enter__(self):
            ov = _overrides()
            self.prev = ov.get(self.prop.name)
            ov[self.prop.name] = str(self.value)
            return self

        def __exit__(self, *exc):
            ov = _overrides()
            if self.prev is None:
                ov.pop(self.prop.name, None)
            else:
                ov[self.prop.name] = self.prev
            return False

    def scoped(self, value: Any) -> "SystemProperty._Scope":
        """``with prop.scoped(123): ...`` — temporary thread-local override."""
        return SystemProperty._Scope(self, value)

    # typed accessors -----------------------------------------------------
    def to_str(self) -> Optional[str]:
        return self.get()

    def to_int(self) -> Optional[int]:
        v = self.get()
        return None if v is None else int(v)

    def to_float(self) -> Optional[float]:
        v = self.get()
        return None if v is None else float(v)

    def to_bool(self) -> Optional[bool]:
        v = self.get()
        if v is None:
            return None
        return str(v).strip().lower() in ("1", "true", "yes", "on")

    def to_duration_ms(self) -> Optional[int]:
        """Parse '100 ms', '10s', '5 minutes', '1h' etc. to milliseconds."""
        v = self.get()
        if v is None:
            return None
        s = str(v).strip().lower()
        num = ""
        for ch in s:
            if ch.isdigit() or ch == ".":
                num += ch
            else:
                break
        unit = s[len(num):].strip()
        if not num:
            raise ValueError(f"invalid duration: {v!r}")
        x = float(num)
        factors = {
            "": 1, "ms": 1, "millis": 1, "millisecond": 1, "milliseconds": 1,
            "s": 1000, "sec": 1000, "second": 1000, "seconds": 1000,
            "m": 60_000, "min": 60_000, "minute": 60_000, "minutes": 60_000,
            "h": 3_600_000, "hour": 3_600_000, "hours": 3_600_000,
            "d": 86_400_000, "day": 86_400_000, "days": 86_400_000,
        }
        if unit not in factors:
            raise ValueError(f"invalid duration unit: {v!r}")
        return int(x * factors[unit])


def registry() -> Dict[str, SystemProperty]:
    return dict(_REGISTRY)


def snapshot_overrides() -> Dict[str, str]:
    """Copy of the CURRENT thread's override map. Overrides are
    thread-local, so a worker thread spawned mid-scope sees only
    env/defaults; pass this snapshot to :func:`adopt_overrides` on the
    worker so both threads resolve every property identically (the
    partition prefetcher does this — a bucketing knob diverging between
    the staging and consuming threads would silently mismatch shapes)."""
    return dict(_overrides())


def adopt_overrides(snapshot: Dict[str, str]) -> None:
    """Install a :func:`snapshot_overrides` copy as this thread's
    override map (replaces any existing thread-local overrides)."""
    _local.overrides = dict(snapshot)


# ---------------------------------------------------------------------------
# Query/scan tunables (names kept from the reference so operator docs carry
# over; see geomesa-index-api/.../conf/QueryProperties.scala).
# ---------------------------------------------------------------------------

#: Soft budget of z-ranges produced by range cover (reference default 2000,
#: QueryProperties.scala:24).
SCAN_RANGES_TARGET = SystemProperty("geomesa.scan.ranges.target", "2000")

#: Query timeout; None = unlimited.
QUERY_TIMEOUT = SystemProperty("geomesa.query.timeout", None)

#: Refuse full-table scans when set (FullTableScanQueryGuard analog).
BLOCK_FULL_TABLE_SCANS = SystemProperty("geomesa.scan.block-full-table", "false")

#: Force exact counts instead of estimates.
FORCE_COUNT = SystemProperty("geomesa.force.count", "false")

#: Loose BBOX semantics: evaluate BBOX on extent geometries as envelope
#: overlap only, skipping the exact-intersection refinement pass (the
#: reference's loose-bbox query option; default is exact).
LOOSE_BBOX = SystemProperty("geomesa.loose.bbox", "false")

#: Parallel shard-scan width (AbstractBatchScan thread analog).
QUERY_THREADS = SystemProperty("geomesa.query.threads", "8")

#: Default number of logical shards per index (ShardStrategy analog).
DEFAULT_SHARDS = SystemProperty("geomesa.index.shards", "4")

#: Density scan row batch (reference DensityScan.scala:58).
DENSITY_BATCH_SIZE = SystemProperty("geomesa.density.batch.size", "100000")

#: Stats scan row batch (reference StatsScan.scala:47).
STATS_BATCH_SIZE = SystemProperty("geomesa.stats.batch.size", "10000")

#: Enable cost-based strategy selection (StrategyDecider analog).
STRATEGY_DECIDER = SystemProperty("geomesa.strategy.decider", "cost")

#: Max interval (days) accepted by the temporal query guard when configured.
TEMPORAL_GUARD_MAX_DAYS = SystemProperty("geomesa.guard.temporal.max.days", None)

#: Default authorization set, comma-separated (geomesa-security analog).
#: Unset = unrestricted access; set (possibly empty auth list via per-query
#: auths) = visibility enforcement on.
SECURITY_AUTHS = SystemProperty("geomesa.security.auths", None)

#: Audit log destination: a JSONL file path, or unset for in-memory only.
AUDIT_PATH = SystemProperty("geomesa.audit.path", None)

#: Enable query auditing (QueryEvent records; reference index/audit/).
AUDIT_ENABLED = SystemProperty("geomesa.audit.enabled", "true")

# ---------------------------------------------------------------------------
# Time-partitioned / out-of-core store (TimePartition.scala:35 analog).
# ---------------------------------------------------------------------------

#: Spill directory for cold time partitions (unset = a per-store temp dir).
SPILL_DIR = SystemProperty("geomesa.partition.spill.dir", None)

#: Max time partitions kept resident in host RAM per partitioned store;
#: the rest live on disk and stream through partition-at-a-time.
MAX_RESIDENT_PARTITIONS = SystemProperty("geomesa.partition.max.resident", "4")

#: Partitioned tables round their padded shard length up to a multiple of
#: this, so near-equal partitions share one compiled scan kernel shape.
SHARD_LEN_BUCKET = SystemProperty("geomesa.partition.shard.bucket", "65536")

# ---------------------------------------------------------------------------
# Columnar geo-lake tier (docs/LAKE.md): the Spatial-Parquet-style spill
# format with per-row-group statistics and file-level pushdown.
# ---------------------------------------------------------------------------

#: Spill partitions as footer-indexed lake snapshots (off = the legacy
#: np.savez snapshots; either format always LOADS).
LAKE_ENABLED = SystemProperty("geomesa.lake.enabled", "true")

#: Rows per lake row group — the pruning granule. Smaller groups prune
#: tighter but cost more footer entries and per-group decode calls.
LAKE_ROWGROUP_ROWS = SystemProperty("geomesa.lake.rowgroup.rows", "16384")

#: Statistics-pruned partial loads for additive cold scans (count /
#: unweighted density / unweighted density_curve / stats): only the row
#: groups whose bbox/time statistics intersect the query load. Off =
#: every cold scan loads whole partitions (the pre-lake behavior).
LAKE_PUSHDOWN = SystemProperty("geomesa.lake.pushdown", "true")

#: Degrees added around a query bbox before it prunes row groups, so the
#: scan kernel's f32 edge arithmetic can never match a row whose group
#: was pruned away (the same safety family as cache.cells.CLASSIFY_MARGIN).
LAKE_PRUNE_MARGIN = SystemProperty("geomesa.lake.prune.margin", "1e-3")

# ---------------------------------------------------------------------------
# Compacted-scan + MXU density kernel tunables (r4; docs/SCALE.md cost
# model). Env names follow the standard mapping, e.g.
# geomesa.compact.min.rows -> GEOMESA_COMPACT_MIN_ROWS.
# ---------------------------------------------------------------------------

#: Enable the window-compacted scan layout (gather only window rows).
COMPACT_ENABLED = SystemProperty("geomesa.compact.enabled", "true")

#: Minimum table rows before compaction is considered.
COMPACT_MIN_ROWS = SystemProperty("geomesa.compact.min.rows", str(1 << 20))

#: Compaction engages only when padded chunk rows < this fraction of the
#: table (windows admitting most rows can't win).
COMPACT_FRACTION = SystemProperty("geomesa.compact.fraction", "0.5")

#: Bucket compiled-kernel shapes (padded window count K to a power of two
#: above the floor below; compact chunk counts already follow the
#: geometric ladder in kernels/density_mxu.ladder8) so distinct-but-similar
#: queries trace once per bucket instead of once per shape. Masked tails
#: keep results exact.
COMPACT_BUCKETING = SystemProperty("geomesa.compact.bucketing", "true")

#: Floor for the bucketed window count K: every query's K pads up to at
#: least this, so any plan with <= floor windows per shard shares one
#: kernel shape. Padded windows are empty (start == end == 0).
COMPACT_BUCKET_FLOOR = SystemProperty("geomesa.compact.bucket.floor", "8")

#: Plain (non-partitioned) stores round their padded shard length L up to
#: a multiple of this under bucketing, so a small insert never changes the
#: padded scan kernel's static shape (partitioned children use the larger
#: geomesa.partition.shard.bucket, set explicitly per table).
COMPACT_SHARD_BUCKET = SystemProperty("geomesa.compact.shard.bucket", "8192")

#: Capacity of the shared compiled-kernel LRU registry (entries). Evicts
#: least-recently-used kernels one at a time — never clear-on-overflow.
#: Raised 256 -> 512 with the query-axis batch kernels (their padded
#: member axis multiplies the key space ~5x for batch sites; the
#: recompile and eviction counts behind it were not measured on the chip
#: — docs/PERF.md "Registry pressure").
KERNEL_CACHE_SIZE = SystemProperty("geomesa.kernel.cache.size", "512")

#: Directory for JAX's persistent compilation cache, for deployments that
#: do not set JAX_COMPILATION_CACHE_DIR (which wins); unset, the cache is
#: <checkout>/.jax_cache (kernels/registry.py enable_persistent_cache).
COMPILE_CACHE_DIR = SystemProperty("geomesa.compile.cache.dir", None)

#: Double-buffered partition pipeline: overlap the NEXT partition's host
#: slab-gather/column assembly with the CURRENT partition's device
#: execution (one prefetch thread, one in-flight partition; compile and
#: dispatch stay on the query thread).
PIPELINE_PREFETCH = SystemProperty("geomesa.pipeline.prefetch", "true")

#: Bin-space (2-D mesh) streaming: lax.scan chunk count per device along
#: the time-bin axis (1 = no streaming; >1 trades HBM for steps).
BIN_STREAM_CHUNKS = SystemProperty("geomesa.bin.stream.chunks", "1")

#: Devices for the sharded partitioned scan (docs/SCALE.md): pruned
#: partitions fan out round-robin over this many local devices, with
#: per-device partial aggregates merged in a fixed deterministic order.
#: Unset/"all" = every local device; an integer caps the count;
#: 0/1/"off" disables (single-device streaming, the pre-sharding path).
#: Ignored when an explicit GSPMD mesh is configured on the dataset (the
#: mesh shards WITHIN a partition instead) and while a serving pool with
#: more than one executor is running (the pool owns the devices — one
#: dispatch thread per device).
MESH_DEVICES = SystemProperty("geomesa.mesh.devices", None)

#: Devices cordoned out of scheduling, comma-separated ids (e.g. "3" or
#: "2,5"): a cordoned device is excluded from the sharded scan's fan-out
#: and from serving-pool slot pinning WITHOUT a restart — the config-knob
#: face of parallel/health.py's explicit cordon()/uncordon() API (the CLI
#: ``devices cordon`` and the sidecar ``cordon-device`` action mutate the
#: in-process registry instead). Unset = nothing cordoned.
MESH_CORDON = SystemProperty("geomesa.mesh.cordon", None)

#: Consecutive dispatch failures that BREAK a device (open its
#: ``device:<id>`` circuit breaker, removing it from scheduling until the
#: reset window's half-open trial succeeds). Fed by sharded-scan dispatch
#: failures and latency-outlier streaks (parallel/health.py).
DEVICE_BREAKER_THRESHOLD = SystemProperty(
    "geomesa.device.breaker.threshold", "3"
)

#: Broken-device reset window (ms): after it, ONE trial dispatch is
#: admitted — success restores the device to scheduling, failure re-opens.
DEVICE_BREAKER_RESET_MS = SystemProperty(
    "geomesa.device.breaker.reset.ms", "30000"
)

#: Latency-outlier factor: a per-device partition sync slower than
#: factor x the trailing mesh-wide median (AND over the floor below)
#: counts one outlier; geomesa.device.breaker.threshold consecutive
#: outliers trip the device's breaker. "0" disables outlier detection.
DEVICE_LATENCY_OUTLIER = SystemProperty(
    "geomesa.device.latency.outlier", "20"
)

#: Absolute floor (ms) below which a sync is never an outlier — keeps
#: microsecond-scale jitter on tiny partitions from breaking a healthy
#: device (outliers are a straggler-lane signal, not a noise detector).
DEVICE_LATENCY_FLOOR_MS = SystemProperty(
    "geomesa.device.latency.floor.ms", "250"
)

#: Extend the partition prefetch pipeline's overlap to the device upload
#: on the SHARDED scan: the prefetch thread device_puts partition i+1's
#: staged host arrays onto its assigned device while device i executes.
#: Safe under the one-jit-thread-per-device discipline because device_put
#: is a pure transfer — it never traces or compiles (the PR 1 wedge was
#: jit compilation on foreign threads) — and results are bit-identical
#: with the overlap off (the upload populates the same device cache, same
#: sharding singleton, the query thread would have populated itself).
PIPELINE_DEVICE_PUT = SystemProperty("geomesa.pipeline.device-put", "true")

#: Bucket count for hash-bucketed per-key sampling (int keys and
#: dictionary vocabularies beyond the exact per-code kernel's gate).
#: Power of two; 0 routes such keys to the host's exact per-key counter.
SAMPLE_HASH_BUCKETS = SystemProperty("geomesa.sample.hash-buckets", "64")

#: Sorted-query top-k pushdown: max Query.max_features eligible for the
#: device threshold-select (binary-searched count reductions, no device
#: sort); larger limits gather the full result and sort on host.
TOPK_MAX = SystemProperty("geomesa.topk.max", "100000")

# ---------------------------------------------------------------------------
# Spatial aggregate cache (cache/; docs/CACHE.md). Memoizes aggregate results
# (density grids, stats sketches, counts) per SFC cell so repeated and
# overlapping queries pay only for the newly exposed residual region.
# ---------------------------------------------------------------------------

#: Master switch for the aggregate result cache (default off).
CACHE_ENABLED = SystemProperty("geomesa.cache.enabled", "false")

#: Memory budget for cached aggregates (bytes), applied PER FEATURE STORE
#: (one budget per schema — a dataset with N schemas can hold up to N x
#: this); size-aware LRU eviction keeps each store under it.
CACHE_BUDGET_BYTES = SystemProperty("geomesa.cache.budget.bytes", str(64 << 20))

#: Partial-cover decomposition targets at most this many grid cells per
#: axis over the query bbox (cell level adapts to the bbox span).
CACHE_CELLS_PER_AXIS = SystemProperty("geomesa.cache.cells-per-axis", "8")

#: Finest SFC cell level the decomposition may choose (cells are the
#: 2^level x 2^level lon/lat grid aligned with the z2 curve blocks).
CACHE_MAX_LEVEL = SystemProperty("geomesa.cache.max.level", "12")

#: Hard cap on interior cells per decomposed query; beyond it the query
#: falls back to whole-result caching only.
CACHE_MAX_CELLS = SystemProperty("geomesa.cache.max.cells", "256")

#: Hierarchical pre-aggregation (cache/hierarchy.py; docs/CACHE.md): a
#: level-k cell assembles from its four level-(k+1) children (counts add,
#: unweighted grids downsample-add, exact sketches merge — all in the
#: fixed SW/SE/NW/NE child order, so assembly is bit-identical to a fresh
#: scan), and completed sibling quads roll up bottom-up on put. Makes a
#: zoom-out over a warm region cost O(visible cells), never O(data).
CACHE_HIERARCHY = SystemProperty("geomesa.cache.hierarchy", "true")

#: How many levels DOWN an on-miss assembly may recurse looking for
#: cached children (1 = direct children only).
CACHE_HIERARCHY_DEPTH = SystemProperty("geomesa.cache.hierarchy.depth", "2")

#: Polygon-region decomposition (cache/cells.py; docs/CACHE.md): a query
#: whose one spatial conjunct is INTERSECTS/WITHIN of a polygon literal
#: splits into interior cells (served from the cache/hierarchy — they
#: share cell keys with bbox queries) plus boundary cells scanned exactly
#: under the polygon predicate. Off = polygon queries are whole-result
#: cached only.
CACHE_POLYGON = SystemProperty("geomesa.cache.polygon", "true")

# ---------------------------------------------------------------------------
# TPU-native spatial joins (planning/join_exec.py; docs/JOIN.md): SFC-cell
# co-partitioned build/probe with a bucketed pairwise kernel.
# ---------------------------------------------------------------------------

#: Pairwise-kernel tile edge: per-cell build/probe blocks chunk into tiles
#: of at most this many rows per side (pow2-bucketed below it), so skewed
#: cells split into more tiles instead of inflating every cell's padding.
JOIN_TILE = SystemProperty("geomesa.join.tile", "64")

#: Finest SFC cell level the join co-partition may choose (cells are the
#: same 2^level x 2^level lon/lat grid the aggregate cache decomposes to).
JOIN_MAX_LEVEL = SystemProperty("geomesa.join.max.level", "12")

#: Matched-pair ColumnBatch chunk size for the streaming join result.
JOIN_BATCH_ROWS = SystemProperty("geomesa.join.batch.rows", "65536")

#: Adaptive per-cell strategy selection (docs/JOIN.md §5): classify each
#: joint cell from its build/probe counts and route it to the cheapest
#: executor — dense balanced cells keep the bucketed pairwise kernel,
#: sparse cells take the flat brute-force path (no tile padding), skewed
#: cells split along the longer side with their own narrow buckets. OFF
#: forces the single-strategy path everywhere (the A/B switch; results
#: are bit-identical either way — only dispatch shapes change).
JOIN_ADAPTIVE = SystemProperty("geomesa.join.adaptive", "true")

#: A joint cell whose n_build * n_probe candidate product is at most this
#: goes to the flat brute-force strategy (gathered 1-D pair list, no
#: [B, P] tile padding).
JOIN_ADAPTIVE_BRUTE_PAIRS = SystemProperty(
    "geomesa.join.adaptive.brute.pairs", "256")

#: A joint cell whose longer side holds at least this many times the
#: shorter side's rows is SKEWED: its tiles dispatch in a separate
#: section whose short-side bucket stays narrow instead of inflating to
#: the dense cells' padding.
JOIN_ADAPTIVE_SKEW_RATIO = SystemProperty(
    "geomesa.join.adaptive.skew.ratio", "8")

#: Window-pushdown join side scans (docs/JOIN.md §8, docs/LAKE.md): for
#: ``join_count`` with the probe side on a partitioned store, stream the
#: probe side per cell group through footer-pruned ranged reads instead
#: of materializing the whole filtered side on the host.
JOIN_PUSHDOWN = SystemProperty("geomesa.join.pushdown", "true")

#: Cell-group size for the pushdown side scan: each probe-side ranged
#: read covers at most this many occupied build cells. Smaller groups
#: bound per-chunk host memory; larger groups amortize the footer pass
#: and avoid re-decoding row groups that straddle chunk boundaries
#: (adjacent chunks' inflated windows overlap by the reach).
JOIN_PUSHDOWN_CELLS = SystemProperty("geomesa.join.pushdown.cells", "256")

# ---------------------------------------------------------------------------
# Resilience layer (resilience.py; docs/RESILIENCE.md). Retry defaults track
# the reference's tablet-server client retry posture; the breaker fences a
# dead sidecar so calls fail fast instead of paying the timeout each time.
# ---------------------------------------------------------------------------

#: Per-call timeout for sidecar Flight RPCs (FlightCallOptions.timeout);
#: a live query deadline tightens it further. None = no per-call timeout.
SIDECAR_TIMEOUT = SystemProperty("geomesa.sidecar.timeout", "30 s")

#: Total tries per retryable remote call (1 disables retry).
RETRY_ATTEMPTS = SystemProperty("geomesa.retry.attempts", "3")

#: Backoff base delay (ms); retry i waits base * 2^(i-1), capped below.
RETRY_BASE_MS = SystemProperty("geomesa.retry.base.ms", "50")

#: Backoff delay cap (ms).
RETRY_MAX_MS = SystemProperty("geomesa.retry.max.ms", "5000")

#: Jitter fraction [0, 1): each delay is scaled by 1 - jitter * U(0, 1)
#: from the policy's seeded RNG (deterministic under a fixed seed).
RETRY_JITTER = SystemProperty("geomesa.retry.jitter", "0.2")

#: Consecutive failures that open a circuit breaker.
BREAKER_THRESHOLD = SystemProperty("geomesa.breaker.threshold", "5")

#: Open -> half-open reset window (ms).
BREAKER_RESET_MS = SystemProperty("geomesa.breaker.reset.ms", "30000")

#: Allow degraded (partial) aggregates: a failing partition is skipped and
#: recorded instead of failing the whole scan. Off = strict (raise); the
#: ``resilience.allow_partial()`` scope enables it per-operation.
SCAN_PARTIAL = SystemProperty("geomesa.scan.partial", "false")

#: Master switch for the deterministic fault-injection registry
#: (resilience.inject_faults refuses to install without it). Fault points
#: are a single no-op check when no injector is installed.
FAULT_INJECTION = SystemProperty("geomesa.fault.injection", "false")

#: Extra gather slots for boundary ties in the device top-k selection;
#: selections whose tie group overflows k + slack fall back to the host.
TOPK_TIE_SLACK = SystemProperty("geomesa.topk.tie-slack", "4096")

# ---------------------------------------------------------------------------
# Observability (tracing.py, obs.py; docs/OBSERVABILITY.md). Tracing is
# off-by-default-cheap: with geomesa.trace.enabled false the span API is a
# no-op (a context-var read returning a shared singleton), asserted by the
# bench smoke trace_overhead_pct gate.
# ---------------------------------------------------------------------------

#: Master switch for query span-tree tracing (default off).
TRACE_ENABLED = SystemProperty("geomesa.trace.enabled", "false")

#: Slow-query threshold: a completed root span slower than this writes its
#: full span tree as a JSONL record through the audit appender (and into
#: the in-memory slow-trace ring served by /debug/queries). Unset = never.
TRACE_SLOW_MS = SystemProperty("geomesa.trace.slow.ms", None)

#: Per-query span budget: spans beyond this are dropped (counted on the
#: root as ``dropped``) so a decomposed 256-cell query cannot balloon its
#: trace unboundedly.
TRACE_MAX_SPANS = SystemProperty("geomesa.trace.max.spans", "512")

#: Mirror spans into jax.profiler.TraceAnnotation scopes so they appear in
#: TensorBoard/Perfetto device profiles alongside XLA ops (default off).
TRACE_JAX_PROFILER = SystemProperty("geomesa.trace.jax.profiler", "false")

#: Per-site recompile alert: a jit site that pays more than this many
#: fresh traces within ONE query trips the ``kernel.recompile.alert``
#: gauge (warm-path regression signal; docs/PERF.md).
KERNEL_ALERT_THRESHOLD = SystemProperty("geomesa.kernel.alert.threshold", "3")

# ---------------------------------------------------------------------------
# Trace export + tail-based sampling (tracing_export.py;
# docs/OBSERVABILITY.md). Export engages when either sink below is
# configured; the sampling decision is made at trace COMPLETION (tail-based):
# slow/errored/degraded/shed/recompile-carrying traces are always kept,
# healthy traces sample at the seeded-deterministic rate.
# ---------------------------------------------------------------------------

#: HTTP OTLP sink: POST finished span batches (OTLP/JSON shape) here.
#: Retried via resilience.RetryPolicy and fenced by the ``trace.otlp``
#: circuit breaker. Unset = no HTTP sink.
TRACE_OTLP_ENDPOINT = SystemProperty("geomesa.trace.otlp.endpoint", None)

#: File sink: append one OTLP-shaped JSON span batch per line (JSONL) —
#: the air-gapped/CI sink. Unset = no file sink.
TRACE_EXPORT_PATH = SystemProperty("geomesa.trace.export.path", None)

#: Tail-sampling keep rate for HEALTHY traces in [0, 1]. Decided
#: deterministically from (seed, trace_id), so a given trace id is kept or
#: dropped identically run to run. Always-keep classes (slow, errored,
#: degraded, shed, recompile-carrying) ignore the rate.
TRACE_SAMPLE_RATE = SystemProperty("geomesa.trace.sample.rate", "1.0")

#: Seed for the deterministic sampling hash above.
TRACE_SAMPLE_SEED = SystemProperty("geomesa.trace.sample.seed", "0")

#: Bounded export queue depth between trace completion and the background
#: flusher. Overflow DROPS the trace (counted in ``trace.export.dropped``)
#: — the query/dispatch threads never block on export.
TRACE_EXPORT_QUEUE = SystemProperty("geomesa.trace.export.queue", "1024")

#: Max traces converted + written per flusher pass (one OTLP batch).
TRACE_EXPORT_BATCH = SystemProperty("geomesa.trace.export.batch", "64")

# ---------------------------------------------------------------------------
# Per-device utilization accounting (utilization.py; docs/OBSERVABILITY.md).
# ---------------------------------------------------------------------------

#: Trailing window (seconds) over which the ``device.busy.<id>`` (in-flight)
#: and ``serving.slot.occupancy.<slot>`` gauges compute their fraction.
DEVICE_BUSY_WINDOW = SystemProperty("geomesa.device.busy.window", "60")

# ---------------------------------------------------------------------------
# SLO burn-rate monitor (slo.py; docs/OBSERVABILITY.md). Targets are
# per-op p99 latencies named ``geomesa.slo.<op>.p99.ms`` (thread-local
# override or env, e.g. GEOMESA_SLO_COUNT_P99_MS=50), evaluated over the
# existing ``trace.<op>`` histograms with fast/slow dual-window burn rates.
# ---------------------------------------------------------------------------

#: Fast burn window (seconds): /healthz degrades when this window burns
#: past geomesa.slo.burn.threshold.
SLO_WINDOW_FAST_S = SystemProperty("geomesa.slo.window.fast.s", "300")

#: Slow burn window (seconds): the page-worthy confirmation window.
SLO_WINDOW_SLOW_S = SystemProperty("geomesa.slo.window.slow.s", "3600")

#: Fast-window burn rate past which /healthz reports degraded (the classic
#: 14.4x = "a 99% monthly budget gone in ~2 days at this rate" threshold).
SLO_BURN_THRESHOLD = SystemProperty("geomesa.slo.burn.threshold", "14.4")

#: Per-op SLO target prefix/suffix: ``geomesa.slo.<op>.p99.ms`` (op is a
#: root-span name: count, density, density_curve, ... — underscores, no
#: dots). Resolved via :func:`slo_targets`.
SLO_PREFIX = "geomesa.slo."
SLO_SUFFIX = ".p99.ms"


def slo_targets() -> Dict[str, float]:
    """Effective per-op p99 targets in ms: ``{op: target_ms}``. Thread-local
    overrides first (``geomesa.slo.<op>.p99.ms``), then env
    (``GEOMESA_SLO_<OP>_P99_MS``); an unparseable value is ignored."""
    out: Dict[str, float] = {}
    env_pre, env_suf = "GEOMESA_SLO_", "_P99_MS"
    for k, v in os.environ.items():
        if k.startswith(env_pre) and k.endswith(env_suf) \
                and len(k) > len(env_pre) + len(env_suf):
            try:
                out[k[len(env_pre):-len(env_suf)].lower()] = float(v)
            except ValueError:
                pass
    for k, v in _overrides().items():
        if k.startswith(SLO_PREFIX) and k.endswith(SLO_SUFFIX) \
                and len(k) > len(SLO_PREFIX) + len(SLO_SUFFIX):
            try:
                out[k[len(SLO_PREFIX):-len(SLO_SUFFIX)]] = float(v)
            except ValueError:
                pass
    return out

# ---------------------------------------------------------------------------
# Serving scheduler (serving/scheduler.py; docs/SERVING.md). The sidecar's
# single dispatch thread sits behind a bounded admission queue with
# deadline-aware ordering, per-user fair share, and cross-query fusion of
# compatible aggregates into one device pass.
# ---------------------------------------------------------------------------

#: Bounded admission queue depth: requests beyond it are rejected at
#: submission with a typed [GM-OVERLOADED] error (load shedding before any
#: planning or device work).
SERVING_QUEUE_DEPTH = SystemProperty("geomesa.serving.queue.depth", "256")

#: Cross-query fusion: compatible queued aggregates (same schema, predicate
#: text, auths, and op shape — hence the same version-stable kernel token)
#: coalesce into one micro-batch sharing a single device pass. Only
#: already-queued work fuses; fusion never delays dispatch to grow a batch.
SERVING_FUSION = SystemProperty("geomesa.serving.fusion", "true")

#: Max members per fused micro-batch.
SERVING_FUSION_MAX = SystemProperty("geomesa.serving.fusion.max", "16")

#: Query-axis (distinct-literal) fusion: requests whose ECQL differs ONLY
#: in BBOX / temporal literals share a structural fuse key and execute as
#: one batched device pass with the literals as kernel data
#: (docs/SERVING.md "Query-axis batching"). Off = only identical-key
#: repeats (and density_curve tile crops) fuse, the pre-megakernel rule.
SERVING_FUSION_DISTINCT = SystemProperty(
    "geomesa.serving.fusion.distinct", "true"
)

#: Pool-aware fusion placement: a fuse-bearing query prefers the executor
#: slot whose device most recently scanned its schema's columns (they are
#: still resident there), deferring briefly to that slot when it is idle
#: instead of binding to whichever slot drains the queue first. The
#: decision is surfaced on the fused group's trace span.
SERVING_PLACEMENT = SystemProperty("geomesa.serving.placement", "true")

#: How long (ms) a placement-deferred ticket is reserved for its preferred
#: slot before any slot may take it (starvation backstop).
SERVING_PLACEMENT_GRACE_MS = SystemProperty(
    "geomesa.serving.placement.grace.ms", "50"
)

#: Per-user fair share: the dispatcher serves the pending user with the
#: least attained service time instead of global FIFO, so one user's burst
#: cannot starve another's interactive queries. Off = strict FIFO.
SERVING_FAIR_SHARE = SystemProperty("geomesa.serving.fair-share", "true")

#: Admission-time estimate shedding: reject a request whose deadline budget
#: is smaller than the estimated queue wait (EWMA service time x pending
#: depth) with a typed [GM-SHED] error — before any device work.
SERVING_SHED_ESTIMATE = SystemProperty("geomesa.serving.shed.estimate", "true")

#: Dispatch-thread pool width for the serving scheduler: N executors,
#: one dispatch thread per executor slot (slot i pins jax device
#: i % device_count), each keeping the one-jit-thread-per-device
#: discipline. Admission, deadline shedding, fair share, and fusion stay
#: GLOBAL; a fusion group binds to one executor so batch results stay
#: bit-identical. "all" = one per local device; default 1 = the single
#: dispatch thread (pre-pool behavior, byte-for-byte).
SERVING_EXECUTORS = SystemProperty("geomesa.serving.executors", "1")

#: Identity attached to queries for fair-share accounting and the
#: /debug/queries per-user rollups (the sidecar client forwards it as the
#: x-geomesa-user Flight header; unset = "anonymous").
USER = SystemProperty("geomesa.user", None)

# ---------------------------------------------------------------------------
# Replica fleet (fleet/; docs/RESILIENCE.md §7). A front-end router plus N
# replica sidecars over one shared storage root: consistent-hash CELL
# affinity routing, per-replica breakers + failover, and mutation-epoch
# propagation so no replica ever serves a pre-mutation aggregate.
# ---------------------------------------------------------------------------

#: This process's replica identity in a fleet (stamped into every response
#: as the x-geomesa-replica-id header; "replica:<id>" names its breaker on
#: routers). Unset = not a fleet replica.
FLEET_REPLICA_ID = SystemProperty("geomesa.fleet.replica.id", None)

#: Shared storage root the fleet's replicas load from / persist to
#: (GeoDataset.save/load layout). A replica whose known fleet epoch for a
#: schema trails an incoming request's epoch refreshes that schema from
#: here BEFORE serving; a replica applying a router-stamped write saves
#: here before acknowledging. Unset = no cross-replica refresh.
FLEET_ROOT = SystemProperty("geomesa.fleet.root", None)

#: SFC cell level the router derives affinity keys at: a query's bbox
#: center quantizes to one 2^level x 2^level cell, and the rendezvous
#: ring hashes (schema, cell prefix) to pick the owner replica — nearby
#: viewports land on the same replica, keeping its cell cache hot.
FLEET_ROUTING_LEVEL = SystemProperty("geomesa.fleet.routing.level", "3")

#: Scatter decomposable MERGEABLE aggregates across replicas by cell
#: ownership (each owner group scans only its cells; partials compose
#: exactly — counts add, unweighted grids add, exact-merge sketches
#: merge, curve chunks slot by block). Off = every query routes whole
#: to one replica.
FLEET_SCATTER = SystemProperty("geomesa.fleet.scatter", "true")

# -- standing queries (geomesa_tpu/subscribe/; docs/STANDING.md) -----------

#: Master switch for the subscription subsystem: off, registrations raise
#: and mutation hooks are no-ops (zero ingest-path overhead).
SUBSCRIBE_ENABLED = SystemProperty("geomesa.subscribe.enabled", "true")

#: Hard-assert every incremental (delta-applied) standing result against a
#: from-scratch re-scan at the same epoch after EVERY settle — the
#: bit-identity contract, paid as a full re-scan per update. On in tests
#: and the standing-smoke CI gate; off in production serving.
SUBSCRIBE_VERIFY = SystemProperty("geomesa.subscribe.verify", "false")

#: Maximum DISTINCT standing groups per schema (fused subscribers share a
#: group, so 10k watchers of one hot viewport cost one slot). Registration
#: past the cap answers a typed [GM-SUB-LIMIT] error.
SUBSCRIBE_MAX_GROUPS = SystemProperty("geomesa.subscribe.max.groups", "256")

#: Update-ring depth per group: how many per-batch update records a slow
#: poller may lag before the ring truncates (a truncated poller sees a
#: version gap and should re-anchor on the carried full result).
SUBSCRIBE_UPDATES_RING = SystemProperty("geomesa.subscribe.updates.ring",
                                        "256")

#: Quadtree-rollup pyramid depth: the leaf grid is 2^levels x 2^levels
#: and downsample-adds up to the 1x1 root (cache/hierarchy.downsample,
#: fixed SW/SE/NW/NE order).
SUBSCRIBE_PYRAMID_LEVELS = SystemProperty("geomesa.subscribe.pyramid.levels",
                                          "5")

#: Concurrent owner-group dispatches per scattered query (the router's
#: fan-out thread bound). "1" serializes the groups (still scattered,
#: no parallel wall-clock win).
FLEET_SCATTER_FANOUT = SystemProperty("geomesa.fleet.scatter.fanout", "8")

#: Consecutive SUCCESSFUL probes after which the router automatically
#: un-cordons a replica it cordoned (router-side cordons only — the
#: geomesa.fleet.cordon config list stays operator-owned). "0" disables
#: auto-uncordon (the pre-PR-15 manual-exit behavior).
FLEET_UNCORDON_PROBES = SystemProperty("geomesa.fleet.uncordon.probes", "3")

#: Hottest cache entries a draining replica pushes to the new ring owner
#: during a warm-handoff drain (per schema, LRU-hottest first).
FLEET_HANDOFF_ENTRIES = SystemProperty("geomesa.fleet.handoff.entries",
                                       "256")

#: Fleet-level admission bound on the router: concurrent in-flight routed
#: queries beyond this are rejected typed [GM-OVERLOADED] before any RPC
#: (the same _UserLedger-backed policy the serving scheduler runs).
FLEET_MAX_INFLIGHT = SystemProperty("geomesa.fleet.max.inflight", "256")

#: Consecutive connect/dispatch failures that BREAK a replica (open its
#: ``replica:<id>`` breaker, removing it from routing until the half-open
#: trial succeeds). Fed by routed-call failures, failed /healthz-style
#: probes, and latency-outlier streaks.
FLEET_BREAKER_THRESHOLD = SystemProperty("geomesa.fleet.breaker.threshold", "3")

#: Broken-replica reset window (ms): after it, ONE trial call is admitted.
FLEET_BREAKER_RESET_MS = SystemProperty(
    "geomesa.fleet.breaker.reset.ms", "30000"
)

#: Latency-outlier factor for routed calls: a replica's call slower than
#: factor x the trailing fleet-wide median for the same op (and over the
#: floor below) counts one outlier; a threshold-long consecutive streak
#: trips the replica's breaker. "0" disables.
FLEET_LATENCY_OUTLIER = SystemProperty("geomesa.fleet.latency.outlier", "20")

#: Absolute floor (ms) below which a routed call is never an outlier.
FLEET_LATENCY_FLOOR_MS = SystemProperty(
    "geomesa.fleet.latency.floor.ms", "250"
)

#: Replicas cordoned out of routing, comma-separated ids — the config-knob
#: face of FleetRouter.cordon()/uncordon() (explicit API on the router).
FLEET_CORDON = SystemProperty("geomesa.fleet.cordon", None)

# ---------------------------------------------------------------------------
# Fleet observability plane (fleet/obs.py; docs/OBSERVABILITY.md §9):
# metrics federation, cross-replica trace stitching, cell-heat telemetry,
# and the replica anomaly watchdog. All pull/async: nothing here runs on
# the routed-query path.
# ---------------------------------------------------------------------------

#: Federation snapshot TTL (ms): a fleet /metrics, /healthz, or /debug/heat
#: read within this window of the last sweep reuses the cached merge
#: instead of re-pulling every replica. "0" re-pulls on every read.
FLEET_OBS_TTL_MS = SystemProperty("geomesa.fleet.obs.ttl.ms", "2000")

#: Per-replica metrics-export / trace-fetch pull timeout (seconds).
FLEET_OBS_TIMEOUT_S = SystemProperty("geomesa.fleet.obs.timeout.s", "5")

#: Master switch for the async trace stitcher: with it false, scattered
#: queries export their router-local trace only (pre-PR-19 behavior).
FLEET_STITCH = SystemProperty("geomesa.fleet.stitch", "true")

#: Completed scattered queries the stitcher queues for assembly; overflow
#: drops the oldest pending id (counted fleet.trace.stitch.failed) — the
#: same non-blocking contract as the trace export queue.
FLEET_STITCH_QUEUE = SystemProperty("geomesa.fleet.stitch.queue", "256")

#: Settle delay (ms) between a scattered query finishing and its stitch
#: pull: replica root spans must FINISH (late children re-finish the
#: trace) before trace-fetch can see their subtree.
FLEET_STITCH_DELAY_MS = SystemProperty("geomesa.fleet.stitch.delay.ms",
                                       "100")

#: Anomaly-watchdog flag factor: a replica whose recent per-op latency
#: median is >= factor x the fleet median for that op (both over >= 8
#: samples) is flagged in fleet.anomaly.<id> and the /debug/fleet advice
#: row. Observation only — no cordon. "0" disables the watchdog.
FLEET_ANOMALY_FACTOR = SystemProperty("geomesa.fleet.anomaly.factor", "4")

#: Distinct (schema, cell) rows the process heat table retains (coldest
#: rows evict first). "0" disables heat recording.
HEAT_CELLS_MAX = SystemProperty("geomesa.heat.cells", "4096")

#: Hottest rows a heat snapshot ships per schema (metrics-export payload
#: and /debug/heat bound).
HEAT_TOP = SystemProperty("geomesa.heat.top", "256")

#: Finished traces retained BY ID for /debug/queries?trace= and the
#: trace-fetch action (a bounded ring; the slow-trace ring is separate).
TRACE_RETAIN = SystemProperty("geomesa.trace.retain", "256")

#: Cross-chunk row-group residency budget (MiB) for window-pushdown join
#: side scans (docs/JOIN.md §11): decoded column chunks of row groups
#: straddling adjacent pushdown chunks are kept across chunk scans so the
#: boundary groups stop decoding twice. "0" disables the cache.
JOIN_PUSHDOWN_RESIDENCY_MB = SystemProperty(
    "geomesa.join.pushdown.residency.mb", "64"
)

# ---------------------------------------------------------------------------
# Durable mutation journal (fs/journal.py; docs/RESILIENCE.md §8): per-root
# crc-framed write-ahead log with group commit. With it attached, an acked
# mutation is ON DISK before the call returns; load() replays records past
# each schema's checkpointed position, and save() checkpoints then truncates
# the journal segment-wise.
# ---------------------------------------------------------------------------

#: Master switch: with it false, attach_journal() is a no-op and every root
#: keeps the pre-journal semantics (acked mutations live until the next
#: explicit save()).
JOURNAL_ENABLED = SystemProperty("geomesa.journal.enabled", "true")

#: Group-commit window (ms): after the first pending append wakes the
#: committer, it waits this long for concurrent appenders to join the
#: batch, then writes + fsyncs ONCE for all of them. "0" commits each
#: drain immediately — concurrent writers still batch naturally because
#: appends arriving during an fsync join the next drain (commit
#: pipelining); positive values trade single-writer append latency for
#: wider groups under concurrency.
JOURNAL_GROUP_MS = SystemProperty("geomesa.journal.group.ms", "2")

#: Segment roll threshold (bytes): the active segment closes and a new one
#: starts past this size, bounding both the torn-tail blast radius and the
#: granularity at which checkpoints reclaim space.
JOURNAL_SEGMENT_BYTES = SystemProperty(
    "geomesa.journal.segment.bytes", str(8 << 20)
)

#: Fleet-replica checkpoint cadence: a replica serving stamped writes from
#: a shared root runs a full ``save()`` (checkpoint + journal truncation)
#: every this-many commits — between checkpoints a one-row insert costs one
#: journal append + marker advance, never a schema snapshot rewrite.
JOURNAL_CHECKPOINT_WRITES = SystemProperty(
    "geomesa.journal.checkpoint.writes", "256"
)

#: Per-user fair-share weight prefix: ``geomesa.serving.user.weight.<user>``
#: scales a user's attained-service debt (the dispatcher picks the user
#: minimizing service_s / weight), so weight 4 earns ~4x the service of
#: weight 1 under contention. Resolved on the SUBMITTING thread at each
#: submit/admit and captured into the user's ledger (thread-local
#: override first, then env — non-alphanumeric identity chars map to
#: ``_`` in the env name), default 1.0; values <= 0 are treated as 1.0.
#: Surfaced in the /debug/queries per-user rollups.
USER_WEIGHT_PREFIX = "geomesa.serving.user.weight."


def user_weight(user: str) -> float:
    """Effective fair-share weight for ``user`` (see USER_WEIGHT_PREFIX)."""
    name = USER_WEIGHT_PREFIX + user
    v = _overrides().get(name)
    if v is None:
        env = "".join(
            ch if ch.isalnum() else "_" for ch in name
        ).upper()
        v = os.environ.get(env)
    if v is None:
        return 1.0
    try:
        w = float(v)
    except ValueError:
        return 1.0
    return w if w > 0 else 1.0
