"""Metrics registry (geomesa-metrics analog, SURVEY.md §2.8).

The reference uses a Dropwizard ``MetricRegistry`` with pluggable reporters
(GeoMesaMetrics.scala:26); consumers are the Kafka live cache and converter
``EvaluationContext`` counters. Here: a process-wide registry of counters,
gauges, and timers with a prometheus-text dump — attached to ingest, query
execution, and the streaming layer.
"""

from __future__ import annotations

import bisect
import threading
import time
from typing import Callable, Dict, List, Optional, Tuple


class Counter:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1):
        with self._lock:
            self.value += n


class Gauge:
    """A sampled value; either set explicitly or backed by a callable.

    ``set()``/``value`` are lock-protected, and a callable backing is only
    installed through :meth:`set_fn` — replacing an existing (different)
    callable must be explicit (``replace=True``), never the silent
    last-registration-wins the old ``MetricRegistry.gauge`` did."""

    def __init__(self, fn: Optional[Callable[[], float]] = None):
        self._lock = threading.Lock()
        self.fn = fn
        self._value = 0.0

    def set(self, v: float):
        with self._lock:
            self._value = float(v)

    def set_fn(self, fn: Callable[[], float], replace: bool = False) -> None:
        """Install (or explicitly replace) the callable backing."""
        with self._lock:
            if self.fn is not None and self.fn is not fn and not replace:
                raise ValueError(
                    "gauge is already callable-backed; pass replace=True to "
                    "swap the backing function"
                )
            self.fn = fn

    @property
    def value(self) -> float:
        with self._lock:
            fn = self.fn
            if fn is None:
                return self._value
        return float(fn())  # sample outside the lock: fn may be slow


#: Fixed histogram bucket upper bounds (seconds). Spans sub-millisecond
#: kernel dispatches through multi-second partitioned scans; the prometheus
#: rendering emits cumulative ``_bucket{le=...}`` lines so p50/p90/p99 are
#: derivable with the standard histogram_quantile arithmetic.
DEFAULT_BUCKETS_S: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)


class Histogram:
    """Fixed-bucket histogram. Defaults to the latency buckets (seconds);
    pass custom ``buckets`` plus ``unit=None`` for dimensionless
    distributions (e.g. fusion batch sizes) — the prometheus rendering then
    drops the ``_seconds`` suffix.

    **Exemplars**: ``observe(seconds, trace_id=...)`` additionally records
    the trace id against the bucket the observation landed in (last-writer
    wins per bucket), rendered in OpenMetrics exemplar syntax — so a p99
    outlier in /metrics links directly to its exported/slow-logged trace.
    The exemplar map is lazily allocated: histograms never fed a trace_id
    pay nothing."""

    __slots__ = ("buckets", "counts", "count", "sum_s", "unit", "exemplars",
                 "_lock")

    def __init__(self, buckets: Optional[Tuple[float, ...]] = None,
                 unit: Optional[str] = "s"):
        self.unit = unit
        self.buckets = tuple(buckets or DEFAULT_BUCKETS_S)
        self.counts = [0] * (len(self.buckets) + 1)  # last = +Inf overflow
        self.count = 0
        self.sum_s = 0.0
        #: bucket index -> (trace_id, value, unix_ts); None until first use
        self.exemplars: Optional[Dict[int, Tuple[str, float, float]]] = None
        self._lock = threading.Lock()

    def observe(self, seconds: float, trace_id: Optional[str] = None):
        i = bisect.bisect_left(self.buckets, seconds)
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum_s += seconds
            if trace_id is not None:
                if self.exemplars is None:
                    self.exemplars = {}
                self.exemplars[i] = (trace_id, seconds, time.time())

    def quantile(self, q: float) -> float:
        """Approximate quantile: the upper bound of the bucket holding the
        q-th observation (the same answer prometheus derives from the text
        exposition; +Inf resolves to the largest finite bound)."""
        with self._lock:
            total = self.count
            counts = list(self.counts)
        if total == 0:
            return 0.0
        rank = q * total
        cum = 0
        for i, c in enumerate(counts):
            cum += c
            if cum >= rank:
                return self.buckets[min(i, len(self.buckets) - 1)]
        return self.buckets[-1]

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            counts = list(self.counts)
            total, s = self.count, self.sum_s
            ex = dict(self.exemplars) if self.exemplars else {}
        return {"count": total, "sum_s": s, "counts": counts,
                "buckets": list(self.buckets), "exemplars": ex}


class Timer:
    """Count + total/max duration + latency distribution. Use as a context
    manager; every existing ``timer(...)`` hot site feeds the embedded
    :class:`Histogram` with no call-site changes, so /metrics carries
    p50/p90/p99 for all of them."""

    __slots__ = ("count", "total_s", "max_s", "hist", "_lock")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.hist = Histogram()
        self._lock = threading.Lock()

    def update(self, seconds: float):
        with self._lock:
            self.count += 1
            self.total_s += seconds
            self.max_s = max(self.max_s, seconds)
        self.hist.observe(seconds)

    def time(self):
        return _TimerContext(self)

    @property
    def mean_s(self) -> float:
        return self.total_s / self.count if self.count else 0.0


class _TimerContext:
    def __init__(self, timer: Timer):
        self.timer = timer

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.timer.update(time.perf_counter() - self._t0)
        return False


class MetricRegistry:
    def __init__(self, prefix: str = "geomesa"):
        self.prefix = prefix
        self._metrics: Dict[str, object] = {}
        self._lock = threading.Lock()

    def _get(self, name: str, cls, *args):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(*args)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as {type(m).__name__}")
            return m

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str, fn: Optional[Callable[[], float]] = None,
              replace: bool = False) -> Gauge:
        """A named gauge. ``fn`` installs a callable backing; replacing an
        EXISTING different backing requires ``replace=True`` (satellite fix:
        the old path silently swapped ``fn`` under concurrent readers)."""
        g = self._get(name, Gauge)
        if fn is not None:
            g.set_fn(fn, replace=replace)
        return g

    def timer(self, name: str) -> Timer:
        return self._get(name, Timer)

    def histogram(self, name: str, buckets: Optional[Tuple[float, ...]] = None,
                  unit: Optional[str] = "s") -> Histogram:
        """A named histogram. ``buckets``/``unit`` apply only on first
        registration (a histogram's shape is fixed for its lifetime)."""
        return self._get(name, Histogram, buckets, unit)

    def report(self) -> Dict[str, object]:
        out: Dict[str, object] = {}
        with self._lock:
            items = list(self._metrics.items())
        for name, m in items:
            if isinstance(m, Counter):
                out[name] = m.value
            elif isinstance(m, Gauge):
                out[name] = m.value
            elif isinstance(m, Timer):
                out[name] = {
                    "count": m.count, "total_s": m.total_s,
                    "mean_s": m.mean_s, "max_s": m.max_s,
                    "p50_s": m.hist.quantile(0.5),
                    "p99_s": m.hist.quantile(0.99),
                }
            elif isinstance(m, Histogram):
                snap = m.snapshot()
                out[name] = {
                    "count": snap["count"], "sum_s": snap["sum_s"],
                    "p50_s": m.quantile(0.5), "p90_s": m.quantile(0.9),
                    "p99_s": m.quantile(0.99),
                }
        return out

    @staticmethod
    def _prom_hist_lines(metric: str, snap: Dict[str, object],
                         exemplars: bool = False) -> List[str]:
        """Cumulative prometheus histogram lines for one histogram
        SNAPSHOT (``Histogram.snapshot()`` shape — the fleet federation
        renders merged snapshot dicts through the same code). With
        ``exemplars`` (OpenMetrics exposition ONLY — the `#` suffix is a
        parse error under the classic text format, so callers must
        negotiate the content type first), buckets holding an exemplar
        render it in OpenMetrics exemplar syntax
        (`... # {trace_id="…"} value timestamp`), linking the bucket to a
        concrete trace (docs/OBSERVABILITY.md)."""
        ex = (snap.get("exemplars") or {}) if exemplars else {}

        def _ex(i: int) -> str:
            e = ex.get(i)
            if e is None:
                return ""
            tid, val, ts = e
            return f' # {{trace_id="{tid}"}} {val:.6f} {ts:.3f}'

        lines: List[str] = []
        cum = 0
        for i, (le, c) in enumerate(zip(snap["buckets"], snap["counts"])):
            cum += c
            lines.append(f'{metric}_bucket{{le="{le}"}} {cum}{_ex(i)}')
        cum += snap["counts"][-1]
        lines.append(
            f'{metric}_bucket{{le="+Inf"}} {cum}'
            f'{_ex(len(snap["buckets"]))}'
        )
        lines.append(f"{metric}_sum {snap['sum_s']:.6f}")
        lines.append(f"{metric}_count {snap['count']}")
        return lines

    def prometheus(self, exemplars: bool = False) -> str:
        """Prometheus text exposition of all metrics. Timers render their
        legacy count/total/max lines PLUS ``_seconds`` histogram buckets;
        standalone histograms render the standard bucket/sum/count triple
        (p50/p90/p99 derivable with histogram_quantile). ``exemplars``
        adds per-bucket exemplar suffixes — legal ONLY in the OpenMetrics
        exposition (obs.py negotiates it via the Accept header and
        appends the required ``# EOF``); the classic ``version=0.0.4``
        text format must stay exemplar-free or standard scrapers fail the
        whole scrape."""
        lines: List[str] = []
        p = self.prefix
        with self._lock:
            items = list(self._metrics.items())
        for name, m in items:
            metric = f"{p}_{name}".replace(".", "_").replace("-", "_")
            if isinstance(m, Timer):
                lines.append(f"{metric}_count {m.count}")
                lines.append(f"{metric}_seconds_total {m.total_s:.6f}")
                lines.append(f"{metric}_seconds_max {m.max_s:.6f}")
                lines.extend(self._prom_hist_lines(
                    metric + "_seconds", m.hist.snapshot(), exemplars))
            elif isinstance(m, Histogram):
                suffix = "_seconds" if m.unit == "s" else ""
                lines.extend(self._prom_hist_lines(
                    metric + suffix, m.snapshot(), exemplars))
            elif isinstance(m, (Counter, Gauge)):
                lines.append(f"{metric} {m.value}")
        return "\n".join(lines) + "\n"

    def export_snapshot(self) -> Dict[str, object]:
        """STRUCTURED export for metrics federation (docs/OBSERVABILITY.md
        §9): raw counters, sampled gauges, and full histogram bucket
        vectors — NOT the quantile summaries :meth:`report` collapses to.
        The fleet router merges these exactly: counters add, histogram
        ``counts`` add bucket-wise (ladders are compared, never assumed),
        gauges keep per-replica identity. Exemplars are deliberately
        omitted: they are per-process pointers into per-process trace
        retention and do not survive a merge."""
        counters: Dict[str, int] = {}
        gauges: Dict[str, float] = {}
        hists: Dict[str, object] = {}
        timers: Dict[str, object] = {}
        with self._lock:
            items = list(self._metrics.items())
        for name, m in items:
            if isinstance(m, Counter):
                counters[name] = m.value
            elif isinstance(m, Gauge):
                try:
                    gauges[name] = float(m.value)
                except Exception:
                    continue  # a dead callable backing must not kill export
            elif isinstance(m, Timer):
                snap = m.hist.snapshot()
                snap.pop("exemplars", None)
                snap["unit"] = m.hist.unit
                timers[name] = {"count": m.count, "total_s": m.total_s,
                                "max_s": m.max_s, "hist": snap}
            elif isinstance(m, Histogram):
                snap = m.snapshot()
                snap.pop("exemplars", None)
                snap["unit"] = m.unit
                hists[name] = snap
        return {"counters": counters, "gauges": gauges,
                "histograms": hists, "timers": timers}

    def clear(self):
        with self._lock:
            self._metrics.clear()


def _merge_hist(acc: Optional[Dict[str, object]],
                snap: Dict[str, object]) -> Tuple[Dict[str, object], bool]:
    """Merge one histogram snapshot into the accumulator. Returns
    ``(acc, ok)``; ``ok`` is False when the bucket ladders differ (custom
    ladders — FUSION_BATCH_BUCKETS, JOURNAL_*_BUCKETS, the router's merge
    buckets — only merge with themselves; a mismatched snapshot is counted
    as skew, never silently re-binned)."""
    if acc is None:
        return ({"buckets": list(snap["buckets"]),
                 "counts": list(snap["counts"]),
                 "count": int(snap["count"]),
                 "sum_s": float(snap["sum_s"]),
                 "unit": snap.get("unit", "s")}, True)
    if list(acc["buckets"]) != list(snap["buckets"]):
        return acc, False
    acc["counts"] = [a + b for a, b in zip(acc["counts"], snap["counts"])]
    acc["count"] = int(acc["count"]) + int(snap["count"])
    acc["sum_s"] = float(acc["sum_s"]) + float(snap["sum_s"])
    return acc, True


def merge_exports(exports: Dict[str, Dict[str, object]]) -> Dict[str, object]:
    """Merge per-replica :meth:`MetricRegistry.export_snapshot` payloads
    into ONE fleet view: counters and histogram bucket vectors add exactly,
    timers add (max of maxes), gauges stay per-replica keyed by replica id.
    ``bucket_skew`` counts (name -> snapshots dropped) histogram snapshots
    whose ladder disagreed with the first replica's — exactness over
    silent re-binning."""
    counters: Dict[str, int] = {}
    gauges: Dict[str, Dict[str, float]] = {}
    hists: Dict[str, Dict[str, object]] = {}
    timers: Dict[str, Dict[str, object]] = {}
    skew: Dict[str, int] = {}
    for rid in sorted(exports):
        snap = exports[rid] or {}
        for name, v in (snap.get("counters") or {}).items():
            counters[name] = counters.get(name, 0) + int(v)
        for name, v in (snap.get("gauges") or {}).items():
            gauges.setdefault(name, {})[rid] = float(v)
        for name, h in (snap.get("histograms") or {}).items():
            merged, ok = _merge_hist(hists.get(name), h)
            hists[name] = merged
            if not ok:
                skew[name] = skew.get(name, 0) + 1
        for name, t in (snap.get("timers") or {}).items():
            acc = timers.get(name)
            if acc is None:
                timers[name] = {"count": int(t["count"]),
                                "total_s": float(t["total_s"]),
                                "max_s": float(t["max_s"]),
                                "hist": dict(t["hist"])}
                timers[name]["hist"]["buckets"] = list(t["hist"]["buckets"])
                timers[name]["hist"]["counts"] = list(t["hist"]["counts"])
                continue
            acc["count"] += int(t["count"])
            acc["total_s"] += float(t["total_s"])
            acc["max_s"] = max(acc["max_s"], float(t["max_s"]))
            merged, ok = _merge_hist(acc["hist"], t["hist"])
            acc["hist"] = merged
            if not ok:
                skew[name] = skew.get(name, 0) + 1
    return {"replicas": sorted(exports), "counters": counters,
            "gauges": gauges, "histograms": hists, "timers": timers,
            "bucket_skew": skew}


def render_fleet(merged: Dict[str, object], prefix: str = "geomesa",
                 openmetrics: bool = False) -> str:
    """Prometheus text exposition of one :func:`merge_exports` result.
    Fleet-level series (summed counters, bucket-wise-merged histograms,
    added timers) render exactly like a single process's; gauges render
    one line per replica with a ``replica`` label — a gauge is a sampled
    per-process fact and summing it would lie. ``openmetrics`` changes
    nothing here (merged snapshots carry no exemplars) but is accepted so
    the caller can negotiate content types uniformly."""
    del openmetrics  # merged snapshots are exemplar-free by construction

    def mangle(name: str) -> str:
        return f"{prefix}_{name}".replace(".", "_").replace("-", "_")

    lines: List[str] = []
    for name, v in sorted((merged.get("counters") or {}).items()):
        lines.append(f"{mangle(name)} {v}")
    for name, per in sorted((merged.get("gauges") or {}).items()):
        for rid, v in sorted(per.items()):
            lines.append(f'{mangle(name)}{{replica="{rid}"}} {v}')
    for name, h in sorted((merged.get("histograms") or {}).items()):
        suffix = "_seconds" if h.get("unit") == "s" else ""
        lines.extend(MetricRegistry._prom_hist_lines(
            mangle(name) + suffix, h))
    for name, t in sorted((merged.get("timers") or {}).items()):
        metric = mangle(name)
        lines.append(f"{metric}_count {t['count']}")
        lines.append(f"{metric}_seconds_total {t['total_s']:.6f}")
        lines.append(f"{metric}_seconds_max {t['max_s']:.6f}")
        lines.extend(MetricRegistry._prom_hist_lines(
            metric + "_seconds", t["hist"]))
    return "\n".join(lines) + "\n"


_REGISTRY = MetricRegistry()


def registry() -> MetricRegistry:
    return _REGISTRY


def inc(name: str, n: int = 1) -> None:
    """Shorthand: bump a counter in the process registry (used by the
    aggregate cache and the stream quarantine path, which count from hot
    loops and shouldn't re-spell the registry plumbing)."""
    _REGISTRY.counter(name).inc(n)


def observe(name: str, seconds: float,
            trace_id: Optional[str] = None) -> None:
    """Shorthand: record one latency observation into a process-registry
    histogram (span completions in tracing.py use this path). An optional
    ``trace_id`` rides along as the bucket's exemplar."""
    _REGISTRY.histogram(name).observe(seconds, trace_id)


# Aggregate-cache metric names (cache/store.py, cache/service.py). Kept here
# so operators grepping the exposition format find the contract in one place:
#   cache.hit          whole-result hits (no scan at all)
#   cache.partial      partial-cover hits (only the residual cells scanned)
#   cache.miss         queries that found nothing reusable
#   cache.put          entries admitted
#   cache.evict        entries evicted by the size-aware LRU
#   cache.invalidate   entries dropped by a dataset epoch bump
#   cache.bytes        resident cached bytes (gauge)
#   cache.entries      resident entry count (gauge)
#   cache.hierarchy.hit      interior cells served by assembling cached
#                            child cells instead of scanning (zoom-out path)
#   cache.hierarchy.promote  coarse entries written by assembly / bottom-up
#                            sibling roll-up
#   cache.hierarchy.residual cells that fell through to a residual scan
#                            after an assembly attempt found no children
#   cache.polygon            queries decomposed into interior + boundary
#                            cells by the polygon-region path
CACHE_HIT = "cache.hit"
CACHE_HIER_HIT = "cache.hierarchy.hit"
CACHE_HIER_PROMOTE = "cache.hierarchy.promote"
CACHE_HIER_RESIDUAL = "cache.hierarchy.residual"
CACHE_POLYGON = "cache.polygon"
#   cache.curve.region    density_curve queries whose block-chunk loop
#                         split into polygon families (interior chunks
#                         residual-keyed, outside chunks unscanned —
#                         docs/CACHE.md "Polygon curve chunks")
CACHE_CURVE_REGION = "cache.curve.region"
# Warm-path executor metrics (kernels/registry.py, planning/executor.py,
# planning/partitioned_exec.py; docs/PERF.md):
#   kernel.recompiles   fresh jit traces admitted to the kernel registry
#                       (each one paid an XLA trace+compile)
#   kernel.bucket_hit   kernel registry hits — a query served by an
#                       already-compiled kernel (shape bucket + key match)
#   kernel.evict        LRU evictions from the kernel registry
#   pipeline.prefetch   partitions whose host load/column assembly was
#                       overlapped with the previous partition's execution
KERNEL_RECOMPILES = "kernel.recompiles"
KERNEL_BUCKET_HIT = "kernel.bucket_hit"
KERNEL_EVICT = "kernel.evict"
PIPELINE_PREFETCH = "pipeline.prefetch"
# Sharded partitioned scan (planning/partitioned_exec.py; docs/SCALE.md):
#   scan.sharded.queries     queries served by the multi-device fan-out
#   scan.sharded.device.<id> per-device partition dispatches (the bench's
#                            per-device dispatch counts read these)
#   pipeline.deviceput       partitions whose device upload was overlapped
#                            on the prefetch thread (geomesa.pipeline.
#                            device-put; docs/PERF.md)
SCAN_SHARDED = "scan.sharded.queries"
SCAN_SHARDED_DEVICE = "scan.sharded.device"
PIPELINE_DEVICE_PUT = "pipeline.deviceput"
# Device fault tolerance (parallel/health.py, planning/partitioned_exec.py,
# serving/scheduler.py; docs/RESILIENCE.md §6):
#   device.health.<id>        gauge: 1 = ok, 0 = cordoned, -1 = broken
#                             (breaker open / half-open awaiting trial)
#   scan.reassigned           partitions requeued onto a surviving device
#                             after a per-device dispatch failure
#   serving.slot.died         pool dispatcher deaths (per-slot suffix too)
#   serving.slot.respawn      slots respawned by the pool supervisor
#                             (per-slot suffix too)
DEVICE_HEALTH_PREFIX = "device.health"
SCAN_REASSIGNED = "scan.reassigned"
SERVING_SLOT_DIED = "serving.slot.died"
SERVING_SLOT_RESPAWN = "serving.slot.respawn"
# Observability metrics (tracing.py, kernels/registry.py, obs.py;
# docs/OBSERVABILITY.md):
#   kernel.recompiles.<site>   per-jit-site fresh traces (suffix = site)
#   kernel.recompile.alert     gauge: sites over geomesa.kernel.alert.
#                              threshold within the LAST query window
#   kernel.recompile.alerts    total alert trips (counter)
#   trace.<stage>              per-stage latency histograms (span tree)
#   trace.slow                 queries that exceeded geomesa.trace.slow.ms
KERNEL_RECOMPILE_ALERT = "kernel.recompile.alert"
KERNEL_RECOMPILE_ALERTS = "kernel.recompile.alerts"
#   kernel.evict.<site>        per-jit-site LRU evictions (suffix = site)
#   kernel.recompiles.evicted  fresh traces paid for keys the LRU had
#                              previously evicted — the registry-thrash
#                              signal (docs/PERF.md "Registry pressure";
#                              the bench eviction_recompiles key reads it)
KERNEL_RECOMPILE_EVICTED = "kernel.recompiles.evicted"
# Trace export + tail sampling (tracing_export.py; docs/OBSERVABILITY.md):
#   trace.export.exported   traces handed to a sink (after sampling)
#   trace.export.sampled    healthy traces dropped by the sample rate
#   trace.export.dropped    traces dropped on export-queue overflow (the
#                           non-blocking contract: full queue = drop+count,
#                           never a blocked query/dispatch thread)
#   trace.export.failed     sink write failures after retries/breaker
#   trace.export.batches    OTLP batches successfully written
TRACE_EXPORT_EXPORTED = "trace.export.exported"
TRACE_EXPORT_SAMPLED = "trace.export.sampled"
TRACE_EXPORT_DROPPED = "trace.export.dropped"
TRACE_EXPORT_FAILED = "trace.export.failed"
TRACE_EXPORT_BATCHES = "trace.export.batches"
# Per-device utilization + SLO burn (utilization.py, slo.py):
#   device.busy.<id>             gauge: in-flight fraction of device <id>
#                                (dispatch to result-ready, an upper
#                                bound on device time) over the trailing
#                                geomesa.device.busy.window
#   serving.slot.occupancy.<s>   gauge: busy fraction of pool slot <s>
#   slo.burn.<op>                gauge: fast-window burn rate for the
#                                geomesa.slo.<op>.p99.ms target
#   slo.breaker.<name>           gauge: circuit-breaker state on the SLO
#                                alert surface (1 open, 0.5 half-open,
#                                0 closed) — breaker-open transitions page
#                                through the same scrape the burn gauges do
DEVICE_BUSY_PREFIX = "device.busy"
SLOT_OCCUPANCY_PREFIX = "serving.slot.occupancy"
SLO_BURN_PREFIX = "slo.burn"
SLO_BREAKER_PREFIX = "slo.breaker"
# Serving-scheduler metrics (serving/scheduler.py, planning/executor.py;
# docs/SERVING.md):
#   serving.queue.depth     gauge: tickets currently queued (all users)
#   serving.queue.wait      histogram: admission -> dispatch latency
#   serving.admitted        tickets admitted to the queue
#   serving.completed       tickets whose execution finished (any outcome)
#   serving.shed.deadline   tickets shed with [GM-SHED] (budget unmeetable)
#   serving.shed.queue_full tickets rejected with [GM-OVERLOADED]
#   serving.fused           tickets served via a fused batch (every member,
#                           primary included — matches the ledger rollups)
#   serving.fusion.batch    histogram (dimensionless): fused batch sizes
#   exec.device.dispatch    device kernel dispatches issued by the executor
#                           (the fusion-actually-fused bench gate counts it)
SERVING_QUEUE_DEPTH = "serving.queue.depth"
SERVING_QUEUE_WAIT = "serving.queue.wait"
SERVING_ADMITTED = "serving.admitted"
SERVING_COMPLETED = "serving.completed"
SERVING_SHED_DEADLINE = "serving.shed.deadline"
SERVING_SHED_QUEUE_FULL = "serving.shed.queue_full"
#   serving.executor.dispatch.<slot>  groups executed per pool slot (the
#                           pool-actually-parallel bench/CI gate reads
#                           these; docs/SERVING.md)
#   serving.fused.distinct  members served via a DISTINCT-literal batched
#                           pass (query-axis megakernel; docs/SERVING.md
#                           "Query-axis batching")
#   serving.speculative     deadline-shed counts answered with the typed
#                           coarse estimate instead of [GM-SHED] (client
#                           opted in via speculative_ok; docs/SERVING.md)
#   serving.placement.bound fused groups that executed on their preferred
#                           (column-hot) slot after a placement deferral
#   serving.placement.defer fuse-bearing tickets deferred toward their
#                           preferred slot (docs/SERVING.md §5c)
SERVING_FUSED = "serving.fused"
SERVING_FUSED_DISTINCT = "serving.fused.distinct"
SERVING_SPECULATIVE = "serving.speculative"
SERVING_PLACEMENT_BOUND = "serving.placement.bound"
SERVING_PLACEMENT_DEFER = "serving.placement.defer"
SERVING_FUSION_BATCH = "serving.fusion.batch"
SERVING_EXECUTOR_DISPATCH = "serving.executor.dispatch"
EXEC_DEVICE_DISPATCH = "exec.device.dispatch"
#   exec.density.kernel.<kernel>  density dispatches by the kernel of the
#                           density ladder that served them: grouped (the
#                           pallas kernel), mxu (the XLA einsum pair
#                           kernel) or scatter (planning/executor.py)
EXEC_DENSITY_KERNEL = "exec.density.kernel"
#: fused batch-size histogram buckets (members per micro-batch)
FUSION_BATCH_BUCKETS = (1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0)
# Stream-consumer lag (stream/live.py, stream/confluent.py;
# docs/OBSERVABILITY.md):
#   stream.lag          gauge: ms between the last applied message's event
#                       time and its apply time (poll -> apply lag)
#   stream.apply        histogram: per-poll apply-phase latency
STREAM_LAG = "stream.lag"
STREAM_APPLY = "stream.apply"
#   stream.epoch.<schema>   gauge: the live window's mutation epoch — the
#                           staleness anchor standing subscriptions and
#                           window-aggregate caches key on (stream/live.py)
#   stream.poll.batches     counter: applied (non-empty) poll batches
STREAM_EPOCH = "stream.epoch"
STREAM_POLL_BATCHES = "stream.poll.batches"
# Standing subscriptions (geomesa_tpu/subscribe/; docs/STANDING.md):
#   subscribe.groups            gauge: distinct standing groups resident
#   subscribe.subscribers       gauge: registered subscribers (all groups)
#   subscribe.update.dispatches counter: delta evaluation passes — ONE per
#                               applied ingest batch per schema, however
#                               many fused subscribers watch (the CI-gated
#                               one-dispatch contract)
#   subscribe.updates           counter: update records emitted to rings
#   subscribe.rescans           counter: dirty-scoped from-scratch rescans
#                               (deletes, age-off, guard-mismatch imports)
#   subscribe.fused             counter: registrations absorbed into an
#                               existing group (serving-fusion analog)
#   subscribe.verify            counter: delta-vs-rescan bit-identity
#                               assertions run (geomesa.subscribe.verify)
#   subscribe.handoff.exported  counter: groups exported for warm handoff
#   subscribe.handoff.imported  counter: groups adopted verbatim (guard
#                               matched) on import
#   subscribe.handoff.resync    counter: groups re-scanned on import
#                               (guard mismatch -> resync update)
SUBSCRIBE_GROUPS = "subscribe.groups"
SUBSCRIBE_SUBSCRIBERS = "subscribe.subscribers"
SUBSCRIBE_DISPATCHES = "subscribe.update.dispatches"
SUBSCRIBE_UPDATES = "subscribe.updates"
SUBSCRIBE_RESCANS = "subscribe.rescans"
SUBSCRIBE_FUSED = "subscribe.fused"
SUBSCRIBE_VERIFY = "subscribe.verify"
SUBSCRIBE_HANDOFF_EXPORTED = "subscribe.handoff.exported"
SUBSCRIBE_HANDOFF_IMPORTED = "subscribe.handoff.imported"
SUBSCRIBE_HANDOFF_RESYNC = "subscribe.handoff.resync"
CACHE_PARTIAL = "cache.partial"
CACHE_MISS = "cache.miss"
CACHE_PUT = "cache.put"
CACHE_EVICT = "cache.evict"
CACHE_INVALIDATE = "cache.invalidate"
CACHE_BYTES = "cache.bytes"
CACHE_ENTRIES = "cache.entries"
# Spatial joins (planning/join_exec.py; docs/JOIN.md):
#   join.queries          spatial joins executed (count + pair forms)
#   join.cells            co-partition cells that held rows on BOTH sides
#   join.candidate.pairs  pairwise tests actually dispatched (same-cell +
#                         boundary-strip pairs — the O(pairs-in-cell)
#                         account vs the naive N*M)
#   join.pairs            matched pairs emitted
JOIN_QUERIES = "join.queries"
JOIN_CELLS = "join.cells"
JOIN_CANDIDATE_PAIRS = "join.candidate.pairs"
JOIN_PAIRS = "join.pairs"
# Adaptive strategy decision trail (docs/JOIN.md §5): per-strategy joint-
# cell routing counts — join.cells.pairwise / .brute / .split, plus
# join.cells.interior for polygon-join cells matched wholesale with zero
# pairwise work. The prefix is the ledger contract; suffixes come from
# JoinStats.strategy_cells.
JOIN_CELLS_STRATEGY = "join.cells."
#   join.pushdown.bytes   probe-side payload bytes actually read by the
#                         window-pushdown join side scan (vs skipped)
JOIN_PUSHDOWN_BYTES = "join.pushdown.bytes"
# Columnar geo-lake tier (geomesa_tpu/lake/; docs/LAKE.md):
#   lake.bytes.read        payload + footer bytes actually read
#   lake.bytes.skipped     payload bytes statistics-pruning never touched
#   lake.rowgroups.loaded  row groups decoded for scans
#   lake.rowgroups.pruned  row groups excluded by footer statistics
#   lake.pushdown.scans    partition scans served by a pruned partial load
#   cache.persist.restored cache entries re-served from a persisted tier
LAKE_BYTES_READ = "lake.bytes.read"
LAKE_BYTES_SKIPPED = "lake.bytes.skipped"
LAKE_ROWGROUPS_LOADED = "lake.rowgroups.loaded"
LAKE_ROWGROUPS_PRUNED = "lake.rowgroups.pruned"
LAKE_PUSHDOWN_SCANS = "lake.pushdown.scans"
#   lake.pushdown.fallback  pushdown asked for, but the snapshot could not
#                           serve a pruned load (exotic/unbuildable
#                           keyspace, pre-lake snapshot) and fell back to
#                           the full resident load — docs/LAKE.md §10
LAKE_PUSHDOWN_FALLBACK = "lake.pushdown.fallback"
CACHE_PERSIST_RESTORED = "cache.persist.restored"
# Replica fleet (geomesa_tpu/fleet/; docs/RESILIENCE.md §7):
#   fleet.route.affinity   queries served by their ring-owner replica
#   fleet.route.failover   queries re-routed to a later ring owner after
#                          the preferred owner failed/was fenced
#   fleet.route.scatter    decomposable counts split across owner groups
#   fleet.route.partial    queries degraded typed [GM-FLEET-PARTIAL]
#   fleet.epoch.bump       router-stamped mutations
#   fleet.epoch.refresh    replica-side schema refreshes forced by an
#                          incoming request's newer fleet epoch
#   fleet.replica.health.<id>  1 ok / 0 cordoned|draining / -1 broken
FLEET_ROUTE_AFFINITY = "fleet.route.affinity"
FLEET_ROUTE_FAILOVER = "fleet.route.failover"
FLEET_ROUTE_SCATTER = "fleet.route.scatter"
FLEET_ROUTE_PARTIAL = "fleet.route.partial"
FLEET_EPOCH_BUMP = "fleet.epoch.bump"
FLEET_EPOCH_REFRESH = "fleet.epoch.refresh"
FLEET_REPLICA_HEALTH_PREFIX = "fleet.replica.health"
#   fleet.scatter.<kind>   scattered queries by aggregate kind (count /
#                          density / stats / curve — docs/RESILIENCE.md
#                          §7 "Scatter-gather for every mergeable
#                          aggregate")
#   fleet.scatter.merge_ms router-side fixed-order merge cost of one
#                          scattered query's partials (histogram)
#   fleet.uncordon         replicas auto-uncordoned after K consecutive
#                          successful probes (geomesa.fleet.uncordon.probes)
#   fleet.member.join      replicas registered with a router at runtime
#   fleet.member.leave     replicas deregistered at runtime
#   fleet.handoff.entries  cache entries pushed to the new ring owner by
#                          warm-handoff drains
FLEET_SCATTER_KIND_PREFIX = "fleet.scatter"
FLEET_SCATTER_MERGE_MS = "fleet.scatter.merge_ms"
FLEET_UNCORDON = "fleet.uncordon"
FLEET_MEMBER_JOIN = "fleet.member.join"
FLEET_MEMBER_LEAVE = "fleet.member.leave"
FLEET_HANDOFF_ENTRIES = "fleet.handoff.entries"
#   fleet.epoch.marker.quarantined  corrupt fleet-epochs.json markers moved
#                          aside (crc mismatch / unparsable — read as empty,
#                          the safe direction: a redundant refresh, never a
#                          stale serve; docs/RESILIENCE.md §8)
FLEET_EPOCH_MARKER_QUARANTINED = "fleet.epoch.marker.quarantined"
# Durable mutation journal (fs/journal.py; docs/RESILIENCE.md §8):
#   journal.appends         records made durable (acked appends)
#   journal.group.size      histogram: appends per group-commit fsync
#   journal.fsync_ms        histogram: group-commit write+fsync latency (ms)
#   journal.replayed        records re-applied by recovery/refresh replay
#   journal.truncated_bytes bytes reclaimed (checkpoints) or clipped
#                           (torn tails)
#   journal.torn_tails      torn segment tails truncated at open/replay
#   journal.lag             gauge: appended-but-not-yet-durable records
#                           (also the /healthz journal section)
JOURNAL_APPENDS = "journal.appends"
JOURNAL_GROUP_SIZE = "journal.group.size"
JOURNAL_FSYNC_MS = "journal.fsync_ms"
JOURNAL_REPLAYED = "journal.replayed"
JOURNAL_TRUNCATED_BYTES = "journal.truncated_bytes"
JOURNAL_TORN_TAILS = "journal.torn_tails"
JOURNAL_LAG = "journal.lag"
#: group-commit batch-width buckets (appends per fsync)
JOURNAL_GROUP_BUCKETS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0)
#: group-commit fsync latency buckets (milliseconds)
JOURNAL_FSYNC_BUCKETS_MS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0,
                            50.0, 100.0, 250.0)
# Fleet observability plane (fleet/obs.py; docs/OBSERVABILITY.md §9):
#   fleet.federation.scrapes   metrics-export federation sweeps completed
#   fleet.federation.errors    replica snapshots a sweep failed to pull
#                              (the merge proceeds over the survivors)
#   fleet.trace.stitched       stitched cross-replica traces assembled
#   fleet.trace.stitch.failed  scattered traces the stitcher could not
#                              assemble (replica retention expired, fetch
#                              failed) — exported unstitched, counted
#   fleet.anomaly.<id>         gauge: per-replica latency anomaly factor —
#                              worst per-op recent-median ratio vs the
#                              fleet median (1.0 = at median; ≥ the
#                              geomesa.fleet.anomaly.factor threshold is
#                              flagged in /debug/fleet). Observation only.
FLEET_FEDERATION_SCRAPES = "fleet.federation.scrapes"
FLEET_FEDERATION_ERRORS = "fleet.federation.errors"
FLEET_TRACE_STITCHED = "fleet.trace.stitched"
FLEET_TRACE_STITCH_FAILED = "fleet.trace.stitch.failed"
FLEET_ANOMALY_PREFIX = "fleet.anomaly"
# Cell-heat telemetry (heat.py, cache/service.py; docs/OBSERVABILITY.md §9):
#   heat.cells        gauge: distinct (schema, cell) rows resident in the
#                     process heat table
#   heat.evicted      heat rows dropped by the table's size bound
HEAT_CELLS = "heat.cells"
HEAT_EVICTED = "heat.evicted"
#   join.pushdown.residency.hits   chunk-boundary row-group column chunks
#                                  served from the cross-chunk residency
#                                  cache instead of a re-decode
#   join.pushdown.residency.bytes  encoded payload bytes that re-decode
#                                  would have re-read (docs/JOIN.md §11)
JOIN_PUSHDOWN_RESIDENCY_HITS = "join.pushdown.residency.hits"
JOIN_PUSHDOWN_RESIDENCY_BYTES = "join.pushdown.residency.bytes"
#   compact.desc.shared   compact-scan descriptors served from the
#                         content-addressed share (a rebuild avoided:
#                         another site/query resolved the same windows —
#                         docs/PERF.md "Shared descriptors")
COMPACT_DESC_SHARED = "compact.desc.shared"
