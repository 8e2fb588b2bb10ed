"""Version-stable compiled-kernel registry + shape bucketing (warm path).

GeoMesa's tablet-server iterators are compile-free; the TPU port instead
pays an XLA trace+compile for every *new* jitted scan kernel. This module
is the executor's warm-path substrate (docs/PERF.md):

* :class:`KernelRegistry` — a bounded, thread-safe LRU of jitted kernels,
  shared across time partitions of one store AND across aggregate-cache
  cell queries (one registry per parent store / partitioned executor).
  Entries evict one at a time, least-recently-used first — never the
  clear-on-overflow wipe the per-site dicts used to do, which threw away
  63 hot kernels to admit the 65th. Default capacity 512
  (``geomesa.kernel.cache.size``; raised from 256 when the query-axis
  batch kernels widened the key space with the padded member axis —
  docs/PERF.md "Registry pressure"; the eviction counts behind the raise
  were not measured on the chip).
* **version-stable keys** — kernel cache keys carry NO store version: the
  compiled function is structure-only (shapes + predicate closure), so a
  store mutation must not recompile anything. What CAN invalidate a
  compiled closure is dictionary growth (string predicates bake resolved
  codes at compile time): :func:`dict_fingerprint` captures exactly that.
  Window *data* stays version-keyed in the executor's separate win caches.
* **shape bucketing** — :func:`bucket_count` pads the per-shard window
  count K to a power of two above a floor, so distinct-but-similar
  queries land on one compiled shape (padded windows are empty and the
  ``valid``/``counts`` masks keep results exact).
* **persistent compile cache** — :func:`enable_persistent_cache` keeps
  JAX's compilation cache in ``JAX_COMPILATION_CACHE_DIR``, else
  ``geomesa.compile.cache.dir``, else ``<checkout>/.jax_cache``, so
  restarts start warm.

Metrics (process registry): ``kernel.recompiles`` (fresh traces),
``kernel.bucket_hit`` (registry hits), ``kernel.evict``.
"""

from __future__ import annotations

import os
import threading
import time as _time
from collections import OrderedDict
from typing import Any, Dict, Hashable, Optional, Tuple

from geomesa_tpu import config, metrics, tracing

#: metric names (declared in metrics.py with the exposition contract)
KERNEL_RECOMPILES = metrics.KERNEL_RECOMPILES
KERNEL_HIT = metrics.KERNEL_BUCKET_HIT
KERNEL_EVICT = metrics.KERNEL_EVICT


# ---------------------------------------------------------------------------
# Per-query recompile window + alert (the ROADMAP "surface per-site
# recompile counts as alerts in the metrics exposition" item). Every fresh
# trace bumps a per-site counter (kernel.recompiles.<site>) and a
# thread-local per-QUERY window; a site paying more than
# geomesa.kernel.alert.threshold traces within one query trips the
# kernel.recompile.alert gauge — the warm-path-broken signal (a healthy
# steady state compiles at most once per site per novel shape bucket).
#
# The gauge LATCHES for _ALERT_TTL_S after the last trip instead of being
# zeroed by the next query: windows are thread-local but the gauge is
# process-global, so clear-on-next-query would let concurrent (or merely
# subsequent) queries race a trip away before any scraper could see it.
# ---------------------------------------------------------------------------

_query_window = threading.local()

_MISSING = object()  # OrderedDict.pop sentinel (None is a valid value)

#: how long a trip stays visible on the gauge (covers realistic scrape
#: intervals; the kernel.recompile.alerts counter is the durable record)
_ALERT_TTL_S = 300.0
_alert_lock = threading.Lock()
_alert_state = {"at": 0.0, "over": 0}


def _alert_value() -> float:
    """Callable backing of the kernel.recompile.alert gauge: the number of
    sites over threshold in the most recent tripped window, until the
    latch TTL expires."""
    with _alert_lock:
        if _time.monotonic() - _alert_state["at"] <= _ALERT_TTL_S:
            return float(_alert_state["over"])
    return 0.0


def _ensure_alert_gauge() -> None:
    # same module-level fn every time: registration is idempotent and
    # survives a registry.clear() (re-registered on the next query)
    metrics.registry().gauge(metrics.KERNEL_RECOMPILE_ALERT, _alert_value)


def reset_alert() -> None:
    """Clear the alert latch (tests)."""
    with _alert_lock:
        _alert_state["at"] = 0.0
        _alert_state["over"] = 0


def _site_slug(site) -> str:
    """Metric-name-safe jit-site label."""
    s = str(site)
    return "".join(ch if (ch.isalnum() or ch in "._-") else "_" for ch in s)


def begin_query_window() -> None:
    """Reset this thread's per-query recompile window (called at the top
    of every query plan). The alert gauge is NOT cleared here — it latches
    for _ALERT_TTL_S so a trip survives until a scraper can observe it."""
    _query_window.counts = {}
    _ensure_alert_gauge()


def query_recompiles() -> Dict[str, int]:
    """site -> fresh traces paid by the CURRENT query window (explain's
    Warm path section reports this next to the lifetime totals)."""
    return dict(getattr(_query_window, "counts", {}))


def alert_threshold() -> int:
    """Effective geomesa.kernel.alert.threshold (single source of the
    default — explain and the trip logic must agree)."""
    t = config.KERNEL_ALERT_THRESHOLD.to_int()
    return 3 if t is None else t


def _note_recompile(site) -> None:
    slug = _site_slug(site)
    metrics.inc(KERNEL_RECOMPILES)
    metrics.inc(f"{KERNEL_RECOMPILES}.{slug}")
    # visible INSIDE the query that paid for it (span-tree event)
    tracing.event("kernel.recompile", site=slug)
    counts = getattr(_query_window, "counts", None)
    if counts is None:
        return
    counts[slug] = counts.get(slug, 0) + 1
    threshold = alert_threshold()
    if counts[slug] > threshold:
        over = sum(1 for v in counts.values() if v > threshold)
        with _alert_lock:
            _alert_state["at"] = _time.monotonic()
            _alert_state["over"] = over
        _ensure_alert_gauge()
        if counts[slug] == threshold + 1:  # first trip for this site
            metrics.inc(metrics.KERNEL_RECOMPILE_ALERTS)
            tracing.event("kernel.recompile.alert", site=slug,
                          recompiles=counts[slug])


class KernelRegistry:
    """Bounded LRU of compiled kernels, keyed by version-stable tuples.

    The mapping protocol mirrors the plain dicts it replaces (``get`` /
    ``put``) plus per-site trace accounting: ``key[0]`` (or, for tagged
    keys, ``key[0][0]``) names the jit site, and :meth:`traces` reports
    how many fresh compiles each site has paid — the recompile-regression
    tests assert directly on it.
    """

    def __init__(self, capacity: Optional[int] = None):
        self._entries: "OrderedDict[Hashable, Any]" = OrderedDict()
        self._capacity = capacity
        self._lock = threading.Lock()
        #: site label -> fresh-trace count (puts, not hits)
        self._traces: Dict[Any, int] = {}
        #: site label -> entries evicted (kernel.evict.<site> twin, kept
        #: here so explain/tests can read per-registry pressure directly)
        self._evicts: Dict[Any, int] = {}
        #: keys evicted and not since re-admitted (bounded FIFO set): a
        #: put() whose key is in here is an EVICTION-CAUSED recompile —
        #: the LRU was too small for the live working set, the thrash
        #: signal docs/PERF.md's registry-pressure check watches
        #: (kernel.recompiles.evicted + the bench eviction_recompiles key)
        self._evicted_keys: "OrderedDict[Hashable, None]" = OrderedDict()
        self._evicted_recompiles = 0

    _EVICTED_KEYS_MAX = 4096

    def _cap(self) -> int:
        if self._capacity is not None:
            return self._capacity
        return config.KERNEL_CACHE_SIZE.to_int() or 512

    @staticmethod
    def _site(key: Hashable) -> Any:
        site = key[0] if isinstance(key, tuple) and key else key
        if isinstance(site, tuple) and site:
            site = site[0]
        return site

    def get(self, key: Hashable, default=None):
        if key is None:
            return default
        with self._lock:
            fn = self._entries.get(key)
            if fn is None:
                return default
            self._entries.move_to_end(key)
        metrics.inc(KERNEL_HIT)
        return fn

    def put(self, key: Hashable, fn) -> None:
        """Admit one freshly-traced kernel, evicting LRU entries over
        capacity (one at a time — the clear-on-overflow this replaces
        wiped every hot kernel to admit one). Evictions account per SITE
        (``kernel.evict.<site>``), and re-tracing a previously-evicted
        key counts as an eviction-caused recompile
        (``kernel.recompiles.evicted``) — the LRU-pressure signals the
        docs/PERF.md registry check reads."""
        with self._lock:
            self._entries[key] = fn
            self._entries.move_to_end(key)
            site = self._site(key)
            self._traces[site] = self._traces.get(site, 0) + 1
            evicted_from = self._evicted_keys.pop(key, _MISSING)
            if evicted_from is not _MISSING:
                self._evicted_recompiles += 1
            evicted_sites = []
            cap = max(self._cap(), 1)
            while len(self._entries) > cap:
                ekey, _ = self._entries.popitem(last=False)
                esite = self._site(ekey)
                self._evicts[esite] = self._evicts.get(esite, 0) + 1
                evicted_sites.append(esite)
                self._evicted_keys[ekey] = None
                while len(self._evicted_keys) > self._EVICTED_KEYS_MAX:
                    self._evicted_keys.popitem(last=False)
        _note_recompile(site)
        if evicted_from is not _MISSING:
            metrics.inc(metrics.KERNEL_RECOMPILE_EVICTED)
        if evicted_sites:
            metrics.inc(KERNEL_EVICT, len(evicted_sites))
            for esite in evicted_sites:
                metrics.inc(f"{KERNEL_EVICT}.{_site_slug(esite)}")

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def traces(self, site=None):
        """Fresh-compile count per jit site (or one site's count)."""
        with self._lock:
            if site is not None:
                return self._traces.get(site, 0)
            return dict(self._traces)

    def evicts(self, site=None):
        """LRU evictions per jit site (or one site's count) — the
        per-registry twin of the kernel.evict.<site> metrics."""
        with self._lock:
            if site is not None:
                return self._evicts.get(site, 0)
            return dict(self._evicts)

    def evicted_recompiles(self) -> int:
        """Fresh traces paid for keys the LRU had previously evicted —
        nonzero means the working set exceeds the capacity
        (geomesa.kernel.cache.size; docs/PERF.md)."""
        with self._lock:
            return self._evicted_recompiles

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()


def dict_fingerprint(dicts: Dict[str, Any]) -> Tuple:
    """Compiled-predicate validity fingerprint: string predicates resolve
    dictionary codes at compile time, and dictionaries are append-only, so
    per-encoder vocabulary *length* captures every growth that could change
    a compiled closure. Mutations that don't grow a vocabulary (inserts of
    known strings, numeric updates, deletes) leave it unchanged — the
    warm-path guarantee that a store mutation never forces a recompile."""
    return tuple(sorted((k, len(d.values)) for k, d in dicts.items()))


def bucket_batch(n: int) -> int:
    """Pad a fused micro-batch's member count to the next power of two, so
    one batched-parameter kernel (its registry key carries the padded
    member axis next to the usual version-stable token — see
    ``Executor.density_curve_batch``) serves every batch size in the
    bucket instead of tracing per size (docs/SERVING.md). Padded members
    carry zero-length parameter spans and are dropped at de-interleave."""
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def bucket_count(n: int) -> int:
    """Pad a per-shard window count to its shape bucket: the next power of
    two, floored at ``geomesa.compact.bucket.floor``. Identity when
    ``geomesa.compact.bucketing`` is off (old behavior: exact pow2)."""
    if n <= 1:
        n = 1
    else:
        n = 1 << (n - 1).bit_length()
    if not config.COMPACT_BUCKETING.to_bool():
        return n
    floor = config.COMPACT_BUCKET_FLOOR.to_int()
    floor = 8 if floor is None else max(floor, 1)
    return max(n, floor)


#: the default cache directory: fixed, because the path is part of what
#: JAX's cache is keyed by (a directory that moves never hits)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)
_persistent_cache_lock = threading.Lock()


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and no
    directory is set here; otherwise a deployment's
    ``geomesa.compile.cache.dir``; otherwise :data:`CHECKOUT_CACHE_DIR`.
    Compiles of every size are kept: scan kernels compile fast but are
    traced often, and the default minimum compile time would skip them."""
    import jax

    with _persistent_cache_lock:
        d = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not d:
            d = config.COMPILE_CACHE_DIR.get() or CHECKOUT_CACHE_DIR
            if jax.config.jax_compilation_cache_dir != d:
                jax.config.update("jax_compilation_cache_dir", d)
        if jax.config.jax_persistent_cache_min_compile_time_secs != 0:
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return d
