"""The executor's per-view host caches and their one owner.

A cold view pays for window resolution, the compact descriptor, the slab
gather and the density schedule; the executor keeps each result in a named
:class:`BoundedCache` of the :class:`ViewCaches` that :func:`caches_of`
returns for a store (or, for plans not reproducible from query text, for
the plan itself). Keys carry the store version where the data depends on
it, so a mutation needs no invalidation pass.

Policy: a full cache empties itself whole on the next put (clear-on-full),
not least-recently-used. The benchmark's view pools are sized against it
(``benchmarks/traffic/*.json``, field ``about``): under seed-drawn cyclic
visits an LRU would warm a pool just past 64 views, so changing the policy
changes what the cells measure and goes together with resized pools.
"""

from __future__ import annotations

from typing import Any, Dict

#: entries of every per-view cache but the two below
VIEW_CACHE_SIZE = 64
#: f32-band verdicts: one per (query text, store version, windows)
BAND_VERDICT_CACHE_SIZE = 256
#: curve-density CDF positions: one per (level, crop window)
CURVE_POS_CACHE_SIZE = 32


class BoundedCache:
    """A map of at most ``capacity`` entries that empties itself whole when
    a put finds it full."""

    __slots__ = ("capacity", "_entries")

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._entries: Dict[Any, Any] = {}

    def get(self, key):
        return self._entries.get(key)

    def put(self, key, value):
        """Store ``value`` under ``key`` and return it."""
        if len(self._entries) >= self.capacity:
            self._entries.clear()
        self._entries[key] = value
        return value

    def put_all(self, entries: Dict[Any, Any]) -> None:
        """Store the entries of one miss (the slabs of one scan's columns)
        after a single fullness check."""
        if len(self._entries) >= self.capacity:
            self._entries.clear()
        self._entries.update(entries)

    def clear(self) -> None:
        self._entries.clear()

    def items(self):
        return self._entries.items()

    def __len__(self) -> int:
        return len(self._entries)


class ViewCaches:
    """The named per-view caches of one store or one token-less plan."""

    __slots__ = ("resolved", "device_windows", "slabs", "shared_descriptors",
                 "ladder", "chunk_boxes", "sample_spans", "band_verdicts",
                 "curve_positions")

    def __init__(self):
        #: host scan windows, coarse (``win``) and fine (``fine``), and the
        #: compact (``compact_desc``) and mesh descriptors built from them
        self.resolved = BoundedCache(VIEW_CACHE_SIZE)
        #: device window arrays: padded, compact, mesh and batch
        self.device_windows = BoundedCache(VIEW_CACHE_SIZE)
        #: device [C, B] slabs, one entry per column
        self.slabs = BoundedCache(VIEW_CACHE_SIZE)
        #: compact descriptors addressed by their window bytes
        self.shared_descriptors = BoundedCache(VIEW_CACHE_SIZE)
        #: the density ladder's ``(kernel, schedule, P, C)`` per view
        self.ladder = BoundedCache(VIEW_CACHE_SIZE)
        #: per-chunk key boxes of a compact layout
        self.chunk_boxes = BoundedCache(VIEW_CACHE_SIZE)
        #: (min, max) of raw integer sample-by keys
        self.sample_spans = BoundedCache(VIEW_CACHE_SIZE)
        #: surviving f32-band rows of a scan
        self.band_verdicts = BoundedCache(BAND_VERDICT_CACHE_SIZE)
        #: curve-density CDF positions
        self.curve_positions = BoundedCache(CURVE_POS_CACHE_SIZE)


def caches_of(host) -> ViewCaches:
    """The :class:`ViewCaches` that ``host`` (a store or a plan) owns,
    made on first use."""
    caches = vars(host).get("_view_caches")
    if caches is None:
        caches = vars(host).setdefault("_view_caches", ViewCaches())
    return caches
