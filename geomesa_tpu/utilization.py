"""Per-device utilization + executor-slot occupancy accounting
(docs/OBSERVABILITY.md §8).

"How busy is device 2?" is the question the many-core evaluations in
PAPERS.md show scaled geospatial scans lose their headroom on — occupancy,
not kernel speed. Device dispatch is asynchronous, so the time around an
enqueue call says nothing about the device. Instead every device dispatch
site stamps the dispatch (:func:`dispatched`: device id, time) and the
stamp is closed where the host next holds the result (:func:`settle`: the
``scan.sync`` read, the merge that syncs a sharded partial, or the end of
the operation). The closed interval — dispatch to result-ready — is the
time the work was IN FLIGHT: an upper bound on device time that adds no
sync of its own. Per device the intervals are kept as their UNION, so
pipelined dispatches are not counted twice. They roll into:

* ``device.busy.<id>`` gauges — in-flight fraction of each device over
  the trailing ``geomesa.device.busy.window`` seconds;
* ``serving.slot.occupancy.<slot>`` gauges — busy fraction per pool slot
  (host intervals around each dispatched ticket group);
* the ``/debug/devices`` payload (obs.py): per-device in-flight seconds
  and per-slot busy seconds, fractions and interval counts, plus the
  queue-wait vs device-time breakdown (total seconds queries spent
  WAITING vs total seconds devices had work in flight — the
  saturation-vs-starvation signal).

A stamp is one clock read and a list append on the dispatching thread; a
settle takes one lock per device. Settled intervals also feed the
per-query cost ledger (``tracing.add_cost("device_ms.<id>", …)``), so the
same measurement backs fleet gauges AND per-user cost attribution.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Any, Dict

from geomesa_tpu import config, metrics, tracing

#: injectable clock (tests advance time deterministically)
_clock = time.monotonic


class _Usage:
    """Busy intervals for one key: cumulative totals plus a trailing-window
    deque of (end_time, duration) the busy-fraction gauge reads."""

    __slots__ = ("busy_s", "count", "recent", "lock")

    def __init__(self):
        self.busy_s = 0.0
        self.count = 0
        self.recent: "deque" = deque()
        self.lock = threading.Lock()

    def add(self, seconds: float, now: float) -> None:
        with self.lock:
            self.busy_s += seconds
            self.count += 1
            self.recent.append((now, seconds))
            self._trim(now)

    def cover(self, start: float, end: float) -> float:
        """Union [start, end] into the intervals: adds only the part no
        earlier interval covers (the trailing deque holds (end, duration)
        pairs, disjoint once merged) and returns it."""
        with self.lock:
            lo, hi, overlap = start, end, 0.0
            while self.recent and self.recent[-1][0] >= start:
                e, d = self.recent.pop()
                s = e - d
                overlap += max(min(e, end) - max(s, start), 0.0)
                lo, hi = min(lo, s), max(hi, e)
            new = max(end - start - overlap, 0.0)
            self.busy_s += new
            self.count += 1
            self.recent.append((hi, hi - lo))
            self._trim(end)
        return new

    def _trim(self, now: float) -> None:
        win = _window_s()
        while self.recent and self.recent[0][0] < now - win:
            self.recent.popleft()

    def fraction(self) -> float:
        """Busy fraction over the trailing window: sum of interval
        durations clipped to the window, over the window length. Clamped
        to 1.0 (intervals recorded by :func:`record_slot` may overlap;
        in-flight device intervals are merged as they are covered)."""
        now = _clock()
        win = _window_s()
        with self.lock:
            self._trim(now)
            total = 0.0
            for end, dur in self.recent:
                start = end - dur
                total += end - max(start, now - win)
        return min(total / win, 1.0) if win > 0 else 0.0

    def snapshot(self) -> Dict[str, Any]:
        with self.lock:
            busy, n = self.busy_s, self.count
        return {
            "busy_s": round(busy, 6),
            "busy_fraction": round(self.fraction(), 4),
            "intervals": n,
        }


def _window_s() -> float:
    try:
        w = config.DEVICE_BUSY_WINDOW.to_float()
    except (TypeError, ValueError):
        w = None
    return 60.0 if w is None or w <= 0 else w


_lock = threading.Lock()
_devices: Dict[int, _Usage] = {}
_slots: Dict[int, _Usage] = {}
_gauged = set()
#: queue-wait half of the breakdown (seconds queries spent queued, fed by
#: the serving scheduler at dispatch time)
_wait = _Usage()


def _usage(table: Dict[int, _Usage], key: int, gauge_name: str) -> _Usage:
    u = table.get(key)
    if u is None:
        with _lock:
            u = table.get(key)
            if u is None:
                u = table[key] = _Usage()
    if gauge_name not in _gauged:
        with _lock:
            if gauge_name not in _gauged:
                # one bound method per key backs the gauge; replace=True
                # because reset() (tests, metrics.clear survivors) leaves
                # a stale backing the fresh _Usage must take over from
                metrics.registry().gauge(gauge_name, u.fraction,
                                         replace=True)
                _gauged.add(gauge_name)
    return u


def record_slot(slot: int, seconds: float) -> None:
    """One serving-pool slot busy interval (a dispatched ticket group)."""
    s = int(slot)
    _usage(_slots, s,
           f"{metrics.SLOT_OCCUPANCY_PREFIX}.{s}").add(seconds, _clock())


def record_wait(seconds: float) -> None:
    """One query's queue wait (the other half of the wait-vs-work
    breakdown in /debug/devices)."""
    _wait.add(seconds, _clock())


#: the calling thread's open dispatch stamps: [(device id, time), ...]
_stamps = threading.local()
_MAX_OPEN = 1024


def dispatched(device_id: int) -> None:
    """Stamp one device dispatch on the calling thread. The stamp stays
    open until :func:`settle` closes it where the host holds the result."""
    lst = getattr(_stamps, "open", None)
    if lst is None:
        lst = _stamps.open = []
    elif len(lst) >= _MAX_OPEN:
        settle()  # a thread that never reads its results: bound the list
        lst = _stamps.open = []
    lst.append((int(device_id), _clock()))


def detach() -> list:
    """Take the calling thread's open stamps (a sharded scan carries each
    partition's stamps to the merge that syncs its partial)."""
    lst = getattr(_stamps, "open", None)
    _stamps.open = None
    return lst or []


def attach(stamps) -> None:
    """Hand stamps back to the calling thread's open set (a sharded
    partition's, before its finish reads or merges the partial)."""
    if stamps:
        lst = getattr(_stamps, "open", None)
        if lst is None:
            lst = _stamps.open = []
        lst.extend(stamps)


def settle(stamps=None) -> None:
    """Close ``stamps`` (default: every open stamp of the calling thread)
    now: the host holds their results. Each device gets the union of its
    dispatch-to-now intervals, and the active trace's cost ledger gets
    the in-flight milliseconds that union gained, so overlapping settles
    on one device are counted once in both."""
    if stamps is None:
        stamps = getattr(_stamps, "open", None)
        if not stamps:
            return
        _stamps.open = None
    if not stamps:
        return
    now = _clock()
    first: Dict[int, float] = {}
    for did, t0 in stamps:
        first[did] = min(t0, first.get(did, t0))
    for did, t0 in first.items():
        new = _usage(_devices, did,
                     f"{metrics.DEVICE_BUSY_PREFIX}.{did}").cover(t0, now)
        tracing.add_cost(f"device_ms.{did}", new * 1e3)


class _SettleOnExit:
    """Context manager closing the thread's open stamps on exit (the end
    of a dataset operation). A shared singleton: nothing is allocated."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        settle()
        return False


#: ``with utilization.OP_END:`` around an operation settles what it left open
OP_END = _SettleOnExit()


@contextlib.contextmanager
def slot_busy(slot: int):
    """Time one pool-slot dispatch as a busy interval."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record_slot(slot, time.perf_counter() - t0)


def snapshot() -> Dict[str, Any]:
    """The /debug/devices payload: per-device in-flight time (dispatch to
    result-ready, an upper bound on device time) and per-slot usage, plus
    the queue-wait vs device-time breakdown."""
    with _lock:
        devs = dict(_devices)
        slots = dict(_slots)
    device_busy_s = sum(u.busy_s for u in devs.values())
    return {
        "window_s": _window_s(),
        "devices": {str(k): u.snapshot() for k, u in sorted(devs.items())},
        "slots": {str(k): u.snapshot() for k, u in sorted(slots.items())},
        "breakdown": {
            "queue_wait_s": round(_wait.busy_s, 6),
            "device_time_s": round(device_busy_s, 6),
            "waits": _wait.count,
        },
    }


def reset() -> None:
    """Drop all usage state (test isolation). Gauges registered against
    previous _Usage objects are re-pointed on next use via replace."""
    global _wait
    with _lock:
        _devices.clear()
        _slots.clear()
        _gauged.clear()
        _wait = _Usage()
    _stamps.open = None
