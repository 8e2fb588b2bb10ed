"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged over
  the devices; window: the span the trace covers;
* device ops: total device time by operation name;
* idle gaps: the stretches between busy intervals, each named by the
  innermost ``geomesa:<span>`` host annotation that covers its middle
  (what the server was doing while the chip waited), or ``-`` when none.

Read with nothing but JAX (``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
ANNOTATION = "geomesa:"

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The stretches of [lo, hi] that no busy interval covers."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def name_gap(gap: Interval, annotations: Sequence[Tuple[float, float, str]]):
    """The innermost annotation covering the gap's middle, or ``-``."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for s, e, name in annotations:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2][len(ANNOTATION):] if best else "-"


def op_name(event_name: str) -> str:
    """An HLO op event's short name: the instruction before its text."""
    return event_name.split(" = ")[0].lstrip("%")


def load(path: str):
    """(device op events per device, host annotations, trace bounds) from
    an ``.xplane.pb`` file (or its gzip), times in ns."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices: Dict[str, List[Tuple[float, float, str]]] = {}
    notes: List[Tuple[float, float, str]] = []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        is_dev = bool(DEVICE_PLANE.match(plane.name))
        is_host = plane.name.startswith("/host:")
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            evs = [(float(e.start_ns), float(e.end_ns), e.name)
                   for e in line.events]
            for s, e, _ in evs:
                lo, hi = min(lo, s), max(hi, e)
            if is_dev and line.name == OPS_LINE:
                devices.setdefault(plane.name, []).extend(evs)
            elif is_host:
                notes.extend(ev for ev in evs if ev[2].startswith(ANNOTATION))
    return devices, notes, (lo, hi)


def reduce(path: str, top: int = 10) -> Dict:
    """Busy and window seconds, top device ops and longest idle gaps."""
    devices, notes, (lo, hi) = load(path)
    if not devices:
        devices = {"none": []}
    window_ns = hi - lo
    busy_ns, per_op = [], {}
    merged_all = []
    for evs in devices.values():
        merged = union(clip([(s, e) for s, e, _ in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in merged))
        merged_all.append(merged)
        for s, e, name in evs:
            name = op_name(name)
            per_op[name] = per_op.get(name, 0.0) + (e - s)
    first = merged_all[0]
    idle = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": window_ns / 1e9,
        "devices": len(devices),
        "device_ops": [[n, t / 1e9] for n, t in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name_gap(g, notes), (g[1] - g[0]) / 1e9]
                      for g in idle],
    }


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)
