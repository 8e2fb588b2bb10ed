"""Canonical answer forms, shared by the load generator and the comparison.

An answer is what the client decoded from the Flight reply. Counts are
compared by a digest of their canonical bytes; grids and stats are kept
whole, as sparse cells or numbers, once per distinct answer.
"""

from __future__ import annotations

import hashlib

import numpy as np


def _sha(*arrays) -> str:
    h = hashlib.sha1()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def sparse(grid: np.ndarray):
    """Dense grid -> (flat index int64, value) of its nonzero cells."""
    g = np.asarray(grid)
    idx = np.flatnonzero(g)
    return idx.astype(np.int64), g.reshape(-1)[idx]


def count_digest(n: int) -> str:
    return _sha(np.asarray([int(n)], np.int64))


def digest(op: str, answer):
    """The digest of an answer compared whole, or None for a grid or
    stats (kept whole by :func:`keep`)."""
    if op == "count":
        return count_digest(answer)
    return None


def kept_digest(kept: dict) -> str:
    """Digest of a kept answer's arrays."""
    return _sha(*(kept[k] for k in sorted(kept)))


def keep(op: str, answer):
    """The part of an answer kept whole: grids as sparse cells, stats as
    (count, min, max)."""
    if op == "density":
        idx, val = sparse(answer)
        return {"idx": idx, "val": np.asarray(val, np.float64)}
    if op == "stats":
        return {"stat": np.asarray(answer, np.float64)}
    return None
