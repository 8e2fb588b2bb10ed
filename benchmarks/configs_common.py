"""Helpers the configuration generators share."""

from __future__ import annotations

import numpy as np


def iso_ms(s: str) -> int:
    """ISO-8601 UTC text -> epoch ms."""
    return int(np.datetime64(s.rstrip("Z"), "ms").astype(np.int64))


def ms_iso(ms: int) -> str:
    """Epoch ms -> ISO-8601 UTC text with milliseconds."""
    return str(np.datetime64(int(ms), "ms")) + "Z"


def draw(rng, p: np.ndarray, n: int) -> np.ndarray:
    """``n`` indices drawn with probabilities ``p`` (inverse CDF)."""
    cdf = np.cumsum(p)
    return np.minimum(np.searchsorted(cdf, rng.random(n) * cdf[-1], "right"),
                      len(p) - 1)


def digit_ids(first: int, n: int, width: int = 9) -> np.ndarray:
    """Sequential integer ids from ``first`` as fixed-width ASCII bytes
    (``S<width>``), built without a Python string per row."""
    ids = first + np.arange(n, dtype=np.int64)
    out = np.empty((n, width), np.uint8)
    for k in range(width):
        out[:, width - 1 - k] = (ids // 10 ** k) % 10 + 48
    return out.view(f"S{width}").reshape(n)
