"""Plain reference: the answer to one request, from the raw arrays.

Independent of the package under test: nothing here imports it. The
semantics are the ones the store documents and ``chip_smoke.py`` proved on
the chip: BBOX is inclusive on both edges and exact on the f64 coordinates;
DURING is inclusive on both ends and exact in epoch ms; a density cell is
binned from the f32 coordinates the device keeps, in f32 arithmetic; a
weighted cell is the f64 sum of the weights (integers here, so exact); MinMax reads the stored
values.

``precision="bf16"`` is the control: the same arithmetic on coordinates,
weights and bbox literals first rounded to bfloat16 (the next precision
below the f32 the configurations state), which a correct run must fail.

Data (``data``) holds ``x``, ``y`` (f64), ``t`` (epoch ms, int64, sorted
ascending) and one array per numeric attribute a request reads.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def _bf16(a):
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def _rows(data: Dict, req: Dict):
    """Row range of the request's time window (inclusive both ends)."""
    t = data["t"]
    return (int(np.searchsorted(t, req["t0"], "left")),
            int(np.searchsorted(t, req["t1"], "right")))


def mask(data: Dict, req: Dict, precision: str = "f64"):
    """(first row, boolean mask over rows first..first+len) of the rows that
    match the request's filter."""
    i0, i1 = _rows(data, req)
    x, y = data["x"][i0:i1], data["y"][i0:i1]
    x0, y0, x1, y1 = req["bbox"]
    if precision == "bf16":
        x, y = _bf16(x), _bf16(y)
        x0, y0, x1, y1 = (float(v) for v in _bf16([x0, y0, x1, y1]))
    return i0, (x >= x0) & (x <= x1) & (y >= y0) & (y <= y1)


def _bins(v, lo: float, span: float, n: int):
    """Cell of f32 coordinates ``v`` along one axis, binned in f32."""
    f = np.float32
    q = (v - f(lo)) / f(span)
    return np.clip((q * f(n)).astype(np.int32), 0, n - 1)


def _density_points(data: Dict, req: Dict, precision: str):
    i0, m = mask(data, req, precision)
    sl = slice(i0, i0 + len(m))
    x = data["x"][sl][m].astype(np.float32)
    y = data["y"][sl][m].astype(np.float32)
    if precision == "bf16":
        x, y = _bf16(x), _bf16(y)
    w = req.get("weight")
    wv = None
    if w:
        wv = data[w][sl][m]
        if precision == "bf16":
            wv = _bf16(wv)
        wv = wv.astype(np.float64)
    return x, y, wv


def density(data: Dict, req: Dict, precision: str = "f64") -> np.ndarray:
    """The [height, width] grid (f32 counts, or f64 sums of weights)."""
    x, y, wv = _density_points(data, req, precision)
    xmin, ymin, xmax, ymax = req["bbox"]
    W, H = req["grid"]
    px = _bins(x, xmin, xmax - xmin, W)
    py = _bins(y, ymin, ymax - ymin, H)
    cell = py.astype(np.int64) * W + px
    if wv is None:
        return np.bincount(cell, minlength=W * H).reshape(H, W).astype(
            np.float32)
    return np.bincount(cell, weights=wv, minlength=W * H).reshape(H, W)


def stats(data: Dict, req: Dict, precision: str = "f64"):
    """``Count();MinMax(<attr>)`` as (count, min, max); NaN when empty."""
    i0, m = mask(data, req, precision)
    attr = req["stat"].split("MinMax(")[1].split(")")[0]
    v = data[attr][i0:i0 + len(m)][m]
    if precision == "bf16":
        v = _bf16(v)
    if len(v) == 0:
        return (0, float("nan"), float("nan"))
    return (int(m.sum()), float(v.min()), float(v.max()))


def answer(data: Dict, req: Dict, precision: str = "f64"):
    """The reference answer of one request, in the client's decoded form."""
    op = req["op"]
    if op == "count":
        return int(mask(data, req, precision)[1].sum())
    if op == "density":
        return density(data, req, precision)
    if op == "stats":
        return stats(data, req, precision)
    raise ValueError(f"unknown op {op!r}")


def matched_rows(data: Dict, req: Dict) -> int:
    """Rows that match the request's filter (the roofline's row count)."""
    return int(mask(data, req)[1].sum())
