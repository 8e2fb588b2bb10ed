"""The yardstick of ``kernel.scan_roofline``: the least device time a
request's scan could take on the chip.

Bytes: the rows that MATCH the request's filter (from the reference, not
the plan's candidates) times the bytes per row of every schema attribute
its filter and aggregate read, at the fixed sizes below, plus the bytes of
its result. Operations: the same rows times the comparisons and arithmetic
the filter and aggregate need per row. The least time is the larger of
bytes over peak HBM bandwidth and operations over peak arithmetic; a PR
that reads fewer rows raises the share and cannot shrink the yardstick.
"""

from __future__ import annotations

import json
import os

#: bytes per row of an attribute read by a scan, by schema type (a String
#: is read as its int32 dictionary code; a Point as two f32 coordinates; a
#: Date as its int32 (bin, offset) pair)
BYTES = {"Point": 8, "Date": 8, "Float": 4, "Integer": 4, "String": 4,
         "Double": 8, "Long": 8}

#: operations per matched row
OPS = {"bbox": 4, "during": 4, "density": 9, "weight": 1, "minmax": 2,
       "count": 1}

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmarks/peaks.json")
    return table[device_kind]


def request_cost(req: dict, matched: int, types: dict, geom: str,
                 dtg: str):
    """(bytes, operations) the request's scan needs at least."""
    attrs = {dtg, geom}
    ops = OPS["during"] + OPS["bbox"]
    op = req["op"]
    if op == "density":
        ops += OPS["density"]
        W, H = req["grid"]
        result = W * H * 4
        if req.get("weight"):
            attrs.add(req["weight"])
            ops += OPS["weight"]
    elif op == "stats":
        attr = req["stat"].split("MinMax(")[1].split(")")[0]
        attrs.add(attr)
        ops += OPS["minmax"] + OPS["count"]
        result = 24
    else:
        ops += OPS["count"]
        result = 8
    per_row = sum(BYTES[types[a]] for a in attrs)
    return matched * per_row + result, matched * ops


def least_time_s(bytes_: float, ops: float, peak: dict) -> float:
    return max(bytes_ / peak["hbm_bytes_per_s"], ops / peak["flops"])
