"""Z-order (Morton) space-filling curves.

Capability parity with the reference's Z2SFC (geomesa-z3/.../curve/Z2SFC.scala:22,
31 bits/dim) and Z3SFC (Z3SFC.scala:22, 21 bits/dim + binned time), including the
bit-interleave kernels that the reference pulls from the external ``sfcurve``
library (declared at geomesa-z3/pom.xml:21) — implemented here from scratch.

Two implementations of the encode kernel:

* **Host (numpy, uint64)** — the ingest path. Encoding a batch of points is a
  handful of vectorized bit ops; this is where sort keys are computed before
  device upload.
* **Device (jnp, uint32 pair)** — JAX has no 64-bit ints without global x64 mode
  (and TPU prefers 32-bit lanes), so on device a z-value is an ``(hi, lo)``
  pair of uint32 arrays. Comparisons are lexicographic on the pair. The encode
  is a statically-unrolled bit-spread, fully vectorized over points.

Bit layout convention (matches the cover algorithm in ``cover.py``): for d
dimensions, bit ``i`` of dimension ``k`` (k=0 most significant) lands at
position ``d*i + (d-1-k)`` — i.e. within each group of d bits, dimension 0 is
the highest bit.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import numpy as np

from geomesa_tpu import config
from geomesa_tpu.curves.binned_time import BinnedTime, TimePeriod
from geomesa_tpu.curves.cover import zcover_fast


# ---------------------------------------------------------------------------
# Dimension normalization (reference: sfcurve NormalizedDimension; lossy
# fixed-point mapping of a float extent onto [0, 2^bits - 1]).
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class NormalizedDimension:
    lo: float
    hi: float
    bits: int

    @property
    def max_index(self) -> int:
        return (1 << self.bits) - 1

    def normalize(self, x: np.ndarray) -> np.ndarray:
        """float -> fixed-point index (clipped to the extent). Vectorized."""
        x = np.asarray(x, dtype=np.float64)
        scaled = (x - self.lo) / (self.hi - self.lo) * (1 << self.bits)
        return np.clip(np.floor(scaled), 0, self.max_index).astype(np.uint64)

    def denormalize(self, i: np.ndarray) -> np.ndarray:
        """fixed-point index -> cell-center float. Vectorized."""
        i = np.asarray(i, dtype=np.float64)
        return self.lo + (i + 0.5) * (self.hi - self.lo) / (1 << self.bits)


# ---------------------------------------------------------------------------
# Host bit-interleave kernels (numpy uint64, vectorized)
# ---------------------------------------------------------------------------

def _split2(x: np.ndarray) -> np.ndarray:
    """Spread the low 31 bits of x so bit i lands at position 2i (uint64)."""
    x = np.asarray(x, dtype=np.uint64) & np.uint64(0x7FFFFFFF)
    x = (x | (x << np.uint64(16))) & np.uint64(0x0000FFFF0000FFFF)
    x = (x | (x << np.uint64(8))) & np.uint64(0x00FF00FF00FF00FF)
    x = (x | (x << np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
    x = (x | (x << np.uint64(2))) & np.uint64(0x3333333333333333)
    x = (x | (x << np.uint64(1))) & np.uint64(0x5555555555555555)
    return x


def _combine2(z: np.ndarray) -> np.ndarray:
    """Inverse of _split2: gather every 2nd bit (starting at 0) down."""
    z = np.asarray(z, dtype=np.uint64) & np.uint64(0x5555555555555555)
    z = (z | (z >> np.uint64(1))) & np.uint64(0x3333333333333333)
    z = (z | (z >> np.uint64(2))) & np.uint64(0x0F0F0F0F0F0F0F0F)
    z = (z | (z >> np.uint64(4))) & np.uint64(0x00FF00FF00FF00FF)
    z = (z | (z >> np.uint64(8))) & np.uint64(0x0000FFFF0000FFFF)
    z = (z | (z >> np.uint64(16))) & np.uint64(0x00000000FFFFFFFF)
    return z


def _split3(x: np.ndarray) -> np.ndarray:
    """Spread the low 21 bits of x so bit i lands at position 3i (uint64)."""
    x = np.asarray(x, dtype=np.uint64) & np.uint64(0x1FFFFF)
    x = (x | (x << np.uint64(32))) & np.uint64(0x1F00000000FFFF)
    x = (x | (x << np.uint64(16))) & np.uint64(0x1F0000FF0000FF)
    x = (x | (x << np.uint64(8))) & np.uint64(0x100F00F00F00F00F)
    x = (x | (x << np.uint64(4))) & np.uint64(0x10C30C30C30C30C3)
    x = (x | (x << np.uint64(2))) & np.uint64(0x1249249249249249)
    return x


def _combine3(z: np.ndarray) -> np.ndarray:
    """Inverse of _split3: gather every 3rd bit (starting at 0) down."""
    z = np.asarray(z, dtype=np.uint64) & np.uint64(0x1249249249249249)
    z = (z | (z >> np.uint64(2))) & np.uint64(0x10C30C30C30C30C3)
    z = (z | (z >> np.uint64(4))) & np.uint64(0x100F00F00F00F00F)
    z = (z | (z >> np.uint64(8))) & np.uint64(0x1F0000FF0000FF)
    z = (z | (z >> np.uint64(16))) & np.uint64(0x1F00000000FFFF)
    z = (z | (z >> np.uint64(32))) & np.uint64(0x1FFFFF)
    return z


def _interleave2_np(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    return (_split2(x) << np.uint64(1)) | _split2(y)


def _deinterleave2_np(z: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    return _combine2(np.asarray(z, np.uint64) >> np.uint64(1)), _combine2(z)


def _interleave3_np(x: np.ndarray, y: np.ndarray, t: np.ndarray) -> np.ndarray:
    return (_split3(x) << np.uint64(2)) | (_split3(y) << np.uint64(1)) | _split3(t)


def _deinterleave3_np(z: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    z = np.asarray(z, np.uint64)
    return (
        _combine3(z >> np.uint64(2)),
        _combine3(z >> np.uint64(1)),
        _combine3(z),
    )


# Native-dispatch threshold: below this the ctypes call overhead dominates.
_NATIVE_MIN = 8192


def _use_native(n: int) -> bool:
    if n < _NATIVE_MIN:
        return False
    from geomesa_tpu import native

    return native.available()


def interleave2(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Morton-interleave two 31-bit indices; x occupies the higher bit of
    each pair. Bulk batches go through the native runtime (ingest hot path:
    the numpy spread is 6 full passes with temporaries; C++ does one)."""
    x = np.asarray(x, np.uint64)
    if _use_native(len(x)):
        from geomesa_tpu import native

        return native.interleave2(x, y)
    return _interleave2_np(x, np.asarray(y, np.uint64))


def deinterleave2(z: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    z = np.asarray(z, np.uint64)
    if _use_native(len(z)):
        from geomesa_tpu import native

        return native.deinterleave2(z)
    return _deinterleave2_np(z)


def interleave3(x: np.ndarray, y: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Morton-interleave three 21-bit indices; x highest within each triple."""
    x = np.asarray(x, np.uint64)
    if _use_native(len(x)):
        from geomesa_tpu import native

        return native.interleave3(x, y, t)
    return _interleave3_np(x, np.asarray(y, np.uint64), np.asarray(t, np.uint64))


def deinterleave3(z: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    z = np.asarray(z, np.uint64)
    if _use_native(len(z)):
        from geomesa_tpu import native

        return native.deinterleave3(z)
    return _deinterleave3_np(z)


# ---------------------------------------------------------------------------
# Device encode kernels (jnp; z as (hi, lo) uint32 pair)
# ---------------------------------------------------------------------------

def device_interleave(dims, bits: int):
    """jnp Morton interleave of ``d`` int32 arrays (each < 2**bits) into a
    (hi, lo) uint32 pair. Statically unrolled — ~3*bits vector ops, fused by XLA.

    ``dims[0]`` is the most-significant dimension within each bit group
    (matches :func:`interleave2` / :func:`interleave3`).
    """
    import jax.numpy as jnp

    d = len(dims)
    dims = [jnp.asarray(v).astype(jnp.uint32) for v in dims]
    lo = jnp.zeros_like(dims[0])
    hi = jnp.zeros_like(dims[0])
    one = jnp.uint32(1)
    for i in range(bits):
        for k in range(d):
            pos = d * i + (d - 1 - k)
            bit = (dims[k] >> jnp.uint32(i)) & one
            if pos < 32:
                lo = lo | (bit << jnp.uint32(pos))
            else:
                hi = hi | (bit << jnp.uint32(pos - 32))
    return hi, lo


def pair_lex_lte(a_hi, a_lo, b_hi, b_lo):
    """Lexicographic (a <= b) on uint32 pairs — the device z-compare."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def pair_lex_gte(a_hi, a_lo, b_hi, b_lo):
    return (a_hi > b_hi) | ((a_hi == b_hi) & (a_lo >= b_lo))


def split_u64(z: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host uint64 z -> (hi, lo) uint32 columns for device upload."""
    z = np.asarray(z, dtype=np.uint64)
    return (z >> np.uint64(32)).astype(np.uint32), (z & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def join_u64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    return (np.asarray(hi, np.uint64) << np.uint64(32)) | np.asarray(lo, np.uint64)


# ---------------------------------------------------------------------------
# Curves
# ---------------------------------------------------------------------------

class Z2SFC:
    """2D Z-order curve over (lon, lat), 31 bits per dimension.

    Reference: geomesa-z3/.../curve/Z2SFC.scala:15-22.
    """

    BITS = 31

    def __init__(self):
        self.lon = NormalizedDimension(-180.0, 180.0, self.BITS)
        self.lat = NormalizedDimension(-90.0, 90.0, self.BITS)

    def index(self, x: np.ndarray, y: np.ndarray) -> np.ndarray:
        """(lon, lat) -> z (uint64). Vectorized (fused native single pass
        when the library is built; numpy normalize+interleave otherwise)."""
        from geomesa_tpu import native

        out = native.z2_encode(np.asarray(x, np.float64), np.asarray(y, np.float64))
        if out is not None:
            return out
        return interleave2(self.lon.normalize(x), self.lat.normalize(y))

    def invert(self, z: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        xi, yi = deinterleave2(z)
        return self.lon.denormalize(xi), self.lat.denormalize(yi)

    def ranges(
        self,
        xmin: float,
        ymin: float,
        xmax: float,
        ymax: float,
        max_ranges: int = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Cover the bbox with z-ranges (host-side, plan time): inclusive
        (lows, highs) as two int64 arrays."""
        if max_ranges is None:
            max_ranges = config.SCAN_RANGES_TARGET.to_int()
        lo = (int(self.lon.normalize(xmin)), int(self.lat.normalize(ymin)))
        hi = (int(self.lon.normalize(xmax)), int(self.lat.normalize(ymax)))
        return zcover_fast(lo, hi, bits=self.BITS, dims=2, max_ranges=max_ranges)


class Z3SFC:
    """3D Z-order curve over (lon, lat, time-offset-in-bin), 21 bits per dim.

    Reference: geomesa-z3/.../curve/Z3SFC.scala:22-54 (time extent depends on
    the schema's time period; offsets are normalized into 21 bits).
    """

    BITS = 21

    def __init__(self, period: "str | TimePeriod" = TimePeriod.WEEK):
        self.binned = BinnedTime(period)
        self.lon = NormalizedDimension(-180.0, 180.0, self.BITS)
        self.lat = NormalizedDimension(-90.0, 90.0, self.BITS)
        self.time = NormalizedDimension(0.0, float(self.binned.max_offset_ms), self.BITS)

    def index(self, x: np.ndarray, y: np.ndarray, t_offset_ms: np.ndarray) -> np.ndarray:
        """(lon, lat, offset-ms-within-bin) -> z (uint64). Vectorized (fused
        native single pass when available)."""
        from geomesa_tpu import native

        t = np.asarray(t_offset_ms)
        if t.dtype.kind in "iu":
            out = native.z3_encode(
                np.asarray(x, np.float64), np.asarray(y, np.float64),
                t.astype(np.int64, copy=False), float(self.binned.max_offset_ms),
            )
            if out is not None:
                return out
        return interleave3(
            self.lon.normalize(x), self.lat.normalize(y), self.time.normalize(t_offset_ms)
        )

    def invert(self, z: np.ndarray) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        xi, yi, ti = deinterleave3(z)
        return (
            self.lon.denormalize(xi),
            self.lat.denormalize(yi),
            self.time.denormalize(ti),
        )

    def ranges(
        self,
        xbounds: Tuple[float, float],
        ybounds: Tuple[float, float],
        tbounds_ms: Tuple[float, float],
        max_ranges: int = None,
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Cover (bbox × time-offset-window) with z-ranges (host, plan
        time): inclusive (lows, highs) as two int64 arrays."""
        if max_ranges is None:
            max_ranges = config.SCAN_RANGES_TARGET.to_int()
        lo = (
            int(self.lon.normalize(xbounds[0])),
            int(self.lat.normalize(ybounds[0])),
            int(self.time.normalize(tbounds_ms[0])),
        )
        hi = (
            int(self.lon.normalize(xbounds[1])),
            int(self.lat.normalize(ybounds[1])),
            int(self.time.normalize(tbounds_ms[1])),
        )
        return zcover_fast(lo, hi, bits=self.BITS, dims=3, max_ranges=max_ranges)
