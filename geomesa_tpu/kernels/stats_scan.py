"""Exact stats over a scan (StatsScan analog, reference
index/iterators/StatsScan.scala:29-85).

Device-supported sketches run as masked reductions inside the scan jit (their
states are the same fixed-shape arrays the host sketches hold, so per-shard
partials merge by tree-map just like the reference's StatsCombiner). Sketches
without a device formulation yet fall back to host observation over the
gathered matches.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from geomesa_tpu.stats import sketches as sk

#: sketch kinds with a device reduction
DEVICE_KINDS = {"count", "minmax", "histogram", "descriptive", "enumeration", "topk"}


def _leaf_stats(stat: sk.Stat) -> List[sk.Stat]:
    return stat.stats if isinstance(stat, sk.SeqStat) else [stat]


def device_supported(stat: sk.Stat, host_only_cols) -> bool:
    for leaf in _leaf_stats(stat):
        if leaf.kind not in DEVICE_KINDS:
            return False
        if isinstance(leaf, sk.DescriptiveStats):
            attrs = leaf.attributes
        elif getattr(leaf, "attribute", None) is not None:
            attrs = [leaf.attribute]
        else:
            attrs = []
        if any(a in host_only_cols for a in attrs):
            return False
    return True


def batch_supported(stat: sk.Stat) -> bool:
    """May this stat tree ride the query-axis batched kernel
    (docs/SERVING.md "Query-axis batching")? Everything the device
    supports EXCEPT descriptive stats: count/minmax/histogram/enumeration/
    topk reduce in exact integer (or order-independent min/max)
    arithmetic, so a batched member's partial is bit-identical to its
    serial scan regardless of layout; descriptive s1/s2 are f32 sums whose
    bits depend on the reduction layout (the serial path may compact),
    so they keep query-at-a-time execution."""
    return all(
        leaf.kind in (DEVICE_KINDS - {"descriptive"})
        for leaf in _leaf_stats(stat)
    )


def device_update(stat: sk.Stat, cols: Dict, mask, xp, vocab_sizes: Dict[str, int]):
    """Compute the masked partial state arrays for every leaf sketch.

    Returns a list of pytrees (one per leaf) — safe to produce inside jit.
    """
    out = []
    fm = mask.reshape(-1)
    n = fm.sum()
    for leaf in _leaf_stats(stat):
        if leaf.kind == "count":
            out.append({"count": n})
        elif leaf.kind == "minmax":
            if leaf.attribute + "__x" in cols:
                vx = cols[leaf.attribute + "__x"].reshape(-1)
                vy = cols[leaf.attribute + "__y"].reshape(-1)
                out.append({
                    "count": n,
                    "lo": xp.stack([
                        xp.where(fm, vx, xp.inf).min(), xp.where(fm, vy, xp.inf).min()
                    ]),
                    "hi": xp.stack([
                        xp.where(fm, vx, -xp.inf).max(), xp.where(fm, vy, -xp.inf).max()
                    ]),
                })
            else:
                v = cols[leaf.attribute].reshape(-1)
                out.append({
                    "count": n,
                    "lo": xp.where(fm, v, xp.inf).min(),
                    "hi": xp.where(fm, v, -xp.inf).max(),
                })
        elif leaf.kind == "histogram":
            v = cols[leaf.attribute].reshape(-1)
            scaled = (v - leaf.lo) / (leaf.hi - leaf.lo) * leaf.bins
            idx = xp.clip(xp.floor(scaled), 0, leaf.bins - 1).astype(xp.int32)
            if xp is np:
                counts = np.bincount(idx[fm], minlength=leaf.bins)
            else:
                counts = xp.zeros(leaf.bins, xp.int32).at[idx].add(fm.astype(xp.int32))
            out.append({"counts": counts})
        elif leaf.kind == "descriptive":
            mat = xp.stack([cols[a].reshape(-1) for a in leaf.attributes], axis=1)
            w = fm.astype(mat.dtype)[:, None]
            mw = mat * w
            out.append({
                "count": n,
                "s1": mw.sum(axis=0),
                "s2": mw.T @ mat,
            })
        elif leaf.kind in ("enumeration", "topk"):
            v = cols[leaf.attribute].reshape(-1).astype(xp.int32)
            size = vocab_sizes[leaf.attribute]
            idx = xp.clip(v, 0, size - 1)
            valid = fm & (v >= 0)
            if xp is np:
                counts = np.bincount(idx[valid], minlength=size)
            else:
                counts = xp.zeros(size, xp.int32).at[idx].add(valid.astype(xp.int32))
            out.append({"counts": counts})
        else:  # pragma: no cover - guarded by device_supported
            raise ValueError(f"no device kernel for stat {leaf.kind!r}")
    return out


def decode_enum_keys(stat: sk.Stat, dicts) -> sk.Stat:
    """Map enumeration/topk count keys from dictionary codes to their string
    values (the host-observe path counts raw code columns; the device path
    decodes in absorb_partials — results must agree)."""
    for leaf in _leaf_stats(stat):
        if leaf.kind in ("enumeration", "topk"):
            d = dicts.get(leaf.attribute)
            if d is None:
                continue
            enum = leaf if leaf.kind == "enumeration" else leaf._enum
            new = {}
            for k, c in enum.counts.items():
                if isinstance(k, (int, np.integer)):
                    if k < 0:
                        continue  # null codes: dropped (device path parity)
                    key = d.values[k] if k < len(d.values) else int(k)
                else:
                    key = k
                new[key] = new.get(key, 0) + c
            enum.counts = new
    return stat


def combine_partials(stat: sk.Stat, a, b):
    """Merge two :func:`device_update` partials of ``stat`` into one (the
    exact host partial of the f32 band rows into the device partial of the
    rest). Returns host arrays."""
    out = []
    for leaf, pa, pb in zip(_leaf_stats(stat), a, b):
        pa = {k: np.asarray(v) for k, v in pa.items()}
        pb = {k: np.asarray(v) for k, v in pb.items()}
        if leaf.kind == "minmax":
            out.append({
                "count": pa["count"] + pb["count"],
                "lo": np.minimum(pa["lo"], pb["lo"]),
                "hi": np.maximum(pa["hi"], pb["hi"]),
            })
        else:  # counts and sums add
            out.append({k: pa[k] + pb[k] for k in pa})
    return out


def absorb_partials(stat: sk.Stat, partials, dicts) -> sk.Stat:
    """Fold device partial states back into host Stat objects."""
    for leaf, p in zip(_leaf_stats(stat), partials):
        p = {k: np.asarray(v) for k, v in p.items()}
        if leaf.kind == "count":
            leaf.count += int(p["count"])
        elif leaf.kind == "minmax":
            cnt = int(p["count"])
            if cnt == 0:
                continue
            lo, hi = p["lo"], p["hi"]
            other = sk.MinMax(
                leaf.attribute,
                lo.tolist() if lo.ndim else float(lo),
                hi.tolist() if hi.ndim else float(hi),
                cnt,
            )
            leaf.merge(other)
        elif leaf.kind == "histogram":
            leaf.counts += p["counts"].astype(np.int64)
        elif leaf.kind == "descriptive":
            leaf.count += int(p["count"])
            leaf.s1 += p["s1"].astype(np.float64)
            leaf.s2 += p["s2"].astype(np.float64)
        elif leaf.kind in ("enumeration", "topk"):
            counts = p["counts"].astype(np.int64)
            d = dicts.get(leaf.attribute)
            enum = leaf if leaf.kind == "enumeration" else leaf._enum
            for code, c in enumerate(counts.tolist()):
                if c:
                    key = d.values[code] if d is not None else code
                    enum.counts[key] = enum.counts.get(key, 0) + c
    return stat
