"""Sharded, sorted columnar feature store — the storage substrate (L3/L4).

The TPU analog of a backend adapter (SURVEY.md §2.5): instead of rowkey tables
in Accumulo/HBase, each index is a set of **sorted columnar shards**. A shard
is a contiguous slab of the index's global sort order (so per-shard
``searchsorted`` row windows play the role of rowkey range scans), padded to a
common length so the stacked [n_shards, shard_len] arrays pjit cleanly over a
device mesh.

Write path parity (GeoMesaFeatureWriter/IndexAdapter.BaseIndexWriter,
reference IndexAdapter.scala:132-190): an ingest batch computes ALL index keys
in one vectorized pass before any table is touched; tables rebuild their sort
on flush (LSM-style delta buffers are a later optimization — the write buffer
is the memtable).

Write-time stats parity (MetadataBackedStats.scala:36-100): flush updates the
persisted sketches (count, geometry/time bounds, Z3 histogram, per-indexed-
attribute sketches) that drive the cost-based strategy decider.
"""

from __future__ import annotations

import itertools
import threading
import uuid
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from geomesa_tpu import config
from geomesa_tpu.index.keyspace import (
    AttributeKeySpace, KeyPlan, KeySpace, keyspaces_for_schema,
)
from geomesa_tpu.schema.columns import ColumnBatch, DictionaryEncoder, encode_batch
from geomesa_tpu.schema.feature_type import FeatureType
from geomesa_tpu.stats import sketches as sk

# Columns that live host-side only (string dtypes or 64-bit keys).
_HOST_ONLY_DTYPES = ("O", "U", "S")


def device_view(a: np.ndarray) -> Optional[np.ndarray]:
    """Host column -> device-eligible array (int32/float32/bool), or None."""
    if a.dtype.kind in _HOST_ONLY_DTYPES:
        return None
    if a.dtype == np.float64:
        return a.astype(np.float32)
    if a.dtype == np.int64:
        # raw epoch-ms / z keys stay host-side; generic int64 attribute
        # columns ride as float32 (documented precision tradeoff)
        return a.astype(np.float32)
    if a.dtype == np.uint64:
        return None
    return a


class IndexTable:
    """One index = a sort permutation + sorted KEY columns over the store's
    single master column set.

    Attribute columns are NOT duplicated per index (the pre-refactor layout
    held a full sorted copy of every column in every table — 8x memory at 8
    indices); they are gathered through ``order`` on demand: once per device
    upload (cached), per-query on the host fallback path."""

    def __init__(self, keyspace: KeySpace, ft: FeatureType, n_shards: int):
        self.keyspace = keyspace
        self.ft = ft
        self.n_shards = n_shards
        #: sorted-row -> master-row permutation
        self.order = np.zeros(0, np.int64)
        #: this index's sort-key columns, already in sorted order
        self.key_columns: Dict[str, np.ndarray] = {}
        self._master: Dict[str, np.ndarray] = {}
        self.n = 0
        self.shard_bounds = np.zeros(n_shards + 1, np.int64)
        self._device_cache: Dict[tuple, dict] = {}
        #: host-side staging for the partition pipeline: stacked [S, L]
        #: arrays assembled off-thread by stage_host, consumed (and freed)
        #: by device_columns on the query thread
        self._host_stage: Dict[tuple, np.ndarray] = {}
        self._rank_vocab: Optional[np.ndarray] = None  # for string attr index
        #: key-column quantization shifts when the radix pack-sort built
        #: this table (None = argsort path, raw keys stored)
        self.key_shifts: Optional[Dict[str, int]] = None
        #: round the padded shard length up to a multiple of this, so tables
        #: of near-equal size (time partitions) share compiled kernel shapes
        self.shard_len_multiple = 1

    # -- build ------------------------------------------------------------
    def rebuild(self, columns: Dict[str, np.ndarray], dicts: Dict[str, DictionaryEncoder]):
        """Re-sort by this index's key and re-shard. ``columns`` is the
        master column dict (attributes + every index's key columns); the
        table keeps a reference plus its own sorted key columns."""
        cols = dict(columns)
        ks = self.keyspace
        if isinstance(ks, AttributeKeySpace) and self.ft.attr(ks.attr).type == "string":
            # dictionary codes are insertion-ordered; build a value-ordered
            # rank column so searchsorted windows work for string ranges
            vocab = np.array(dicts[ks.attr].values, dtype=object)
            order = np.argsort(vocab)
            rank_of_code = np.empty(len(vocab), np.int64)
            rank_of_code[order] = np.arange(len(vocab))
            codes = columns[ks.attr]
            ranks = np.where(codes >= 0, rank_of_code[np.clip(codes, 0, None)], -1)
            cols[ks.sort_col] = ranks
            self._rank_vocab = vocab[order]
        fb = ks.fast_build(cols)
        if fb is not None:
            # radix pack-sort: permutation + quantized sorted keys in one
            # value-sort, no argsort / key gather (packsort module)
            self.order, self.key_columns, self.key_shifts = fb
            self._master = cols
            self.n = len(self.order)
        else:
            order = ks.sort_order(cols)
            self.order = np.asarray(
                order, np.int32 if len(order) < 2**31 else np.int64
            )
            self._master = cols
            key_names = (set(ks.key_cols) | {getattr(ks, "sort_col", None)}) - {None}
            self.key_columns = {
                k: cols[k][order] for k in key_names if k in cols
            }
            self.key_shifts = None
            self.n = len(order)
        self.shard_bounds = np.linspace(0, self.n, self.n_shards + 1).astype(np.int64)
        self._device_cache.clear()
        self._host_stage.clear()

    def append_rows(
        self,
        columns: Dict[str, np.ndarray],
        dicts: Dict[str, DictionaryEncoder],
        fresh_cols: Dict[str, np.ndarray],
        n_fresh: int,
    ):
        """LSM append: sort the fresh rows locally and MERGE them into the
        existing order via searchsorted insertion positions — O(old + fresh)
        instead of the full O(n log n) re-sort (SURVEY.md §7 hard part (c)).
        Falls back to :meth:`rebuild` when the key space requires it."""
        ks = self.keyspace
        if self.n == 0 or not ks.can_insert:
            return self.rebuild(columns, dicts)
        key_names = list(self.key_columns)
        if any(k not in fresh_cols for k in key_names):
            return self.rebuild(columns, dicts)
        if self.key_shifts is not None:
            # quantized table: fresh keys must be quantized with the SAME
            # shifts or the merged column would not be sorted
            fb = ks.fast_build(fresh_cols, force_shifts=self.key_shifts)
            if fb is None or fb[2] != self.key_shifts:
                return self.rebuild(columns, dicts)
            fresh_order, fresh_sorted, _ = fb
            fresh_order = fresh_order.astype(np.int64, copy=False)
        else:
            fresh_order = np.asarray(ks.sort_order(fresh_cols), np.int64)
            fresh_sorted = {k: fresh_cols[k][fresh_order] for k in key_names}
        p = ks.insert_positions(self.key_columns, fresh_sorted)
        if p is None:
            return self.rebuild(columns, dicts)
        old_n = self.n
        master_base = old_n  # master rows are [old | fresh]
        total = old_n + n_fresh
        final = np.empty(total, np.int32 if total < 2**31 else np.int64)
        at = p + np.arange(n_fresh)
        is_fresh = np.zeros(total, bool)
        is_fresh[at] = True
        final[is_fresh] = master_base + fresh_order
        final[~is_fresh] = self.order
        self.order = final
        self._master = columns
        # masked scatter-merge of the sorted key columns (np.insert's
        # generality made it the per-flush hotspot)
        merged_keys = {}
        for k in key_names:
            old = self.key_columns[k]
            m = np.empty(total, old.dtype)
            m[at] = fresh_sorted[k].astype(old.dtype, copy=False)
            m[~is_fresh] = old
            merged_keys[k] = m
        self.key_columns = merged_keys
        self.n = total
        self.shard_bounds = np.linspace(0, self.n, self.n_shards + 1).astype(np.int64)
        self._device_cache.clear()
        self._host_stage.clear()

    # -- column access -----------------------------------------------------
    def has_column(self, name: str) -> bool:
        return name in self.key_columns or name in self._master

    def dtype_of(self, name: str):
        col = self.key_columns.get(name)
        if col is None:
            col = self._master.get(name)
        return None if col is None else col.dtype

    def is_host_only(self, name: str) -> bool:
        dt = self.dtype_of(name)
        return dt is None or dt.kind in _HOST_ONLY_DTYPES

    def column_names(self):
        names = dict.fromkeys(self._master)
        names.update(dict.fromkeys(self.key_columns))
        return list(names)

    def col_sorted(self, name: str) -> np.ndarray:
        """Full column in this index's sort order (key cols are stored
        sorted; attribute cols gather through the permutation)."""
        col = self.key_columns.get(name)
        if col is not None:
            return col
        return self._master[name][self.order]

    def col_sorted_at(self, name: str, pos: np.ndarray) -> np.ndarray:
        """``col_sorted(name)[pos]`` without gathering the whole column."""
        col = self.key_columns.get(name)
        if col is not None:
            return col[pos]
        return self._master[name][self.order[pos]]

    def shard_cols(self, names, s: int) -> Dict[str, np.ndarray]:
        """Selected columns for one shard, in sorted order."""
        sl = self.shard_slice(s)
        rows = self.order[sl]
        out = {}
        for k in names:
            kc = self.key_columns.get(k)
            if kc is not None:
                out[k] = kc[sl]
            elif k in self._master:
                out[k] = self._master[k][rows]
        return out

    def shard_rows_cols(self, names, s: int, idx: np.ndarray) -> Dict[str, np.ndarray]:
        """Selected columns for specific sorted-order row positions of one
        shard — gathers only ``idx`` rows (the refinement-candidate path),
        avoiding a full-shard copy."""
        sl = self.shard_slice(s)
        rows = self.order[sl.start + idx]
        out = {}
        for k in names:
            kc = self.key_columns.get(k)
            if kc is not None:
                out[k] = kc[sl.start + idx]
            elif k in self._master:
                out[k] = self._master[k][rows]
        return out

    @property
    def shard_len(self) -> int:
        """Padded per-shard length (static shape for the device).

        Partitioned children always round up to ``shard_len_multiple``
        (geomesa.partition.shard.bucket) so near-equal partitions share
        kernel shapes; under warm-path shape bucketing plain stores round
        to ``geomesa.compact.shard.bucket`` (8192) the same way, so a
        small insert never changes L — the padded scan kernel's static
        shape — and therefore never recompiles. Padding costs masked rows
        (≤ bucket/L relative overhead: 0.4% at the bench's 2.5M-row
        shards)."""
        if self.n == 0:
            return 0
        m = int(np.max(np.diff(self.shard_bounds)))
        b = self.shard_len_multiple
        if b <= 1 and config.COMPACT_BUCKETING.to_bool():
            b = config.COMPACT_SHARD_BUCKET.to_int() or 1
        return m if b <= 1 else -(-m // b) * b

    def shard_slice(self, s: int) -> slice:
        return slice(int(self.shard_bounds[s]), int(self.shard_bounds[s + 1]))

    # -- device layout ----------------------------------------------------
    def _stack_host(self, name: str, L: int) -> Optional[np.ndarray]:
        """One column's padded [n_shards, L] HOST array (the slab gather +
        pad half of a device upload) — pure numpy, no jax."""
        if not self.has_column(name):
            return None
        dv = device_view(self.col_sorted(name))
        if dv is None:
            return None
        stacked = np.zeros((self.n_shards, L), dtype=dv.dtype)
        for s in range(self.n_shards):
            sl = self.shard_slice(s)
            stacked[s, : sl.stop - sl.start] = dv[sl]
        return stacked

    def stage_host(self, names: Sequence[str]) -> int:
        """Assemble (and cache) the stacked host arrays for ``names`` —
        the expensive host half of :meth:`device_columns`, jax-free so the
        partition pipeline's prefetch thread can overlap it with another
        partition's device execution. ``device_columns`` consumes each
        staged array (paying only the device_put) and frees it. Columns
        already device-resident are skipped: in the warm steady state
        (device cache hit) staging would be pure waste, and the pipeline's
        consumer additionally clears leftovers after each partition.
        Returns the bytes newly staged by THIS call (the per-query cost
        ledger's ``bytes_staged`` contribution — 0 in the warm state)."""
        L = self.shard_len
        resident = set()
        for cached in list(self._device_cache.values()):
            resident.update(cached)
        staged_bytes = 0
        for name in sorted(set(names)):
            if name in resident or (name, L) in self._host_stage:
                continue
            stacked = self._stack_host(name, L)
            if stacked is not None:
                self._host_stage[(name, L)] = stacked
                staged_bytes += int(stacked.nbytes)
        return staged_bytes

    def device_columns(self, names: Sequence[str], sharding=None):
        """Stacked padded [n_shards, shard_len] jnp arrays for ``names``
        (cached). With a ``NamedSharding``, columns are placed sharded over
        the mesh's 'shard' axis. Host-only columns are silently skipped —
        callers must route predicates on those through the host path."""
        import jax

        key = (tuple(sorted(set(names))), id(sharding))
        L = self.shard_len
        cached = self._device_cache.get(key)
        if cached is not None:
            # free any staged host copies a prefetcher built before this
            # hit (they would otherwise sit as dead duplicates)
            for name in key[0]:
                self._host_stage.pop((name, L), None)
            return cached
        out = {}
        for name in key[0]:
            stacked = self._host_stage.pop((name, L), None)
            if stacked is None:
                stacked = self._stack_host(name, L)
            if stacked is None:
                continue
            out[name] = (
                jax.device_put(stacked, sharding)
                if sharding is not None
                else jax.device_put(stacked)
            )
        self._device_cache[key] = out
        return out

    # -- scan windows ------------------------------------------------------
    def windows(self, plan: KeyPlan) -> Tuple[np.ndarray, np.ndarray]:
        """Resolve the key plan to per-shard row windows, padded to a common
        window count: (starts [S, K], ends [S, K]) in *local* shard rows."""
        per_shard = []
        for s in range(self.n_shards):
            sl = self.shard_slice(s)
            n = sl.stop - sl.start
            # window resolution only ever touches the sort-key columns
            shard_cols = {k: v[sl] for k, v in self.key_columns.items()}
            if self.key_shifts is not None:
                shard_cols["__shifts__"] = self.key_shifts
            if self._rank_vocab is not None:
                vocab = self._rank_vocab

                def rank_lookup(value, side):
                    if side == "lo":
                        return int(np.searchsorted(vocab, value, side="left"))
                    return int(np.searchsorted(vocab, value, side="right")) - 1

                shard_cols["__rank_lookup__"] = rank_lookup
            starts, ends = plan.windows(shard_cols, n)
            per_shard.append((starts, ends))
        K = max(len(s) for s, _ in per_shard)
        # pad the window count to its shape bucket (power of two above the
        # geomesa.compact.bucket.floor): K is a kernel static shape, and
        # bucketing keeps near-identical queries (or the same query across
        # time partitions, or distinct queries with few windows) on one
        # compiled kernel. Padded windows are (0, 0) — empty, exact.
        from geomesa_tpu.kernels.registry import bucket_count

        K = bucket_count(K)
        S = self.n_shards
        starts = np.zeros((S, K), np.int32)
        ends = np.zeros((S, K), np.int32)
        for i, (s, e) in enumerate(per_shard):
            starts[i, : len(s)] = s
            ends[i, : len(e)] = e
        return starts, ends

    def host_gather(self, global_mask: np.ndarray,
                    names: Optional[Sequence[str]] = None) -> ColumnBatch:
        """Select matching rows from the host master copy.

        ``global_mask`` is over the padded [S, L] layout (flattened).
        ``names``: optional projection — only the listed columns (plus
        their derived ``<name>__*`` companions and the feature id) gather,
        so projected queries on lazily-loaded cold partitions touch only
        the column groups they need (ColumnGroups.scala:28 analog)."""
        L = self.shard_len
        idx = []
        for s in range(self.n_shards):
            sl = self.shard_slice(s)
            local = global_mask[s * L : s * L + (sl.stop - sl.start)]
            idx.append(np.nonzero(local)[0] + sl.start)
        sel = np.concatenate(idx) if idx else np.zeros(0, np.int64)
        return self._gather_sorted(sel, names)

    def host_gather_positions(self, positions: np.ndarray,
                              names: Optional[Sequence[str]] = None) -> ColumnBatch:
        """Like :meth:`host_gather` but from padded [S*L] flat POSITIONS
        (device top-k / kNN results) — O(k), never touching a full-table
        mask. Row order follows ``positions``."""
        positions = np.asarray(positions, np.int64)
        L = self.shard_len
        s = positions // L
        sel = self.shard_bounds[s] + (positions - s * L)
        return self._gather_sorted(sel, names)

    def _gather_sorted(self, sel: np.ndarray,
                       names: Optional[Sequence[str]] = None) -> ColumnBatch:
        rows = self.order[sel]
        cols = self.column_names() if names is None else [
            k for k in self.column_names()
            if k == "__fid__" or k in names
            or any(k.startswith(n + "__") for n in names)
        ]
        out = {}
        for k in cols:
            if k in self._master:  # master wins: key copies may be quantized
                out[k] = self._master[k][rows]
            else:
                kc = self.key_columns.get(k)
                if kc is not None:
                    out[k] = kc[sel]
        return ColumnBatch(out, len(sel))


class FeatureStore:
    """All index tables + write buffer + persisted stats for one schema.

    The GeoMesaDataStore-per-type analog: schema, writer, tables, stats
    (reference GeoMesaDataStore.scala:49, MetadataBackedStats)."""

    _uids = itertools.count()

    def __init__(self, ft: FeatureType, n_shards: Optional[int] = None):
        #: process-unique id: cache keys must never collide across store
        #: objects (id() can be recycled after GC — partition children churn)
        self.uid = next(FeatureStore._uids)
        self.ft = ft
        self.n_shards = n_shards or ft.shards or config.DEFAULT_SHARDS.to_int()
        self.dicts: Dict[str, DictionaryEncoder] = {}
        self.keyspaces = keyspaces_for_schema(ft)
        self.tables: Dict[str, IndexTable] = {
            ks.name: IndexTable(ks, ft, self.n_shards) for ks in self.keyspaces
        }
        self._buffer: List[ColumnBatch] = []
        self._all: Optional[ColumnBatch] = None
        #: cached index-key columns for the current master rows
        self._key_cols: Dict[str, np.ndarray] = {}
        self._lock = threading.Lock()
        self.stats = self._init_stats()
        #: bumped on every data mutation; keys cross-query kernel caches
        self.version = 0
        #: changes whenever PERSISTED rows are rewritten (delete, column
        #: adds) rather than appended; incremental checkpoints compare it
        #: to decide between append-a-chunk and full rewrite. EVERY
        #: mutation path that rewrites existing rows must call
        #: :meth:`_bump_epoch`.
        self.mutation_epoch = uuid.uuid4().hex

    def _init_stats(self) -> Dict[str, sk.Stat]:
        ft = self.ft
        out: Dict[str, sk.Stat] = {"count": sk.CountStat()}
        if ft.geom_field:
            out["bounds"] = sk.MinMax(ft.geom_field)
        if ft.dtg_field:
            out["time-bounds"] = sk.MinMax(ft.dtg_field)
        if ft.geom_field and ft.attr(ft.geom_field).is_point:
            out["z2-histogram"] = sk.Z2HistogramStat(ft.geom_field, 1024)
        if ft.geom_field and ft.dtg_field and ft.attr(ft.geom_field).is_point:
            out["z3-histogram"] = sk.Z3HistogramStat(
                ft.geom_field, ft.dtg_field, ft.time_period, 1024
            )
        for a in ft.attributes:
            if a.indexed and not a.is_geom and a.type != "json":
                if a.type == "string":
                    out[f"enum-{a.name}"] = sk.EnumerationStat(a.name)
                else:
                    out[f"minmax-{a.name}"] = sk.MinMax(a.name)
        return out

    # -- write path --------------------------------------------------------
    def append(self, data: Dict, fids=None, visibilities=None,
               observer=None) -> int:
        """Buffer an ingest batch (encoded immediately; keys at flush).

        ``visibilities``: per-feature visibility expression(s) — one string
        for the whole batch or a sequence per feature (geomesa-security
        analog; dictionary-encoded into the ``__vis__`` code column).

        ``observer``: optional callable handed the ENCODED ColumnBatch
        after it buffers — the standing-query delta hook (docs/
        STANDING.md) reads the exact columns a window re-scan would."""
        from geomesa_tpu.security import VIS_COLUMN, parse_visibility

        batch = encode_batch(self.ft, data, self.dicts, fids)
        vd = self.dicts.get(VIS_COLUMN)
        if vd is None:
            vd = self.dicts[VIS_COLUMN] = DictionaryEncoder([""])
        if visibilities is None:
            vis = np.zeros(batch.n, np.int32)
        else:
            if isinstance(visibilities, str):
                visibilities = [visibilities] * batch.n
            exprs = [v or "" for v in visibilities]
            for v in set(exprs):
                parse_visibility(v)  # validate at write time
            vis = vd.encode(exprs)
        batch.columns[VIS_COLUMN] = vis
        with self._lock:
            self._buffer.append(batch)
        if observer is not None:
            observer(batch)
        return batch.n

    @property
    def pending(self) -> int:
        return sum(b.n for b in self._buffer)

    @property
    def count(self) -> int:
        return (self._all.n if self._all else 0) + self.pending

    def flush(self):
        """Merge buffer into tables: compute all index keys in one vectorized
        pass, then rebuild each table's sort (atomic mutation batch parity,
        reference IndexAdapter.scala:140-154)."""
        with self._lock:
            if not self._buffer:
                return
            fresh = ColumnBatch.concat(self._buffer)
            self._buffer = []
        # index keys for the FRESH rows only (per-row functions — old rows'
        # keys are cached in self._key_cols and just concatenated)
        fresh_keys: Dict[str, np.ndarray] = {}
        for ks in self.keyspaces:
            fresh_keys.update(ks.index_keys(self.ft, fresh))
        # write-time stats on the fresh rows; include the freshly-computed
        # key columns so Z-histograms reuse them instead of re-encoding
        # (the period marker tells Z3 sketches the keys match their config)
        stat_cols = {**fresh.columns, **fresh_keys}
        if "__z3" in fresh_keys:
            stat_cols["__z3_period"] = self.ft.time_period
        for st in self.stats.values():
            st.observe(stat_cols)
        if self._all is not None:
            # datasets persisted before visibility support lack __vis__
            from geomesa_tpu.security import VIS_COLUMN

            if VIS_COLUMN in fresh.columns and VIS_COLUMN not in self._all.columns:
                self._all.columns[VIS_COLUMN] = np.zeros(self._all.n, np.int32)
                # the back-fill REWRITES persisted rows (they gain a column):
                # an incremental checkpoint appending only the fresh chunk
                # would leave old chunks without __vis__, silently dropping
                # visibility labels on reload — force a full rewrite
                self._bump_epoch()
        if self._all is None:
            merged = fresh
            key_cols: Dict[str, np.ndarray] = {**fresh.columns, **fresh_keys}
        else:
            merged = ColumnBatch.concat([self._all, fresh])
            key_cols = dict(merged.columns)
            old_keys = self._key_cols
            recomputed = set()
            for k, fv in fresh_keys.items():
                ov = old_keys.get(k)
                if ov is None:  # cold cache (load()): recompute, once per ks
                    for ks in self.keyspaces:
                        if k in ks.key_cols and ks.name not in recomputed:
                            key_cols.update(ks.index_keys(self.ft, merged))
                            recomputed.add(ks.name)
                            break
                else:
                    key_cols[k] = np.concatenate([ov, fv])
        self._all = ColumnBatch(
            {k: key_cols[k] for k in merged.columns}, merged.n
        )
        self._key_cols = {
            k: v for k, v in key_cols.items() if k not in merged.columns
        }
        fresh_all = {**fresh.columns, **fresh_keys}
        for ks in self.keyspaces:
            self.tables[ks.name].append_rows(
                key_cols, self.dicts, fresh_all, fresh.n
            )
        self.version += 1

    # -- schema / index lifecycle -----------------------------------------
    def add_columns(self, new_ft: FeatureType, added) -> None:
        """Append null-filled columns for ``added`` attributes IN PLACE —
        no index key changes, so every table keeps its sort permutation
        and only learns the new master columns (the O(1)-per-index path
        GeoMesaDataStore.scala:288-336's append-only updateSchema implies;
        r4 rebuilt + re-flushed the whole store here)."""
        from geomesa_tpu.schema.columns import null_columns

        self.flush()
        self.ft = new_ft
        n = self._all.n if self._all is not None else 0
        cols = null_columns(new_ft, added, n, self.dicts)
        self._bump_epoch()
        if n:
            self._all.columns.update(cols)
        for t in self.tables.values():
            t.ft = new_ft
            if n:
                t._master.update(cols)
                t._device_cache.clear()
        self.version += 1

    def _bump_epoch(self) -> None:
        """Mark persisted rows as rewritten: the next incremental
        checkpoint must do a full rewrite, not append a chunk."""
        self.mutation_epoch = uuid.uuid4().hex

    def _attr_stat_key(self, attr: str) -> str:
        a = self.ft.attr(attr)
        return f"enum-{attr}" if a.type == "string" else f"minmax-{attr}"

    def build_missing_table(self, t: IndexTable) -> None:
        """Build an empty table's permutation from the master rows —
        used both when an index is enabled on a live store and when a
        partition snapshot predating the index is loaded. Only the
        keyspace's own input columns are touched, so lazily-loaded
        snapshots (_LazyCols) materialize one column, not the store."""
        if self._all is None or not self._all.n:
            return
        ks = t.keyspace
        fresh = ks.index_keys(self.ft, self._all)
        self._key_cols.update(fresh)
        needed = dict(fresh)
        if isinstance(ks, AttributeKeySpace):
            needed[ks.attr] = self._all.columns[ks.attr]
        t.rebuild(needed, self.dicts)
        # master lookup mapping for on-demand attribute gathers:
        # share an existing table's (possibly lazy) master
        other = next((ot for oname, ot in self.tables.items()
                      if oname != ks.name and ot.n), None)
        if other is not None:
            base = other._master
            for k, v in t._master.items():
                if k not in base:
                    base[k] = v
            t._master = base
        else:
            merged = {**self._all.columns, **self._key_cols}
            for k, v in t._master.items():
                merged.setdefault(k, v)
            t._master = merged

    def ensure_attr_sketch(self, attr: str) -> None:
        """Retroactively build the write-time sketch the cost model needs
        for an attribute index, if absent."""
        skey = self._attr_stat_key(attr)
        if skey in self.stats:
            return
        a = self.ft.attr(attr)
        stat = (sk.EnumerationStat(attr) if a.type == "string"
                else sk.MinMax(attr))
        if self._all is not None and self._all.n:
            stat.observe(self._all.columns)
        self.stats[skey] = stat

    def add_attribute_index(self, attr: str) -> None:
        """Enable an attribute index on a live schema: build ONLY the new
        sort permutation over the existing master columns (the reference
        validates such transitions in updateSchema,
        GeoMesaDataStore.scala:288-336; r4 required a full re-create)."""
        a = self.ft.attr(attr)
        if a.is_geom or a.type == "json":
            raise ValueError(f"cannot attribute-index {attr!r} ({a.type})")
        ks = AttributeKeySpace(attr, self.ft.geom_field, a.type)
        if ks.name in self.tables:
            return  # already indexed
        self.flush()
        self.keyspaces.append(ks)
        t = IndexTable(ks, self.ft, self.n_shards)
        self.tables[ks.name] = t
        self.build_missing_table(t)
        self.ensure_attr_sketch(attr)
        self.version += 1

    def remove_attribute_index(self, attr: str) -> None:
        """Drop an attribute index (permutation + key columns + sketch);
        master data is untouched."""
        name = f"attr:{attr}"
        if name not in self.tables:
            raise KeyError(f"no attribute index on {attr!r}")
        del self.tables[name]
        self.keyspaces = [k for k in self.keyspaces if k.name != name]
        self._key_cols.pop(f"__attr_{attr}", None)
        self.stats.pop(self._attr_stat_key(attr), None)
        self.version += 1

    def wkt_geoms(self) -> List[str]:
        """Non-point geometry attributes stored WITH exact WKT (drives the
        Arrow field type for extent geometries)."""
        cols = self._all.columns if self._all is not None else {}
        return [
            a.name for a in self.ft.attributes
            if a.is_geom and a.name + "__wkt" in cols
        ]

    def delete(self, mask_fn) -> int:
        """Remove rows matching ``mask_fn(columns) -> bool mask`` (host)."""
        self.flush()
        if self._all is None or self._all.n == 0:
            return 0
        mask = mask_fn(self._all.columns)
        removed = int(mask.sum())
        if removed == 0:
            return 0
        keep_mask = ~mask
        keep = self._all.select(keep_mask)
        self._all = keep
        self._bump_epoch()
        self.stats["count"] = sk.CountStat(keep.n)
        key_cols: Dict[str, np.ndarray] = dict(keep.columns)
        # filter the cached key columns with the same mask (per-row values)
        self._key_cols = {k: v[keep_mask] for k, v in self._key_cols.items()}
        key_cols.update(self._key_cols)
        for ks in self.keyspaces:
            for k in ks.key_cols:
                if k not in key_cols:
                    key_cols.update(ks.index_keys(self.ft, keep))
                    self._key_cols.update({
                        kk: vv for kk, vv in key_cols.items()
                        if kk not in keep.columns
                    })
                    break
            self.tables[ks.name].rebuild(key_cols, self.dicts)
        self.version += 1
        return removed
