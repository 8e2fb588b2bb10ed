"""GeoDataset: the datastore API surface.

Role parity with the reference's GeoMesaDataStore + process layer
(GeoMesaDataStore.scala:49: schema CRUD, feature writer/reader, query planner
wiring, stats; geomesa-process: density/stats/unique/sampling/knn/proximity):
one Python object owning the schema catalog, per-schema FeatureStores, the
planner, and the executor.

Queries accept ECQL text plus hints. Aggregations (density, stats, knn, ...)
are first-class methods — the equivalent of GeoMesa's query-hint-driven
pushdown scans.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import shutil
import time
import uuid
import zlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from geomesa_tpu import (
    config, metrics, resilience, security, tracing, utilization,
)
from geomesa_tpu.audit import AuditWriter
from geomesa_tpu.cache import AggregateCache
from geomesa_tpu.filter import ir, parse_ecql
from geomesa_tpu.filter.compile import CompiledFilter
from geomesa_tpu.index.store import FeatureStore
from geomesa_tpu.planning.executor import Executor, query_deadline
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.planning.planner import QueryHints, QueryPlanner
from geomesa_tpu.schema.columns import ColumnBatch, DictionaryEncoder, decode_batch
from geomesa_tpu.schema.feature_type import FeatureType
from geomesa_tpu.stats import parse_stat
from geomesa_tpu.stats import sketches as sk


@dataclass
class Query:
    """A query: ECQL + hints (the GeoTools Query analog)."""

    ecql: str = "INCLUDE"
    max_features: Optional[int] = None
    properties: Optional[List[str]] = None
    sort_by: Optional[List[Tuple[str, bool]]] = None  # (attr, descending)
    sampling: Optional[int] = None
    #: per-key sampling attribute: 1-in-``sampling`` per distinct value
    sample_by: Optional[str] = None
    index: Optional[str] = None
    #: visibility authorizations for this query (None = dataset default)
    auths: Optional[List[str]] = None
    #: EPSG code to reproject result geometries into (storage is 4326;
    #: the reference reprojects as the final post-processing step,
    #: QueryPlanner.scala:68-90). Built-in closed forms: 3857 (latitudes
    #: beyond +/-85.051 clamp to the projection edge with a
    #: RuntimeWarning), 3395, UTM 326xx/327xx, 5070, 3035; any EPSG via
    #: pyproj when installed; others pluggable via
    #: utils.reproject.register.
    srid: Optional[int] = None

    def hints(self) -> QueryHints:
        return QueryHints(
            query_index=self.index,
            sampling=self.sampling,
            sample_by=self.sample_by,
            max_features=self.max_features,
            properties=self.properties,
            sort_by=self.sort_by,
        )


class FeatureCollection:
    """Query result: host columns + decode helpers."""

    def __init__(self, ft: FeatureType, batch: ColumnBatch,
                 dicts: Dict[str, DictionaryEncoder], srid: int = 4326):
        self.ft = ft
        self.batch = batch
        self.dicts = dicts
        #: CRS of the geometry columns (4326 unless the query reprojected)
        self.srid = srid

    def __len__(self):
        return self.batch.n

    @property
    def columns(self):
        return self.batch.columns

    @property
    def fids(self):
        """Feature ids as ``str`` (the raw ``columns['__fid__']`` is a
        fixed-width bytes column at bulk scale)."""
        from geomesa_tpu.schema.columns import fid_strs

        col = self.batch.columns.get("__fid__")
        if col is None:
            return []
        return fid_strs(col).tolist()

    def to_dict(self) -> Dict[str, Any]:
        if self.batch.n == 0:
            return {}
        return decode_batch(self.ft, self.batch, self.dicts)

    def to_pandas(self):
        import pandas as pd

        d = self.to_dict()
        if not d:
            return pd.DataFrame()
        geom = self.ft.geom_field
        if geom in d and isinstance(d[geom], list) and d[geom] and isinstance(d[geom][0], tuple):
            xs, ys = zip(*d[geom])
            d[geom + "_x"], d[geom + "_y"] = list(xs), list(ys)
            del d[geom]
        return pd.DataFrame(d)


class SpatialJoinResult:
    """Result of a co-partitioned spatial join (docs/JOIN.md): the exact
    matched-pair total plus a streaming matched-pair view. ``count`` is
    exact over completed tiles (equal to the full answer unless
    ``stats.skipped`` is non-empty — the ``allow_partial()`` degradation
    account). ``batches()`` streams matched pairs as ColumnBatches of at
    most ``geomesa.join.batch.rows`` rows: left columns verbatim, right
    columns prefixed ``right.`` (the attribute equi-join's convention)."""

    def __init__(self, lst, lbatch: ColumnBatch, rst, rbatch: ColumnBatch,
                 pairs, count: int, stats):
        self._lst, self._lbatch = lst, lbatch
        self._rst, self._rbatch = rst, rbatch
        #: matched (left, right) row positions, int64 [K, 2], row-major
        self.pairs = pairs
        self.count = int(count)
        self.stats = stats

    @property
    def degraded(self) -> bool:
        return bool(self.stats.skipped)

    def batches(self, batch_rows: Optional[int] = None):
        """Yield matched-pair ColumnBatches (chunked: peak memory is one
        chunk's gathered columns, never the whole pair set)."""
        if self.pairs is None:
            raise ValueError("join_count result carries no pairs; use "
                             "join_spatial for the streaming form")
        if batch_rows is None:
            batch_rows = config.JOIN_BATCH_ROWS.to_int() or 65536
        batch_rows = max(int(batch_rows), 1)
        for lo in range(0, len(self.pairs), batch_rows):
            chunk = self.pairs[lo: lo + batch_rows]
            li, rj = chunk[:, 0], chunk[:, 1]
            cols = {k: v[li] for k, v in self._lbatch.columns.items()}
            for k, v in self._rbatch.columns.items():
                cols["right." + k] = v[rj]
            yield ColumnBatch(cols, len(chunk))

    def __iter__(self):
        return self.batches()

    def to_batch(self) -> ColumnBatch:
        """The whole pair set as one ColumnBatch (small joins / tests)."""
        out = list(self.batches(batch_rows=max(len(self.pairs), 1)))
        return out[0] if out else ColumnBatch({}, 0)


def _traced(op: str, speculative: Optional[str] = None):
    """Open one ROOT span per public query operation (docs/OBSERVABILITY.md)
    and pass it through serving admission (docs/SERVING.md): the local-path
    analog of the sidecar's admission queue — an op whose deadline budget is
    already expired (or provably unmeetable against recent service times)
    is SHED with a typed error before any planning or device work, and the
    op's wall time lands in the per-user serving ledger that backs both
    fair-share and the /debug/queries rollups. Admission is reentrant
    (nested public ops account once) and a no-op inside a scheduler-
    dispatched ticket (the ticket already accounts).

    ``speculative``: name of a method serving the SPECULATIVE degraded
    answer when admission sheds AND the caller opted in with
    ``speculative_ok=True`` — the op returns the typed coarse result
    (host-only, no device work — exactly what shedding protects) instead
    of raising ``[GM-SHED]`` (docs/SERVING.md)."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, name, *args, **kw):
            from geomesa_tpu.resilience import DeadlineShedError

            spec_ok = bool(kw.pop("speculative_ok", False))
            with tracing.start(op, schema=name), utilization.OP_END:
                # the fallback runs INSIDE the op's root span, so the
                # speculative audit event carries this trace id — the
                # degraded answers are exactly the ones operators need
                # to correlate back to a trace
                try:
                    with self.serving.admit(op):
                        return fn(self, name, *args, **kw)
                except DeadlineShedError:
                    # only the ADMISSION gate raises DeadlineShedError
                    # (a mid-scan expiry is a plain QueryTimeoutError),
                    # so no device work has happened yet
                    if not (spec_ok and speculative):
                        raise
                    return getattr(self, speculative)(name, *args, **kw)

        return wrapper

    return deco


class GeoDataset:
    """Schema catalog + per-schema stores + planner + executor."""

    def __init__(self, mesh=None, n_shards: Optional[int] = None,
                 prefer_device: bool = True,
                 auths: Optional[Sequence[str]] = None):
        self.mesh = mesh
        self.n_shards = n_shards
        self.prefer_device = prefer_device
        #: dataset-level authorizations (None = geomesa.security.auths or
        #: unrestricted; per-query ``Query.auths`` overrides)
        self.auths = list(auths) if auths is not None else None
        self.audit = AuditWriter()
        #: aggregate result cache (docs/CACHE.md) — shared by every query of
        #: this dataset, including all Flight queries when a sidecar serves
        #: it. Inert unless geomesa.cache.enabled=true.
        self.cache = AggregateCache()
        #: serving scheduler (docs/SERVING.md): local ops pass through its
        #: inline admission (deadline shed + per-user ledger); a Flight
        #: sidecar serving this dataset starts its dispatch thread, so
        #: Flight and local ops share ONE fair-share domain and ledger.
        from geomesa_tpu.serving import QueryScheduler

        self.serving = QueryScheduler()
        self.serving.set_residency_probe(self._residency_bytes)
        self._stores: Dict[str, FeatureStore] = {}
        self._executors: Dict[str, Executor] = {}
        self.metadata: Dict[str, Dict[str, str]] = {}
        #: durable mutation journal (fs/journal.py; docs/RESILIENCE.md §8).
        #: Attached by load()/attach_journal(); None keeps the
        #: in-memory-only semantics (acked mutations live until the next
        #: explicit save). With it attached, every mutation edge appends a
        #: typed record BEFORE applying and blocks until it is on disk.
        self._journal = None
        #: replay guard: mutations applied FROM the journal or a checkpoint
        #: attach must not re-journal themselves
        self._replaying = False
        #: per-schema high-water mark of journal records applied locally —
        #: lets a fleet replica catch up incrementally from the shared
        #: journal instead of re-attaching the whole schema snapshot
        self._applied_seq: Dict[str, int] = {}
        #: fingerprint of the manifest entry each schema was attached from —
        #: the incremental journal catch-up is only valid while the root's
        #: manifest entry is unchanged (journal-only growth); an entry
        #: rewritten out-of-band (e.g. a non-journaled save) forces the
        #: full re-attach path
        self._ckpt_fp: Dict[str, int] = {}
        #: records re-applied by the last load()/replay (CLI/bench surface)
        self._journal_replayed = 0
        #: standing-query engine (geomesa_tpu/subscribe/; docs/STANDING.md)
        #: — created lazily on the first subscribe() so datasets that never
        #: register a viewport pay nothing on the ingest path
        self.standing = None

    # -- schema CRUD (MetadataBackedDataStore analog) ----------------------
    def create_schema(self, name_or_ft, spec: Optional[str] = None) -> FeatureType:
        if isinstance(name_or_ft, FeatureType):
            ft = name_or_ft
        else:
            ft = FeatureType.from_spec(name_or_ft, spec)
        if ft.name in self._stores:
            raise ValueError(f"schema {ft.name!r} already exists")
        # schema-create records carry the spec so recovery is self-contained
        # (a schema created after the last checkpoint rebuilds from the
        # journal alone)
        self._journal_rec("schema-create", ft.name, spec=ft.spec(),
                          n_shards=self.n_shards)
        from geomesa_tpu.index.partitioned import (
            PartitionedFeatureStore, is_partitioned_schema,
        )

        if is_partitioned_schema(ft):
            self._stores[ft.name] = PartitionedFeatureStore(ft, self.n_shards)
        else:
            self._stores[ft.name] = FeatureStore(ft, self.n_shards)
        self.metadata[ft.name] = {"spec": ft.spec()}
        return ft

    def get_schema(self, name: str) -> FeatureType:
        return self._store(name).ft

    def list_schemas(self) -> List[str]:
        return sorted(self._stores)

    def delete_schema(self, name: str):
        st = self._store(name)  # raise if missing
        # tombstone FIRST: if we crash between the in-memory drop and the
        # next checkpoint, replay must not resurrect the schema from its
        # still-on-disk files
        self._journal_rec("delete-schema", name)
        if self.standing is not None:
            self.standing.drop_schema(name)
        # drop the schema's cached aggregates: its uid is never accessed
        # again, so neither epoch sync nor the per-uid LRU could reclaim them
        self.cache.store.invalidate(st.uid)
        del self._stores[name]
        del self.metadata[name]
        self._applied_seq.pop(name, None)
        self._ckpt_fp.pop(name, None)

    def describe(self, name: str) -> str:
        st = self._store(name)
        lines = [st.ft.describe(), f"  count: {st.count}"]
        lines.append(f"  indices: {[ks.name for ks in st.keyspaces]}")
        return "\n".join(lines)

    def _store(self, name: str) -> FeatureStore:
        st = self._stores.get(name)
        if st is None:
            raise KeyError(
                f"no schema {name!r} (have: {', '.join(sorted(self._stores)) or 'none'})"
            )
        return st

    # -- durable mutation journal (docs/RESILIENCE.md §8) ------------------
    def attach_journal(self, path: str):
        """Attach (or create) the write-ahead mutation journal under
        ``path``: from here on, every mutation edge appends a typed,
        crc-framed record and blocks until it is group-committed to disk —
        **ack = durable**. ``load()`` attaches automatically when the root
        has a journal; ``save()`` attaches on first checkpoint. No-op when
        ``geomesa.journal.enabled`` is false or a journal is already
        attached. Returns the journal (or None when disabled)."""
        if not config.JOURNAL_ENABLED.to_bool():
            return None
        if self._journal is None:
            from geomesa_tpu.fs.journal import MutationJournal

            self._journal = MutationJournal(path)
        return self._journal

    @contextlib.contextmanager
    def _replay_scope(self):
        prev = self._replaying
        self._replaying = True
        try:
            yield
        finally:
            self._replaying = prev

    def _journal_rec(self, kind: str, name: Optional[str],
                     blobs=None, **payload) -> None:
        """Append one typed mutation record (WAL discipline: BEFORE the
        mutation applies) and block until durable. A journal failure
        raises — the mutation is never acked non-durable. ``blobs`` is
        the raw-bytes sink filled by the caller's enc_columns pass."""
        j = self._journal
        if j is None or self._replaying:
            return
        rec = {"kind": kind, "schema": name}
        rec.update(payload)
        seq = j.append(rec, blobs=blobs)
        if name is not None:
            self._applied_seq[name] = seq

    def _apply_record(self, rec: Dict[str, Any]) -> bool:
        """Re-apply one journal record through the normal mutation edges
        (under :meth:`_replay_scope`, so nothing re-journals). Returns
        False for unknown kinds."""
        from geomesa_tpu.fs import journal as _jr

        kind, name = rec.get("kind"), rec.get("schema")
        if kind == "schema-create":
            prev = self.n_shards
            self.n_shards = rec.get("n_shards", prev)
            try:
                self.create_schema(FeatureType.from_spec(name, rec["spec"]))
            finally:
                self.n_shards = prev
        elif kind == "delete-schema":
            # tombstone: replay must never resurrect a dropped schema whose
            # files outlived the crash
            if name in self._stores:
                self.delete_schema(name)
                self._plan_cache_clear(name)
                self._drop_executors(name)
        elif kind == "insert":
            self.insert(name, _jr.dec_columns(rec["data"]),
                        _jr.dec_value(rec.get("fids")),
                        _jr.dec_value(rec.get("vis")))
        elif kind == "delete-features":
            self.delete_features(name, rec["ecql"],
                                 _jr.dec_value(rec.get("auths")))
        elif kind == "update-schema":
            self.update_schema(name, rec["add_spec"])
        elif kind == "age-off":
            self.age_off(name, int(rec["older_than_ms"]))
        elif kind == "add-index":
            self.add_attribute_index(name, rec["attr"])
        elif kind == "remove-index":
            self.remove_attribute_index(name, rec["attr"])
        elif kind == "subscribe":
            from geomesa_tpu.subscribe.spec import StandingSpec

            self._standing_engine().register(
                StandingSpec.from_dict(rec["spec"]), sub_id=rec["sub_id"])
        elif kind == "unsubscribe":
            if self.standing is not None:
                self.standing.unregister(rec["sub_id"])
        else:
            return False
        return True

    def _journal_replay(self, ckpt_seq: Dict[str, int],
                        schema: Optional[str] = None,
                        truncate: bool = False) -> int:
        """Replay journal records past each schema's checkpointed position
        (``ckpt_seq``), in global sequence order. A record that fails to
        apply is recorded through the degradation trail and skipped — a
        poisoned record must not fail the whole root. Returns #applied."""
        j = self._journal
        if j is None:
            return 0
        applied = 0
        with self._replay_scope():
            for rec in j.records(schema=schema, truncate=truncate):
                name = rec.get("schema")
                seq = int(rec.get("seq", 0))
                if seq <= ckpt_seq.get(name, 0):
                    continue
                if seq <= self._applied_seq.get(name, 0):
                    continue  # already applied live / by a prior replay
                try:
                    if not self._apply_record(rec):
                        continue
                except Exception as e:
                    resilience.record_skip(
                        "journal.replay", f"{name}@{seq}", e, phase="apply")
                    continue
                if name is not None:
                    self._applied_seq[name] = seq
                applied += 1
        if applied:
            metrics.registry().counter(metrics.JOURNAL_REPLAYED).inc(applied)
        self._journal_replayed = applied
        return applied

    # -- writes ------------------------------------------------------------
    def insert(self, name: str, data: Dict[str, Any], fids=None,
               visibilities=None) -> int:
        """Append a batch of features. Call flush() (or query) to index.

        ``visibilities``: per-feature visibility expression(s) (one string or
        a sequence), enforced at query time against ``Query.auths``."""
        st = self._store(name)
        if self._journal is not None and not self._replaying:
            from geomesa_tpu.fs import journal as _jr

            sink: list = []
            self._journal_rec(
                "insert", name, blobs=sink,
                data=_jr.enc_columns(data, sink),
                fids=None if fids is None else _jr.enc_value(fids, sink),
                vis=None if visibilities is None
                else _jr.enc_value(visibilities, sink))
        # standing-query delta hook (docs/STANDING.md): the observer sees
        # the ENCODED batch inside append — the same columns a re-scan of
        # the window reads — so delta evaluation is race-free and fires on
        # journal replay too (fleet catch-up advances standing results
        # through this same edge)
        obs = None
        if self.standing is not None and self.standing.active(name):
            obs = lambda b: self.standing.on_batch(name, b.columns, b.n)
        n = st.append(data, fids, visibilities, observer=obs)
        metrics.registry().counter("ingest.features").inc(n)
        return n

    def flush(self, name: Optional[str] = None):
        for st in ([self._store(name)] if name else self._stores.values()):
            st.flush()

    def ingest(self, name: str, source, converter_config) -> "Any":
        """Converter-driven ingest (geomesa-convert analog). ``source`` is
        text / a file object / parsed JSON; returns the EvaluationContext
        with success/failure counts."""
        from geomesa_tpu.convert import EvaluationContext, converter_for

        st = self._store(name)
        conv = converter_for(st.ft, converter_config)
        ctx = EvaluationContext()
        for data, fids in conv.convert(source, ctx):
            if data and len(next(iter(data.values()), ())) > 0:
                self.insert(name, data, fids)
        self.flush(name)
        return ctx

    def update_schema(self, name: str, add_spec: str) -> FeatureType:
        """Add attributes to an existing schema, keeping data (the reference's
        ``updateSchema`` supports append-only attribute changes; GeoMesaData
        Store.scala:288-336 validates transitions the same way).

        Existing columns — including visibility labels and derived geometry/
        time columns — are carried over verbatim IN PLACE: no index key
        changes, so sort permutations are untouched and no row is
        re-flushed (r4 rebuilt the whole store here — O(dataset) per
        added column). Added columns fill with this layout's null
        representation: string -> null code (-1), float -> NaN, int/long
        -> 0, bool -> False, date -> epoch 0 (the fixed-width columnar
        model has no validity bitmap for those). Spilled partitions
        upgrade lazily on their next load."""
        st = self._store(name)
        st.flush()
        old = st.ft
        # insert new attributes before the ';user-data' section, if any
        spec = old.spec()
        attrs_part, sep, ud_part = spec.partition(";")
        new_ft = FeatureType.from_spec(
            name, attrs_part + "," + add_spec + sep + ud_part
        )
        added = [a for a in new_ft.attributes if not old.has(a.name)]
        for a in added:
            if a.is_geom:
                raise ValueError("cannot add geometry attributes to a schema")
        self._journal_rec("update-schema", name, add_spec=add_spec)
        st.add_columns(new_ft, added)
        self._drop_executors(name)
        self._plan_cache_clear(name)
        self.metadata[name]["spec"] = new_ft.spec()
        return new_ft

    def add_attribute_index(self, name: str, attr: str) -> None:
        """Enable an attribute index on an existing schema without
        recreating it: builds ONLY the new sort permutation (per
        partition, under the residency budget, for partitioned stores;
        spilled partitions build theirs on next load). The reference
        validates exactly this transition in updateSchema
        (GeoMesaDataStore.scala:288-336)."""
        st = self._store(name)
        a = st.ft.attr(attr)
        self._journal_rec("add-index", name, attr=attr)
        st.add_attribute_index(attr)
        a.options["index"] = "true"  # so spec()/save()/load round-trips
        # an explicit geomesa.indices list overrides the option-derived
        # defaults in keyspaces_for_schema — it must name the attr kind
        # or rebuilt/loaded child stores would silently drop the index
        explicit = st.ft.user_data.get("geomesa.indices")
        if explicit is not None:
            kinds = [k.strip().lower() for k in explicit.split(",")
                     if k.strip()]
            if "attr" not in kinds:
                st.ft.user_data["geomesa.indices"] = explicit + ",attr"
        self._drop_executors(name)
        self._plan_cache_clear(name)
        self.metadata[name]["spec"] = st.ft.spec()

    def remove_attribute_index(self, name: str, attr: str) -> None:
        """Drop an attribute index (permutation + sketch); data untouched."""
        st = self._store(name)
        self._journal_rec("remove-index", name, attr=attr)
        st.remove_attribute_index(attr)
        st.ft.attr(attr).options.pop("index", None)
        self._drop_executors(name)
        self._plan_cache_clear(name)
        self.metadata[name]["spec"] = st.ft.spec()

    def age_off(self, name: str, older_than) -> int:
        """Drop features older than a cutoff (AgeOffFilter/DtgAgeOffFilter
        analog, reference index/filters/AgeOffFilter.scala). ``older_than``:
        epoch-ms int, numpy datetime64, or ISO string. Returns rows removed."""
        st = self._store(name)
        dtg = st.ft.dtg_field
        if dtg is None:
            raise ValueError(f"schema {name!r} has no date attribute")
        if isinstance(older_than, str):
            from geomesa_tpu.filter.ecql import parse_iso_ms

            cutoff = parse_iso_ms(older_than)
        elif isinstance(older_than, np.datetime64):
            cutoff = int(older_than.astype("datetime64[ms]").astype(np.int64))
        else:
            cutoff = int(older_than)
        # the RESOLVED cutoff is journaled, so replay is deterministic even
        # for callers that passed a relative/now-derived value
        self._journal_rec("age-off", name, older_than_ms=cutoff)
        st.flush()
        pred = lambda cols: cols[dtg] < cutoff
        bounds = self._standing_dirty_bounds(name, st, pred)
        n = st.delete(pred)
        if n and self.standing is not None and self.standing.active(name):
            self.standing.on_dirty(name, bounds)
        return n

    def delete_features(self, name: str, ecql: str,
                        auths: Optional[Sequence[str]] = None) -> int:
        """Delete matching features. A caller with restricted auths can only
        delete rows their auths permit them to see."""
        st = self._store(name)
        f = parse_ecql(ecql)
        self._journal_rec("delete-features", name, ecql=ecql,
                          auths=None if auths is None else list(auths))
        from geomesa_tpu.filter.compile import compile_filter

        cf = compile_filter(f, st.ft, st.dicts)
        cf = self._vis_wrap(st, cf, self._effective_auths(Query(auths=auths)))
        # exact_mask applies the extent-geometry refinement pass — deletes
        # must never act on the coarse bbox superset
        pred = lambda cols: cf.exact_mask(cols, len(cols["__fid__"]))
        bounds = self._standing_dirty_bounds(name, st, pred)
        n = st.delete(pred)
        if n and self.standing is not None and self.standing.active(name):
            # deletes are non-additive: standing groups intersecting the
            # removed rows' bounds re-scan; disjoint groups are untouched
            self.standing.on_dirty(name, bounds)
        return n

    def _standing_dirty_bounds(self, name: str, st: FeatureStore, pred):
        """BBox of the rows ``pred`` is about to remove — the dirty extent
        a non-additive mutation scopes standing re-scans to (docs/
        STANDING.md). None = no standing groups, or unknown extent."""
        if self.standing is None or not self.standing.active(name):
            return None
        st.flush()
        if st._all is None or not st._all.n:
            return None
        g = st.ft.geom_field
        cols = st._all.columns
        if g is None or g + "__x" not in cols:
            return None
        try:
            m = np.asarray(pred(cols)).astype(bool)
        except Exception:
            return None
        xs = cols[g + "__x"][m]
        ys = cols[g + "__y"][m]
        ok = np.isfinite(xs) & np.isfinite(ys)
        if not ok.any():
            return None
        xs, ys = xs[ok], ys[ok]
        return (float(xs.min()), float(ys.min()),
                float(xs.max()), float(ys.max()))

    # -- standing queries (geomesa_tpu/subscribe/; docs/STANDING.md) -------
    def _standing_engine(self):
        if self.standing is None:
            from geomesa_tpu.subscribe import (
                StandingQueryEngine, StoreWindow,
            )

            self.standing = StandingQueryEngine(
                lambda nm: StoreWindow(self, nm)
            )
        return self.standing

    def subscribe(self, name: str, aggregate: str, bbox=None, region=None,
                  width: int = 256, height: int = 256,
                  levels: Optional[int] = None,
                  stat_spec: Optional[str] = None,
                  sub_id: Optional[str] = None) -> str:
        """Register a standing viewport: every applied ingest batch then
        updates the result incrementally instead of re-scanning (docs/
        STANDING.md). Same-viewport subscribers fuse into one standing
        group. Returns the subscription id (its prefix is the fleet ring
        route key). NOTE: standing results are visibility-unrestricted —
        they aggregate every row of the window."""
        from geomesa_tpu.subscribe import spec as subspec

        sp = subspec.make_spec(
            name, aggregate, bbox=bbox, region=region, width=width,
            height=height, levels=levels, stat_spec=stat_spec,
        )
        self._store(name)  # raise on unknown schema before registering
        eng = self._standing_engine()
        if sub_id is None:
            # WAL discipline: the journal record carries the id the
            # register will use, so crash replay rebuilds the SAME
            # subscription id the caller was handed (docs/STANDING.md §7)
            sub_id = eng.make_sub_id(sp)
        self._journal_rec("subscribe", name, spec=sp.to_dict(),
                          sub_id=sub_id)
        return eng.register(sp, sub_id=sub_id)

    def unsubscribe(self, sub_id: str) -> bool:
        if self.standing is None:
            return False
        schema = self.standing.schema_of(sub_id)
        if schema is not None:
            self._journal_rec("unsubscribe", schema, sub_id=sub_id)
        return self.standing.unregister(sub_id)

    def subscription_poll(self, sub_id: str, cursor: int = 0):
        """Current standing result + update records past ``cursor``."""
        from geomesa_tpu.subscribe import UnknownSubscription

        if self.standing is None:
            raise UnknownSubscription(sub_id)
        return self.standing.poll(sub_id, cursor)

    # -- planning ----------------------------------------------------------
    def _effective_auths(self, q: Query) -> Optional[List[str]]:
        if q.auths is not None:
            return list(q.auths)
        if self.auths is not None:
            return self.auths
        return security.DefaultAuthorizationsProvider().auths()

    def _vis_wrap(self, st: FeatureStore, compiled: CompiledFilter,
                  auths) -> CompiledFilter:
        """Fuse the row-visibility check into a predicate mask
        (LocalQueryRunner.visible:133 analog, but in the scan kernel)."""
        if auths is None:
            return compiled
        vd = st.dicts.get(security.VIS_COLUMN)
        if vd is None:
            return compiled  # no feature has ever carried a visibility
        lut = security.allowed_lut(vd.values, auths)
        if lut.all():
            return compiled
        inner = compiled

        def fn(cols, xp):
            allowed = xp.asarray(lut)[cols[security.VIS_COLUMN]]
            return inner.fn(cols, xp) & allowed

        refine = inner.refine
        if refine is not None:
            # the exact tree must ALSO enforce visibility: band corrections
            # and refinement passes evaluate it directly, and a row the
            # caller's auths cannot see must never be restored by either
            inner_refine = refine

            def refine(cols, xp=np):  # noqa: F811
                allowed = np.asarray(lut)[np.asarray(cols[security.VIS_COLUMN])]
                return np.asarray(inner_refine(cols, xp)) & allowed

        rcols = list(inner.refine_columns or [])
        if refine is not None and security.VIS_COLUMN not in rcols:
            rcols.append(security.VIS_COLUMN)
        return CompiledFilter(
            fn, list(inner.columns) + [security.VIS_COLUMN], inner.ecql,
            refine=refine, refine_columns=rcols,
            band=inner.band,
            refine_only_if_band=inner.refine_only_if_band,
        )

    def _apply_visibility(self, st: FeatureStore, plan, auths) -> None:
        plan.compiled = self._vis_wrap(st, plan.compiled, auths)

    def _plan(self, name: str, query: "str | Query", explain=None):
        from geomesa_tpu.kernels import registry as kreg

        # per-query recompile window: a jit site tracing more than
        # geomesa.kernel.alert.threshold times before the next query trips
        # the kernel.recompile.alert gauge (docs/OBSERVABILITY.md)
        kreg.begin_query_window()
        with tracing.span("plan"):
            return self._plan_inner(name, query, explain)

    def _plan_inner(self, name: str, query: "str | Query", explain=None):
        st = self._store(name)
        st.flush()
        q = Query(ecql=query) if isinstance(query, str) else query
        auths = self._effective_auths(q)
        # Plan-object cache (IteratorCache.scala:30 analog: remote servers
        # cache parsed filters by spec string): a plan is pure in (query,
        # auths, schema/store version, interceptor registry), and reusing
        # the OBJECT also reuses the window/kernel caches that live on it.
        pkey = None
        if explain is None and isinstance(q.ecql, str):
            from geomesa_tpu.planning import interceptors

            pkey = (name, repr(q), None if auths is None else tuple(auths),
                    st.uid, st.version, interceptors.version())
            cache = self.__dict__.setdefault("_plan_cache", {})
            hit = cache.get(pkey)
            if hit is not None:
                # guards are config-dependent (e.g. BLOCK_FULL_TABLE_SCANS
                # may have flipped since the plan was cached): re-check
                # them on every hit — they are cheap; planning is not
                QueryPlanner(st)._guard(hit.key_plan, hit.filter, Explainer())
                interceptors.apply_guards(st.ft, hit)
                # exec_path/degraded describe ONE execution: stale notes
                # from the cached plan's previous run (device_error, sort,
                # skipped partitions, ...) must not leak into this call's
                # audit/explain
                hit.__dict__.pop("exec_path", None)
                hit.__dict__.pop("degraded", None)
                return st, q, hit
        planner = QueryPlanner(st)
        t0 = time.perf_counter()
        with metrics.registry().timer("query.plan").time():
            plan = planner.plan(q.ecql, q.hints(), explain)
        self._apply_visibility(st, plan, auths)
        if isinstance(q.ecql, str):
            # the predicate is reproducible from text + auths + the
            # EFFECTIVE filter (interceptors may rewrite it for the same
            # text — QueryInterceptor.scala:51): allow the executor to
            # reuse jitted kernels and resolved windows across API calls
            plan.__dict__["cache_token"] = (
                q.ecql,
                None if auths is None else tuple(auths),
                hash(repr(plan.filter)),
            )
        plan.__dict__["plan_time_ms"] = (time.perf_counter() - t0) * 1e3
        if pkey is not None:
            if len(cache) >= 256:
                cache.clear()
            cache[pkey] = plan
        return st, q, plan

    def _plan_cache_clear(self, name: str) -> None:
        """Drop cached plans for one schema (lifecycle changes bump the
        store version too, so stale entries could never HIT — this just
        releases them eagerly). The fusion layer's structural-template
        memo rides along: slot eligibility reads the schema's attribute
        types, which lifecycle changes can alter (docs/SERVING.md
        "Query-axis batching")."""
        cache = self.__dict__.get("_plan_cache")
        if cache:
            for k in [k for k in cache if k[0] == name]:
                del cache[k]
        tcache = self.__dict__.get("_template_key_cache")
        if tcache:
            for k in [k for k in tcache if k[0] == name]:
                del tcache[k]

    @staticmethod
    def _plan_audit_extras(plan) -> Dict[str, Any]:
        """Execution-path hints shared by every audit writer (the normal
        :meth:`_audit` and the fused batch's per-member events): exec_path,
        device timings, and the degraded-partition account. Pops
        ``degraded`` — the plan object is cached/reused across calls, and
        each execution's skip list must be reported exactly once
        (docs/RESILIENCE.md)."""
        extras: Dict[str, Any] = {}
        path = plan.__dict__.get("exec_path")
        if path:
            extras["exec_path"] = {
                k: v for k, v in path.items() if v is not None
            }
        if "device_coarse_ms" in plan.__dict__:
            extras["device_coarse_ms"] = round(
                plan.__dict__["device_coarse_ms"], 3
            )
        acct = plan.__dict__.pop("lake_acct", None)
        if acct:
            # pruned-vs-loaded row groups and bytes for THIS execution
            # (docs/LAKE.md; popped like degraded — cached plans re-run)
            extras["lake"] = dict(acct)
        degraded = plan.__dict__.pop("degraded", None)
        if degraded:
            extras["degraded"] = [
                {"part": d.part, "error": d.error, "phase": d.phase}
                for d in degraded
            ]
        return extras

    def _audit(self, name: str, q: Query, plan, t_scan0: float, hits: int,
               op: str = "query"):
        hints = {"op": op, "index": plan.index_name,
                 "max_features": q.max_features, "sampling": q.sampling}
        # the span tree and the audit event meet on this id: operators go
        # from a slow QueryEvent straight to its trace (and, for sidecar
        # queries, from the server audit back to the client's root span)
        tid = tracing.current_trace_id()
        if tid is not None:
            hints["trace_id"] = tid
        hints.update(self._plan_audit_extras(plan))
        self.audit.record(
            name, plan.ecql, hints,
            plan.__dict__.get("plan_time_ms", 0.0),
            (time.perf_counter() - t_scan0) * 1e3, hits,
            # serving identity (docs/SERVING.md): the admitted user —
            # Flight header or geomesa.user — lands on the QueryEvent, so
            # the audit log and the fair-share ledger attribute alike
            user=self.serving.current_user() or "",
            scanned=plan.__dict__.get("scanned_rows", 0),
            table_rows=plan.__dict__.get("table_rows", 0),
        )

    @_traced("explain")
    def explain(self, name: str, query: "str | Query",
                analyze: bool = False, region=None) -> str:
        """Planner explain tree. ``analyze=True`` additionally resolves the
        scan windows and runs a count so the output reports selectivity —
        candidate (scanned) rows vs matched rows — the over-scan signal.
        ``region``: optional polygon, folded in exactly as the aggregate
        entry points do (see :meth:`density`)."""
        exp = Explainer(enabled=True)
        st, q0, plan = self._plan(
            name, self._with_region(name, query, region), exp
        )
        # cache participation (docs/CACHE.md): would this query be served
        # from / populate the aggregate cache, and in what shape?
        from geomesa_tpu.cache import decompose

        exp.push("Aggregate cache")
        exp.kv("enabled", bool(config.CACHE_ENABLED.to_bool()))
        d = decompose(plan.filter, st.ft)
        if d is not None:
            exp.kv("partial-cover", f"level {d.level}, "
                   f"{len(d.cells)} interior cells, "
                   f"{len(d.strips)} boundary strips")
            exp.kv("residual filter", d.residual_key)
        else:
            from geomesa_tpu.cache import decompose_region

            dr = decompose_region(plan.filter, st.ft)
            if dr is not None:
                exp.kv("polygon cover", f"level {dr.level}, "
                       f"{len(dr.cells)} interior cells, "
                       f"{len(dr.boundary)} boundary cells")
                exp.kv("residual filter", dr.residual_key)
            else:
                exp.line("partial-cover: not decomposable "
                         "(whole-result caching only)")
        exp.pop()
        # hierarchical pre-aggregation posture (docs/CACHE.md): would this
        # query's cells be served from the quadtree, and from which levels?
        from geomesa_tpu.cache import hierarchy as _hier

        exp.push("Hierarchy")
        exp.kv("enabled", _hier.enabled())
        exp.kv("depth", _hier.depth())
        probe = (self.cache.probe_cover(self, st, q0, plan)
                 if _hier.enabled() else None)
        if probe is not None:
            served = sum(probe["levels"].values())
            exp.kv(
                "cells resident/assemblable",
                f"{served}/{probe['cells']}"
                + (f" ({probe['boundary']} boundary cells scan exactly)"
                   if probe["kind"] == "polygon" else ""),
            )
            if probe["levels"]:
                exp.kv("levels hit", ", ".join(
                    f"L{lvl}={n}" for lvl, n in sorted(probe["levels"].items())
                ))
            exp.kv("residual fraction", probe["residual_fraction"])
        else:
            exp.line("no cell cover for this query (whole-result only)")
        exp.pop()
        # warm-path posture (docs/PERF.md): shape bucketing + the shared
        # version-stable kernel registry + the partition prefetch pipeline
        exp.push("Warm path")
        floor = config.COMPACT_BUCKET_FLOOR.to_int()
        exp.kv(
            "shape bucketing",
            f"on (K floor {8 if floor is None else floor})"
            if config.COMPACT_BUCKETING.to_bool() else "off",
        )
        ex0 = self._executor(st)
        reg = (ex0.kernel_registry()
               if hasattr(ex0, "kernel_registry") else None)
        if reg is not None:
            tr = reg.traces()
            exp.kv(
                "kernel registry",
                f"{len(reg)} compiled kernels, "
                f"{sum(tr.values())} traces to date",
            )
            if tr:
                per_site = ", ".join(
                    f"{site}={n}" for site, n in sorted(
                        tr.items(), key=lambda kv: -kv[1]
                    )[:8]
                )
                exp.kv("traces by site", per_site)
        # per-site recompile alert posture (docs/OBSERVABILITY.md): the
        # same signal /metrics exposes as kernel.recompile.alert
        from geomesa_tpu.kernels import registry as kreg

        thr = kreg.alert_threshold()
        qw = kreg.query_recompiles()
        over = {s: n for s, n in qw.items() if n > thr}
        exp.kv(
            "recompile alert",
            (f"TRIPPED ({', '.join(f'{s}={n}' for s, n in sorted(over.items()))})"
             if over else f"clear (threshold {thr}/query)"),
        )
        exp.kv("prefetch pipeline",
               bool(config.PIPELINE_PREFETCH.to_bool()))
        exp.kv("persistent compile cache", kreg.enable_persistent_cache())
        exp.pop()
        # observability posture. The trace_id is THIS explain call's own
        # trace (explain writes no audit event); a query's audit-greppable
        # id lives in its QueryEvent hints — this line documents the id
        # format and proves tracing is live end-to-end
        exp.push("Observability")
        exp.kv("tracing", "on" if tracing.enabled() else "off")
        tid = tracing.current_trace_id()
        if tid is not None:
            exp.kv("trace_id (this explain call)", tid)
        slow = config.TRACE_SLOW_MS.get()
        exp.kv("slow-query threshold", f"{slow} ms" if slow else "off")
        exp.pop()
        if analyze:
            ex = self._executor(st)
            matched = ex.count(plan)
            scanned = plan.__dict__.get("scanned_rows", 0)
            total = plan.__dict__.get("table_rows", 0)
            exp.push("Selectivity (analyze)")
            exp.line(f"Table rows: {total}")
            exp.line(f"Window candidates (scanned): {scanned}")
            exp.line(f"Matched: {matched}")
            if scanned:
                exp.line(f"Match ratio: {matched / scanned:.4f}")
            if "device_coarse_ms" in plan.__dict__:
                exp.line(
                    "Device coarse kernel: "
                    f"{plan.__dict__['device_coarse_ms']:.3f} ms "
                    "(host refined candidates only)"
                )
            path = plan.__dict__.get("exec_path")
            if path:
                exp.push("Execution path")
                for k, v in path.items():
                    if v is not None:
                        exp.line(f"{k}: {v}")
                # achieved scan bandwidth vs the docs/SCALE.md roofline
                # (the cost model's per-row HBM bound), when a device
                # coarse timing exists to measure against
                ms = plan.__dict__.get("device_coarse_ms")
                if ms and scanned:
                    n_cols = len(plan.compiled.columns) or 1
                    gbs = scanned * n_cols * 4 / (ms * 1e-3) / 1e9
                    exp.line(f"achieved scan bandwidth: {gbs:.1f} GB/s "
                             f"({scanned} rows x {n_cols} f32 cols)")
                exp.pop()
            exp.pop()
        # per-query cost attribution (docs/OBSERVABILITY.md): THIS explain
        # call's trace cost ledger — device ms per device, partition
        # pruning, bytes staged, cache hits — populated by analyze's count
        # (a plan-only explain shows planning-side cost only). The same
        # ledger rolls per-user into /debug/queries and rides exported
        # traces as geomesa.cost.* attributes.
        exp.push("Cost")
        cost = tracing.current_cost()
        if cost:
            for k, v in sorted(cost.items()):
                exp.kv(k, round(v, 3))
        else:
            exp.line("(none recorded — enable geomesa.trace.enabled and "
                     "analyze=True for device/partition attribution)")
        exp.pop()
        return str(exp)

    def _executor(self, st: FeatureStore) -> Executor:
        # one executor per store (per serving-pool slot): executors cache
        # sharding objects, and device_columns keys its upload cache by
        # id(sharding) — a fresh executor per query would re-upload every
        # column on meshed datasets. On a pool dispatch thread (slot > 0)
        # the executor is keyed (schema, slot) and PINNED to that slot's
        # device, so N dispatch threads drive N devices without ever
        # sharing one (docs/SERVING.md); slot 0 / inline callers keep the
        # original un-keyed, un-pinned executor byte-for-byte.
        from geomesa_tpu.index.partitioned import PartitionedFeatureStore
        from geomesa_tpu.planning.partitioned_exec import PartitionedExecutor

        slot = self.serving.current_slot()
        key = st.ft.name if not slot else (st.ft.name, slot)
        ex = self._executors.get(key)
        if ex is not None and slot and self.mesh is None \
                and self.prefer_device:
            # device-health re-pin (docs/RESILIENCE.md §6): the slot ->
            # device mapping moves when a device is cordoned or its
            # breaker opens (parallel/devices.slot_device skips fenced
            # lanes), so a cached slot executor pinned to the OLD device
            # is rebuilt on its next dispatch — the supervisor's
            # "respawn on a healthy device" lands here
            from geomesa_tpu.parallel.devices import slot_device

            if getattr(ex, "device", None) is not slot_device(slot):
                ex = None
        if ex is None or ex.store is not st:
            device = None
            if slot and self.mesh is None and self.prefer_device:
                from geomesa_tpu.parallel.devices import slot_device

                device = slot_device(slot)
            if isinstance(st, PartitionedFeatureStore):
                ex = PartitionedExecutor(st, self.mesh, self.prefer_device,
                                         device=device)
            else:
                ex = Executor(st, self.mesh, self.prefer_device,
                              device=device)
            self._executors[key] = ex
        return ex

    def _drop_executors(self, name: str) -> None:
        """Drop every slot's executor for one schema (lifecycle changes)."""
        for k in [k for k in self._executors
                  if k == name or (isinstance(k, tuple) and k[0] == name)]:
            del self._executors[k]

    def _residency_bytes(self, schema: str, slot: int) -> int:
        """One schema's device-resident column bytes on serving slot
        ``slot``'s device RIGHT NOW — the scheduler's placement-ranking
        probe (docs/SERVING.md §5c: rank candidate slots by ACTUAL
        residency, not by who dispatched last). A cheap metadata walk
        over the stores' device-column caches (no jit, no locks, no
        device sync — it runs under the scheduler lock). Meshed datasets
        shard every column across all devices, so residency is uniform
        and the probe abstains."""
        if self.mesh is not None:
            return 0
        st = self._stores.get(schema)
        if st is None:
            return 0
        try:
            from geomesa_tpu.parallel.devices import slot_device

            dev = slot_device(slot)
        except Exception:
            return 0
        total = 0
        children = (list(st.partitions.values())
                    if hasattr(st, "partitions") else [st])
        for child in children:
            for t in getattr(child, "tables", {}).values():
                for cached in list(t._device_cache.values()):
                    for arr in list(cached.values()):
                        try:
                            if dev in arr.devices():
                                total += int(arr.nbytes)
                        except Exception:
                            continue  # a mid-walk eviction never fails
        return total

    # -- reads -------------------------------------------------------------
    @staticmethod
    def _timeout_s() -> Optional[float]:
        ms = config.QUERY_TIMEOUT.to_duration_ms()
        return ms / 1000.0 if ms is not None else None

    @_traced("query")
    def query(self, name: str, query: "str | Query" = "INCLUDE") -> FeatureCollection:
        st, q, plan = self._plan(name, query)
        t0 = time.perf_counter()
        ex = self._executor(st)
        with metrics.registry().timer("query.scan").time(), \
                query_deadline(self._timeout_s()):
            batch = None
            # sort+limit pushdown: the device selects the top-k candidate
            # rows by the PRIMARY sort key (superset with boundary ties —
            # threshold select for large k / non-f32 dtypes), and the host
            # gathers + exact-sorts only those candidates instead of the
            # whole result set. Multi-key sorts are exact because every
            # primary-key boundary tie is among the candidates.
            topk_max = config.TOPK_MAX.to_int()
            if topk_max is None:
                topk_max = int(config.TOPK_MAX.default)  # 0 disables
            if (
                q.sort_by
                and q.max_features is not None
                and 0 < q.max_features <= topk_max
            ):
                attr, desc = q.sort_by[0]
                ties = len(q.sort_by) > 1
                names = None
                if plan.hints.properties:
                    names = list(plan.hints.properties) + [
                        a for a, _ in q.sort_by]
                if hasattr(ex, "top_rows"):
                    idx = ex.top_rows(plan, attr, desc, q.max_features,
                                      include_ties=ties)
                    if idx is not None:
                        table = st.tables[plan.index_name]
                        batch = table.host_gather_positions(idx, names)
                elif hasattr(ex, "top_batch"):
                    # partitioned store: per-partition candidate top-ks,
                    # exact-sorted + truncated below
                    batch = ex.top_batch(plan, attr, desc, q.max_features,
                                         names, include_ties=ties)
                if batch is not None:
                    plan.__dict__.setdefault("exec_path", {})[
                        "sort"] = f"device-topk(k={q.max_features})"
            if batch is None:
                batch = ex.features(plan)
        self._audit(name, q, plan, t0, batch.n)
        # post-processing: sort -> limit -> projection (QueryPlanner.runQuery
        # order, reference QueryPlanner.scala:68-90)
        if q.sort_by and batch.n:
            # stable multi-key sort, least-significant key first
            order = np.arange(batch.n)
            for attr, desc in reversed(q.sort_by):
                col = batch.columns[attr][order]
                if attr in st.dicts:
                    # dictionary codes are insertion-ordered: decode so
                    # ORDER BY a string is lexicographic (nulls first)
                    col = np.asarray(
                        [v if v is not None else ""
                         for v in st.dicts[attr].decode(col)],
                        dtype=object,
                    )
                if desc:
                    o2 = (batch.n - 1) - np.argsort(col[::-1], kind="stable")[::-1]
                else:
                    o2 = np.argsort(col, kind="stable")
                order = order[o2]
            batch = ColumnBatch(
                {k: v[order] for k, v in batch.columns.items()}, batch.n
            )
        if q.max_features is not None and batch.n > q.max_features:
            batch = ColumnBatch(
                {k: v[: q.max_features] for k, v in batch.columns.items()},
                q.max_features,
            )
        if q.properties:
            keep = set(q.properties) | {"__fid__"}
            pref = tuple(p + "__" for p in q.properties)
            batch = ColumnBatch(
                {
                    k: v for k, v in batch.columns.items()
                    if k in keep or k.startswith(pref)
                },
                batch.n,
            )
        if q.srid is not None and q.srid != 4326 and batch.n:
            batch = self._reproject_batch(st.ft, batch, q.srid)
        return FeatureCollection(st.ft, batch, st.dicts, srid=q.srid or 4326)

    @staticmethod
    def _reproject_batch(ft: FeatureType, batch: ColumnBatch,
                         srid: int) -> ColumnBatch:
        """Transform every geometry column to ``srid`` (last step of the
        post-processing chain, matching QueryPlanner.scala:68-90; raises
        for unregistered CRS pairs). Point x/y columns transform in one
        vectorized pass; WKT extent columns batch every vertex of every
        geometry into one transform call (nulls pass through)."""
        from geomesa_tpu.utils import reproject as rp

        fn = rp.transformer(4326, srid)
        cols = dict(batch.columns)
        for a in ft.attributes:
            if not a.is_geom:
                continue
            xc, yc = a.name + "__x", a.name + "__y"
            if xc in cols:
                x, y = fn(
                    np.asarray(cols[xc], np.float64),
                    np.asarray(cols[yc], np.float64),
                )
                cols[xc], cols[yc] = x, y
            wc = a.name + "__wkt"
            if wc in cols:
                cols[wc] = rp.reproject_wkt_array(cols[wc], fn)
        return ColumnBatch(cols, batch.n)

    def query_batches(self, name: str, query: "str | Query" = "INCLUDE",
                      batch_rows: Optional[int] = None):
        """Stream query results as ColumnBatch chunks (the ArrowScan delta-
        batch contract): a partitioned store yields partition-at-a-time so
        peak memory is one partition's matches, never the whole result.
        Sorted queries fall back to one materialized batch (a global sort
        needs all rows). Projection and CRS reprojection (Query.srid)
        apply per chunk — the stream carries the same CRS query() returns
        — and audit fires once at stream end."""
        q = Query(ecql=query) if isinstance(query, str) else query
        if q.sort_by:  # a global sort needs all rows: one materialized batch
            fc = self.query(name, q)

            def _one():
                if fc.batch.n:
                    yield fc.batch

            return _one()
        # plan EAGERLY so unknown attributes / parse errors / guard vetoes
        # (and unregistered CRS pairs) raise here, not mid-stream inside
        # the consumer's iteration. The root span is managed manually
        # (adopt + finish, never __enter__/__exit__): it must cover the
        # consumer-driven iteration, which outlives this call frame.
        root = tracing.start("query_batches", schema=name)
        traced = root is not tracing.NOOP
        prev = tracing.snapshot()
        if traced:
            root.t0 = time.perf_counter()
            tracing.adopt(root)
        try:
            # serving admission (docs/SERVING.md): shed-before-work + the
            # per-user ledger; the admitted span covers the eager planning
            # (the stream body is driven by the consumer's iteration)
            with self.serving.admit("query_batches"):
                st, q, plan = self._plan(name, q)
            if q.srid is not None and q.srid != 4326:
                from geomesa_tpu.utils import reproject as rp

                rp.transformer(4326, q.srid)  # raise now if unknown
        except BaseException:
            # the generator (whose finally owns the happy-path finish)
            # never runs when planning raises: close the root here so a
            # failed query still lands in the histogram/slow log
            if traced:
                root.finish()
            raise
        finally:
            if traced:
                tracing.adopt(prev)  # restore any enclosing span, not None
        keep_pref = None
        if q.properties:
            keep = set(q.properties) | {"__fid__"}
            keep_pref = (keep, tuple(p + "__" for p in q.properties))

        def _iter():
            t0 = time.perf_counter()
            hits = 0
            iter_prev = tracing.snapshot()  # the CONSUMER thread's context
            if traced:
                tracing.adopt(root)
            try:
                with metrics.registry().timer("query.scan").time(), \
                        query_deadline(self._timeout_s()):
                    for batch in self._executor(st).features_iter(plan, batch_rows):
                        hits += batch.n
                        if keep_pref is not None:
                            keep, pref = keep_pref
                            batch = ColumnBatch(
                                {
                                    k: v for k, v in batch.columns.items()
                                    if k in keep or k.startswith(pref)
                                },
                                batch.n,
                            )
                        if q.srid is not None and q.srid != 4326 and batch.n:
                            batch = self._reproject_batch(st.ft, batch, q.srid)
                        yield batch
                self._audit(name, q, plan, t0, hits)
            finally:
                if traced:
                    root.finish()
                    tracing.adopt(iter_prev)

        return _iter()

    def _with_region(self, name: str, query: "str | Query", region):
        """Fold a polygon ``region`` into the query as one INTERSECTS
        conjunct on the schema's geometry — the canonical aggregate-over-
        polygon shape (docs/CACHE.md): the cache decomposes it into
        interior cells (hierarchy-served) plus an exact boundary scan.
        ``region``: WKT text or a geometry object. Composed as ECQL TEXT
        when the query is textual, so the plan cache, the version-stable
        kernel tokens, and the serving fusion keys (docs/SERVING.md) all
        see the polygon — two different regions can never fuse or share a
        whole-result entry."""
        if region is None:
            return query
        from geomesa_tpu.utils import geometry as geo

        geom = self._store(name).ft.geom_field
        if geom is None:
            raise ValueError(f"schema {name!r} has no geometry field")
        wkt = region if isinstance(region, str) else region.wkt()
        geo.parse_wkt(wkt)  # validate before it reaches the planner
        conjunct = f"INTERSECTS({geom}, {wkt})"
        q = query if isinstance(query, Query) else Query(ecql=query)
        if not isinstance(q.ecql, str):
            combined: "str | ir.Filter" = ir.And(
                (q.ecql, parse_ecql(conjunct))
            )
        elif q.ecql.strip().upper() == "INCLUDE":
            combined = conjunct
        else:
            combined = f"({q.ecql}) AND {conjunct}"
        import dataclasses

        q = dataclasses.replace(q, ecql=combined)
        return q if isinstance(query, Query) or not isinstance(combined, str) \
            else combined

    @_traced("count", speculative="_speculative_count")
    def count(self, name: str, query: "str | Query" = "INCLUDE",
              exact: bool = True, region=None) -> int:
        """Exact feature count. ``speculative_ok=True`` (kw): under
        overload, a count this deadline would shed at admission returns
        the planner's coarse estimate — typed via an audit event carrying
        ``speculative: true`` — instead of failing ``[GM-SHED]``
        (docs/SERVING.md; the sidecar's ``speculative_ok`` request flag /
        ``x-geomesa-speculative-ok`` header ride the same path)."""
        st, q, plan = self._plan(name, self._with_region(name, query, region))
        if not exact:
            return int(plan.est_count)
        t0 = time.perf_counter()
        with query_deadline(self._timeout_s()):
            n = self.cache.count(self, st, q, plan)
        self._audit(name, q, plan, t0, n, op="count")
        return n

    def _speculative_count(self, name: str, query: "str | Query" = "INCLUDE",
                           exact: bool = True, region=None) -> int:
        """The speculative degraded count (see :meth:`count`): planner
        estimate only — host work, zero device time — with its own audit
        marker so operators can distinguish every coarse answer served
        under load from the exact counts around it."""
        st, q, plan = self._plan(name, self._with_region(name, query, region))
        est = int(plan.est_count)
        metrics.inc(metrics.SERVING_SPECULATIVE)
        hints = {"op": "count", "index": plan.index_name,
                 "speculative": True, "shed": True}
        tid = tracing.current_trace_id()
        if tid is not None:
            hints["trace_id"] = tid
        self.audit.record(
            name, plan.ecql, hints,
            plan.__dict__.get("plan_time_ms", 0.0), 0.0, est,
            user=self.serving.current_user() or "",
        )
        return est

    def bounds(self, name: str) -> Optional[Tuple[float, float, float, float]]:
        st = self._store(name)
        st.flush()
        mm = st.stats.get("bounds")
        if not isinstance(mm, sk.MinMax) or mm.is_empty:
            return None
        return (mm.lo[0], mm.lo[1], mm.hi[0], mm.hi[1])

    # -- analytics (geomesa-process parity) --------------------------------
    @_traced("density", speculative="_speculative_density")
    def density(self, name: str, query: "str | Query" = "INCLUDE",
                bbox=None, width: int = 256, height: int = 256,
                weight: Optional[str] = None, region=None) -> np.ndarray:
        """Heatmap grid (DensityProcess / DensityScan analog). ``region``:
        optional polygon (WKT or geometry) clipping the aggregate — folded
        in as an INTERSECTS conjunct; with the cache enabled the interior
        decomposes over hierarchy cells and only the polygon boundary
        scans (docs/CACHE.md). ``speculative_ok=True`` (kw): under
        overload, a density this deadline would shed at admission returns
        the coarse cache/hierarchy-served estimate grid — typed via an
        audit event carrying ``speculative: true`` — instead of failing
        ``[GM-SHED]`` (docs/SERVING.md)."""
        st, q, plan = self._plan(name, self._with_region(name, query, region))
        if bbox is None:
            bbox = self.bounds(name) or (-180, -90, 180, 90)
            bbox = (bbox[0], bbox[1], bbox[2], bbox[3])
        else:
            bbox = tuple(bbox)
        t0 = time.perf_counter()
        with metrics.registry().timer("query.density").time(), \
                query_deadline(self._timeout_s()):
            grid = self.cache.density(
                self, st, q, plan, bbox, width, height, weight
            )
        self._audit(name, q, plan, t0, int(np.count_nonzero(grid)), op="density")
        return grid

    def _speculative_audit(self, name: str, plan, op: str, hits: int,
                           extra: Optional[Dict[str, Any]] = None) -> None:
        """Shared audit marker for every speculative degraded answer
        (docs/SERVING.md): ``speculative: true`` + ``shed: true`` so
        operators can distinguish each coarse answer served under load."""
        metrics.inc(metrics.SERVING_SPECULATIVE)
        hints: Dict[str, Any] = {"op": op, "index": plan.index_name,
                                 "speculative": True, "shed": True}
        if extra:
            hints.update(extra)
        tid = tracing.current_trace_id()
        if tid is not None:
            hints["trace_id"] = tid
        self.audit.record(
            name, plan.ecql, hints,
            plan.__dict__.get("plan_time_ms", 0.0), 0.0, hits,
            user=self.serving.current_user() or "",
        )

    def _speculative_density(self, name: str,
                             query: "str | Query" = "INCLUDE",
                             bbox=None, width: int = 256, height: int = 256,
                             weight: Optional[str] = None,
                             region=None) -> np.ndarray:
        """The speculative degraded density (see :meth:`density`): a
        coarse estimate grid assembled from RESIDENT cache/hierarchy
        count cells — host reads only, zero device work (exactly what
        shedding protects). Resident cells splat their exact counts
        uniformly over their footprint; unresident coverage splats the
        planner-estimate remainder; a non-decomposable query splats the
        whole estimate. Typed + audited like speculative counts.
        Weighted grids never serve speculatively — the resident cells
        hold row COUNTS, and a count splatted into a weight-sum grid
        would be a silent unit change — so a weighted shed stays
        ``[GM-SHED]``."""
        if weight is not None:
            from geomesa_tpu.resilience import DeadlineShedError

            raise DeadlineShedError(
                "[GM-SHED] weighted density has no speculative form "
                "(resident cells hold counts, not weight sums)"
            )
        st, q, plan = self._plan(name, self._with_region(name, query, region))
        if bbox is None:
            bbox = self.bounds(name) or (-180, -90, 180, 90)
        bbox = tuple(float(v) for v in bbox)
        grid = np.zeros((height, width), np.float32)
        est = float(plan.est_count)
        got = self.cache.speculative_cells(self, st, q, plan)

        def splat(box, value):
            # uniform splat of `value` over box ∩ render bbox, in pixels
            x0, y0, x1, y1 = box
            sx = width / max(bbox[2] - bbox[0], 1e-12)
            sy = height / max(bbox[3] - bbox[1], 1e-12)
            c0 = int(np.clip(np.floor((x0 - bbox[0]) * sx), 0, width))
            c1 = int(np.clip(np.ceil((x1 - bbox[0]) * sx), 0, width))
            r0 = int(np.clip(np.floor((y0 - bbox[1]) * sy), 0, height))
            r1 = int(np.clip(np.ceil((y1 - bbox[1]) * sy), 0, height))
            if c1 > c0 and r1 > r0 and value > 0:
                grid[r0:r1, c0:c1] += np.float32(
                    value / ((r1 - r0) * (c1 - c0))
                )

        resident_cells = 0
        if got is not None:
            decomp, resident, missing = got
            from geomesa_tpu.cache.cells import cell_box

            resident_cells = len(resident)
            served = 0
            for cell, n in resident:
                splat(cell_box(decomp.level, *cell), float(n))
                served += n
            remainder = max(est - served, 0.0)
            uncovered = len(missing) + decomp.residual_count()
            if uncovered and remainder > 0:
                for cell in missing:
                    splat(cell_box(decomp.level, *cell),
                          remainder / uncovered)
        else:
            splat(bbox, est)
        self._speculative_audit(
            name, plan, "density", int(np.count_nonzero(grid)),
            {"resident_cells": resident_cells},
        )
        return grid

    @_traced("density_curve")
    def density_curve(self, name: str, query: "str | Query" = "INCLUDE",
                      level: int = 9, bbox=None,
                      weight: Optional[str] = None, region=None):
        """Exact density over the morton-block grid at ``level`` (a global
        2^level x 2^level partition of lon/lat — the EPSG:4326 tile pyramid
        aligns with it by construction). Returns ``(grid, snapped_bbox)``
        where the grid covers the blocks intersecting ``bbox`` (default:
        the store's bounds), row 0 at the south edge.

        This is the index-native heatmap: per-block counts are CDF
        differences over the z2-sorted scan — no scatter — so it runs at
        memory bandwidth where the per-pixel scatter path pays ~6.7 ns per
        scanned row (docs/SCALE.md). Use it for tile rendering; use
        :meth:`density` when the grid must align to an arbitrary bbox.

        ``region``: optional polygon (WKT or geometry) folded in as an
        INTERSECTS conjunct; the cache's block-chunk loop classifies each
        chunk against it — interior chunks share residual-keyed entries
        with non-region pyramids and outside chunks never scan
        (docs/CACHE.md "Polygon curve chunks")."""
        if not 0 < level <= 15:
            raise ValueError("level must be in 1..15 (grid = 4^level blocks)")
        query = self._with_region(name, query, region)
        q = Query(ecql=query) if isinstance(query, str) else query
        import dataclasses

        q = dataclasses.replace(q, index="z2")
        st, q, plan = self._plan(name, q)
        if bbox is None:
            bbox = self.bounds(name) or (-180.0, -90.0, 180.0, 90.0)
        window, snapped = self._snap_blocks(bbox, level)
        t0 = time.perf_counter()
        with metrics.registry().timer("query.density").time(), \
                query_deadline(self._timeout_s()):
            grid = self.cache.density_curve(
                self, st, q, plan, level, window, weight
            )
        self._audit(name, q, plan, t0, int(np.count_nonzero(grid)),
                    op="density_curve")
        return grid, snapped

    @staticmethod
    def _snap_blocks(bbox, level: int):
        """Snap a bbox outward to the level-``level`` morton block grid:
        ``((ix0, iy0, ix1, iy1), snapped_bbox)``. Inclusive outward snap:
        floor on BOTH edges — a bbox edge exactly on a block boundary
        includes the block CONTAINING it, matching the inclusive
        x <= xmax semantics of the equivalent BBOX filter."""
        n_blocks = 1 << level
        fx = lambda v: (v + 180.0) / 360.0 * n_blocks  # noqa: E731
        fy = lambda v: (v + 90.0) / 180.0 * n_blocks  # noqa: E731
        ix0 = int(np.clip(np.floor(fx(bbox[0])), 0, n_blocks - 1))
        ix1 = int(np.clip(np.floor(fx(bbox[2])), ix0, n_blocks - 1))
        iy0 = int(np.clip(np.floor(fy(bbox[1])), 0, n_blocks - 1))
        iy1 = int(np.clip(np.floor(fy(bbox[3])), iy0, n_blocks - 1))
        snapped = (
            ix0 * 360.0 / n_blocks - 180.0,
            iy0 * 180.0 / n_blocks - 90.0,
            (ix1 + 1) * 360.0 / n_blocks - 180.0,
            (iy1 + 1) * 180.0 / n_blocks - 90.0,
        )
        return (ix0, iy0, ix1, iy1), snapped

    def density_curve_batch(self, name: str, query: "str | Query" = "INCLUDE",
                            level: int = 9, bboxes=(), weight: Optional[str] = None,
                            members: Optional[List[Dict[str, Any]]] = None):
        """N curve-aligned density crops of ONE layer + filter in a single
        device pass (docs/SERVING.md): the cross-query fusion entry the
        serving scheduler uses when concurrent clients ask for different
        tiles of the same heatmap. Plans once, stacks the per-crop CDF
        gather positions over the query axis, and de-interleaves
        bit-identically versus calling :meth:`density_curve` per bbox.

        Returns ``[(grid, snapped_bbox), ...]`` in ``bboxes`` order (a
        ``None`` bbox uses the store bounds). ``members`` (optional, same
        length): per-member metadata dicts — ``trace_id``/``user`` land in
        that member's audit event so fused queries stay individually
        attributable. Bypasses the aggregate cache (each member is a
        fresh crop; repeats are served by fusion itself)."""
        if not 0 < level <= 15:
            raise ValueError("level must be in 1..15 (grid = 4^level blocks)")
        q = Query(ecql=query) if isinstance(query, str) else query
        import dataclasses

        q = dataclasses.replace(q, index="z2")
        bboxes = list(bboxes)
        if members is not None and len(members) != len(bboxes):
            raise ValueError("members must align with bboxes")
        with tracing.start("density_curve_batch", schema=name,
                           batch=len(bboxes)), utilization.OP_END, \
                self.serving.admit("density_curve"):
            st, q, plan = self._plan(name, q)
            default_bbox = None
            windows, snaps = [], []
            for bb in bboxes:
                if bb is None:
                    if default_bbox is None:
                        default_bbox = (
                            self.bounds(name)
                            or (-180.0, -90.0, 180.0, 90.0)
                        )
                    bb = default_bbox
                w, s = self._snap_blocks(bb, level)
                windows.append(w)
                snaps.append(s)
            t0 = time.perf_counter()
            with metrics.registry().timer("query.density").time(), \
                    query_deadline(self._timeout_s()):
                ex = self._executor(st)
                if hasattr(ex, "density_curve_batch"):
                    grids = ex.density_curve_batch(plan, level, windows,
                                                   weight)
                else:  # executor without the fused entry: per-crop serial
                    grids = [
                        ex.density_curve(plan, level, w, weight)
                        for w in windows
                    ]
            # one audit event PER MEMBER via the shared fused-batch audit
            # helper (fused queries stay individually attributable; the
            # shared scan cost + extras ride member 0 so sums over events
            # never double-count). All members share ONE plan here.
            self._batch_audit(
                name, "density_curve", [plan],
                [int(np.count_nonzero(g)) for g in grids], t0, members,
                extra_hints={"level": level}, distinct=False,
            )
            return list(zip(grids, snaps))

    def density_curve_filter_batch(self, name: str, queries, level: int = 9,
                                   bboxes=None, weight: Optional[str] = None,
                                   members: Optional[List[Dict[str, Any]]] = None):
        """M curve-aligned density crops with DISTINCT filters — each
        member its own viewport literals AND its own crop window — in one
        device dispatch, or None when the members do not share a
        batchable structural template (docs/SERVING.md "Query-axis
        batching", extended to the curve path). Returns
        ``[(grid, snapped_bbox), ...]`` in member order, each grid
        bit-identical to its serial :meth:`density_curve`."""
        if not 0 < level <= 15:
            raise ValueError("level must be in 1..15 (grid = 4^level blocks)")
        if not queries:
            return []
        if members is not None and len(members) != len(queries):
            raise ValueError("members must align with queries")
        bboxes = list(bboxes) if bboxes is not None \
            else [None] * len(queries)
        if len(bboxes) != len(queries):
            raise ValueError("bboxes must align with queries")
        import dataclasses

        qs = [
            dataclasses.replace(
                Query(ecql=q) if isinstance(q, str) else q, index="z2"
            )
            for q in queries
        ]
        with tracing.start("density_curve_filter_batch", schema=name,
                           batch=len(qs)), utilization.OP_END, \
                self.serving.admit("density_curve"):
            st, plans, spec = self._batch_plans(name, qs)
            if spec is None:
                return None
            ex = self._executor(st)
            if not hasattr(ex, "density_curve_filter_batch"):
                return None
            default_bbox = None
            windows, snaps = [], []
            for bb in bboxes:
                if bb is None:
                    if default_bbox is None:
                        default_bbox = (
                            self.bounds(name)
                            or (-180.0, -90.0, 180.0, 90.0)
                        )
                    bb = default_bbox
                w, s = self._snap_blocks(bb, level)
                windows.append(w)
                snaps.append(s)
            t0 = time.perf_counter()
            with metrics.registry().timer("query.density").time(), \
                    query_deadline(self._timeout_s()):
                grids = ex.density_curve_filter_batch(
                    plans, spec, level, windows, weight
                )
            if grids is None:
                return None
            metrics.inc(metrics.SERVING_FUSED_DISTINCT, len(grids))
            self._batch_audit(
                name, "density_curve", plans,
                [int(np.count_nonzero(g)) for g in grids], t0, members,
                extra_hints={"level": level},
            )
            return list(zip(grids, snaps))

    # -- query-axis batched aggregates (docs/SERVING.md "Query-axis
    # batching"): M *distinct* viewports of one structural query shape in
    # a single device dispatch. These are the fusion layer's distinct-
    # literal batch executors (serving/fuse.py) and are also directly
    # callable. Every method returns None when the batch cannot ride the
    # megakernel — the caller degrades to query-at-a-time execution, so
    # batching can change latency, never results. Bypasses the aggregate
    # cache (each member is a fresh viewport; repeats are served by
    # repeat fusion / the cache on the serial path).
    def _batch_plans(self, name: str, queries):
        """Plan every member; returns ``(st, plans, spec)`` with spec None
        when the members do not share a batchable structural template."""
        from geomesa_tpu.planning import batch as batchmod

        qs = [Query(ecql=q) if isinstance(q, str) else q for q in queries]
        auths = self._effective_auths(qs[0])
        akey = None if auths is None else tuple(auths)
        st = plans = None
        triples = []
        for q in qs:
            if (None if self._effective_auths(q) is None
                    else tuple(self._effective_auths(q))) != akey:
                return None, None, None  # mixed auths never batch
            triples.append(self._plan(name, q))
        st = triples[0][0]
        plans = [t[2] for t in triples]
        # members near an index cost boundary can split their choice
        # (say z2 vs z3 for one bbox+time template): the batch needs ONE
        # table, and any candidate index returns identical results, so
        # re-plan the minority onto the majority's index
        names = {p.index_name for p in plans}
        if len(names) > 1:
            import dataclasses
            from collections import Counter

            maj = Counter(
                p.index_name for p in plans
            ).most_common(1)[0][0]
            for i, (q, p) in enumerate(zip(qs, plans)):
                if p.index_name != maj:
                    try:
                        _, _, p2 = self._plan(
                            name, dataclasses.replace(q, index=maj)
                        )
                        plans[i] = p2
                    except Exception:
                        return st, plans, None  # index can't serve it
        spec = batchmod.build_spec(self, st, plans, auths)
        return st, plans, spec

    def _batch_audit(self, name: str, op: str, plans, hits, t0: float,
                     members, extra_hints=None,
                     distinct: bool = True) -> None:
        """One audit event PER MEMBER of a fused batch: fused queries
        stay individually attributable; the shared scan cost and
        execution-path extras ride member 0 so sums over events never
        double-count. ``plans`` is per-member, or length-1 when every
        member shares one plan (the density_curve tile batch);
        ``distinct`` marks query-axis (distinct-literal) batches."""
        scan_ms = (time.perf_counter() - t0) * 1e3
        extras = self._plan_audit_extras(plans[0])
        shared_plan = len(plans) != len(hits)
        for i in range(len(hits)):
            plan = plans[0] if shared_plan else plans[i]
            hints: Dict[str, Any] = {
                "op": op, "index": plan.index_name, "fused": True,
                "fused_batch": len(hits), "fused_member": i,
            }
            if distinct:
                hints["distinct"] = True
            if extra_hints:
                hints.update(extra_hints)
            m = members[i] if members is not None else {}
            tid = m.get("trace_id") or tracing.current_trace_id()
            if tid is not None:
                hints["trace_id"] = tid
            if m.get("user"):
                hints["user"] = m["user"]
            if i == 0:
                hints.update(extras)
            self.audit.record(
                name, plan.ecql, hints,
                plan.__dict__.get("plan_time_ms", 0.0) if i == 0 else 0.0,
                scan_ms if i == 0 else 0.0,
                int(hits[i]),
                user=m.get("user") or (self.serving.current_user() or ""),
                scanned=plan.__dict__.get("scanned_rows", 0)
                if i == 0 else 0,
                table_rows=plan.__dict__.get("table_rows", 0),
            )

    def count_batch(self, name: str, queries, exact: bool = True,
                    members: Optional[List[Dict[str, Any]]] = None):
        """M distinct exact counts in one device dispatch, or None when
        the members do not share a structural template (the caller runs
        them query-at-a-time). Each member's value equals its serial
        :meth:`count` exactly — the CI-gated contract."""
        if not queries:
            return []
        if not exact:
            return None  # estimates never scan; nothing to batch
        if members is not None and len(members) != len(queries):
            raise ValueError("members must align with queries")
        with tracing.start("count_batch", schema=name,
                           batch=len(queries)), utilization.OP_END, \
                self.serving.admit("count"):
            st, plans, spec = self._batch_plans(name, queries)
            if spec is None:
                return None
            ex = self._executor(st)
            if not hasattr(ex, "count_batch"):
                return None
            t0 = time.perf_counter()
            with query_deadline(self._timeout_s()):
                res = ex.count_batch(plans, spec)
            if res is None:
                return None
            metrics.inc(metrics.SERVING_FUSED_DISTINCT, len(res))
            self._batch_audit(name, "count", plans, res, t0, members)
            return res

    def density_batch(self, name: str, queries, bboxes=None,
                      width: int = 256, height: int = 256,
                      weight: Optional[str] = None,
                      members: Optional[List[Dict[str, Any]]] = None):
        """M distinct heatmaps — each over its OWN query + grid bbox — in
        one device dispatch, or None when ineligible. ``bboxes`` aligns
        with ``queries`` (None entries use the store bounds, exactly like
        :meth:`density`)."""
        if not queries:
            return []
        if members is not None and len(members) != len(queries):
            raise ValueError("members must align with queries")
        bboxes = list(bboxes) if bboxes is not None \
            else [None] * len(queries)
        if len(bboxes) != len(queries):
            raise ValueError("bboxes must align with queries")
        with tracing.start("density_batch", schema=name,
                           batch=len(queries)), utilization.OP_END, \
                self.serving.admit("density"):
            st, plans, spec = self._batch_plans(name, queries)
            if spec is None:
                return None
            ex = self._executor(st)
            if not hasattr(ex, "density_batch"):
                return None
            default_bbox = None
            boxes = []
            for bb in bboxes:
                if bb is None:
                    if default_bbox is None:
                        default_bbox = (
                            self.bounds(name) or (-180, -90, 180, 90)
                        )
                    bb = default_bbox
                boxes.append(tuple(bb))
            t0 = time.perf_counter()
            with metrics.registry().timer("query.density").time(), \
                    query_deadline(self._timeout_s()):
                grids = ex.density_batch(plans, spec, boxes, width,
                                         height, weight)
            if grids is None:
                return None
            metrics.inc(metrics.SERVING_FUSED_DISTINCT, len(grids))
            self._batch_audit(
                name, "density", plans,
                [int(np.count_nonzero(g)) for g in grids], t0, members,
            )
            return grids

    def stats_batch(self, name: str, stat_spec: str, queries,
                    members: Optional[List[Dict[str, Any]]] = None):
        """M distinct stats scans of one spec in one device dispatch, or
        None when ineligible (descriptive leaves, surviving f32 band
        rows, or a non-batchable template keep query-at-a-time
        execution). The member Stat objects are freshly parsed here and
        discarded on fallback, so a partially-absorbed batch can never
        leak into the serial rerun."""
        if not queries:
            return []
        if members is not None and len(members) != len(queries):
            raise ValueError("members must align with queries")
        with tracing.start("stats_batch", schema=name,
                           batch=len(queries)), utilization.OP_END, \
                self.serving.admit("stats"):
            stats = [parse_stat(stat_spec) for _ in queries]
            st, plans, spec = self._batch_plans(name, queries)
            if spec is None:
                return None
            ex = self._executor(st)
            if not hasattr(ex, "stats_batch"):
                return None
            t0 = time.perf_counter()
            with metrics.registry().timer("query.stats").time(), \
                    query_deadline(self._timeout_s()):
                out = ex.stats_batch(plans, spec, stats)
            if out is None:
                return None
            metrics.inc(metrics.SERVING_FUSED_DISTINCT, len(out))
            self._batch_audit(name, "stats", plans, [0] * len(out), t0,
                              members, extra_hints={"stat": stat_spec})
            return out

    @_traced("stats", speculative="_speculative_stats")
    def stats(self, name: str, stat_spec: str,
              query: "str | Query" = "INCLUDE", region=None) -> sk.Stat:
        """Exact stats over matching features (StatsProcess/StatsScan
        analog). ``region``: optional polygon (WKT or geometry) — see
        :meth:`density`. ``speculative_ok=True`` (kw): under overload, a
        shed stats call returns the coarse write-time-sketch-served
        estimate — typed ``speculative: true`` in the audit —
        instead of failing ``[GM-SHED]`` (docs/SERVING.md)."""
        st, q, plan = self._plan(name, self._with_region(name, query, region))
        parse_stat(stat_spec)  # validate the spec before any timing/scan
        t0 = time.perf_counter()
        with metrics.registry().timer("query.stats").time(), \
                query_deadline(self._timeout_s()):
            out = self.cache.stats(self, st, q, plan, stat_spec)
        self._audit(name, q, plan, t0, 0, op="stats")
        return out

    def _speculative_stats(self, name: str, stat_spec: str,
                           query: "str | Query" = "INCLUDE",
                           region=None) -> sk.Stat:
        """The speculative degraded stats (see :meth:`stats`): served
        from the PERSISTED write-time sketches — host reads, zero device
        work. Leaves with a matching persisted sketch (MinMax of an
        indexed attribute or the dtg field; Count — exact unfiltered,
        planner-estimated otherwise) return its value; other leaves
        return empty. The result shape
        always matches the spec, so typed consumers need no special
        casing — only the audit marker distinguishes it."""
        st, q, plan = self._plan(name, self._with_region(name, query, region))
        stat = parse_stat(stat_spec)
        leaves = stat.stats if isinstance(stat, sk.SeqStat) else [stat]
        served = 0
        for leaf in leaves:
            if isinstance(leaf, sk.CountStat):
                # unfiltered count is exact from the store; a filtered
                # one degrades to the planner estimate
                f = plan.filter
                leaf.count = int(
                    st.count if isinstance(f, ir.Include)
                    else plan.est_count
                )
                served += 1
            elif isinstance(leaf, sk.MinMax):
                mm = st.stats.get(f"minmax-{leaf.attribute}")
                if mm is None and leaf.attribute == st.ft.dtg_field:
                    mm = st.stats.get("time-bounds")
                if isinstance(mm, sk.MinMax) and not mm.is_empty:
                    leaf.merge(mm)
                    served += 1
        self._speculative_audit(name, plan, "stats", 0,
                                {"stat": stat_spec,
                                 "served_leaves": served})
        return stat

    def unique(self, name: str, attribute: str,
               query: "str | Query" = "INCLUDE") -> List:
        """Distinct values (UniqueProcess analog)."""
        st = self._store(name)
        stat = self.stats(name, f"Enumeration({attribute})", query)
        vals = list(stat.value().keys())
        return sorted(vals, key=lambda v: (v is None, v))

    def min_max(self, name: str, attribute: str,
                query: "str | Query" = "INCLUDE", exact: bool = True):
        """MinMaxProcess / GeoMesaStats.getMinMax analog. ``exact=False``
        reads the persisted write-time sketch (no scan)."""
        if not exact:
            st = self._store(name)
            st.flush()
            mm = st.stats.get(f"minmax-{attribute}")
            if isinstance(mm, sk.MinMax) and not mm.is_empty:
                return mm.value()
            # no persisted sketch for this attribute: fall through to exact
        return self.stats(name, f"MinMax({attribute})", query).value()

    # -- stats sketch surface (GeoMesaStats.scala:39-230 parity) -----------
    def histogram(self, name: str, attribute: str, bins: int = 20,
                  bounds: Optional[Tuple[float, float]] = None,
                  query: "str | Query" = "INCLUDE") -> sk.Histogram:
        """Binned histogram (getHistogram). ``bounds`` defaults to the
        attribute's (exact or persisted) min/max."""
        if bounds is None:
            # persisted write-time sketch when available (no extra scan)
            mm = self.min_max(name, attribute, query, exact=False)
            if not mm or mm.get("min") is None:
                raise ValueError(f"no data to bound histogram on {attribute!r}")
            bounds = (float(mm["min"]), float(mm["max"]))
        lo, hi = bounds
        if hi <= lo:
            hi = lo + 1.0
        return self.stats(
            name, f"Histogram({attribute},{bins},{lo},{hi})", query
        )

    def frequency(self, name: str, attribute: str, width: int = 256,
                  query: "str | Query" = "INCLUDE") -> sk.Frequency:
        """Count-min frequency sketch (getFrequency)."""
        return self.stats(name, f"Frequency({attribute},{width})", query)

    def top_k(self, name: str, attribute: str, k: int = 10,
              query: "str | Query" = "INCLUDE") -> List:
        """Top-k values with counts (getTopK)."""
        stat = self.stats(name, f"TopK({attribute},{k})", query)
        return stat.value()

    def z3_histogram(self, name: str) -> Optional[sk.Z3HistogramStat]:
        """The persisted spatio-temporal histogram driving the cost model
        (getZ3Histogram; write-time, no scan)."""
        st = self._store(name)
        st.flush()
        z = st.stats.get("z3-histogram")
        return z if isinstance(z, sk.Z3HistogramStat) and not z.is_empty else None

    @_traced("knn")
    def knn(self, name: str, x: float, y: float, k: int = 10,
            query: "str | Query" = "INCLUDE") -> FeatureCollection:
        """K nearest neighbors via iterative expanding-radius search
        (KNearestNeighborSearchProcess.scala parity): start from a radius
        sized by the store's average point density, constrain the plan with
        that bbox so the z-index windows prune the scan, and double until
        the k-th candidate's exact distance fits inside the searched bbox's
        inscribed circle — an INCLUDE kNN no longer scans the whole table."""
        import math

        from geomesa_tpu.utils.geometry import EARTH_RADIUS_M, haversine_m

        st = self._store(name)
        st.flush()
        q = Query(ecql=query) if isinstance(query, str) else query
        ex = self._executor(st)
        empty = FeatureCollection(st.ft, ColumnBatch({}, 0), st.dicts)
        if st.count == 0 or k <= 0:
            return empty
        geom = st.ft.geom_field
        base = parse_ecql(q.ecql)
        bounds = self.bounds(name) or (-180.0, -90.0, 180.0, 90.0)
        area = max((bounds[2] - bounds[0]) * (bounds[3] - bounds[1]), 1e-9)
        full_span = max(bounds[2] - bounds[0], bounds[3] - bounds[1], 1e-6)
        # initial radius: expect ~4k points of average density inside
        r = max(
            math.sqrt(4.0 * k * area / (math.pi * max(st.count, 1))), 1e-4
        )
        deg_m = math.pi / 180.0 * EARTH_RADIUS_M
        planner = QueryPlanner(st)
        auths = self._effective_auths(q)
        from geomesa_tpu.filter.compile import compile_filter

        base_compiled = compile_filter(base, st.ft, st.dicts)
        batch, order = None, None
        prev_n = -1
        for attempt in range(16):
            # the lon half-width uses the band-EDGE cosine (smallest in the
            # band) so every point within r*deg_m meters falls inside the
            # box; pole-adjacent or extreme-latitude searches skip the
            # restriction (the inscribed-circle argument breaks there), and
            # the last attempt is always unrestricted — the search can
            # never silently return a truncated result
            pole = (y + r >= 89.99) or (y - r <= -89.99)
            cos_edge = math.cos(math.radians(min(abs(y) + r, 89.99)))
            restricted = (
                r < full_span and not pole and cos_edge >= 0.05
                and attempt < 15
            )
            if restricted:
                half_lon = r / cos_edge
                lat_lo, lat_hi = max(y - r, -90.0), min(y + r, 90.0)
                lon_lo, lon_hi = x - half_lon, x + half_lon
                if lon_hi - lon_lo >= 360.0:
                    boxes = [(-180.0, lat_lo, 180.0, lat_hi)]
                elif lon_lo < -180.0:  # antimeridian wrap (west)
                    boxes = [(-180.0, lat_lo, lon_hi, lat_hi),
                             (lon_lo + 360.0, lat_lo, 180.0, lat_hi)]
                elif lon_hi > 180.0:  # antimeridian wrap (east)
                    boxes = [(lon_lo, lat_lo, 180.0, lat_hi),
                             (-180.0, lat_lo, lon_hi - 360.0, lat_hi)]
                else:
                    boxes = [(lon_lo, lat_lo, lon_hi, lat_hi)]
                bb = tuple(ir.BBox(geom, *b) for b in boxes)
                f = ir.And((base, bb[0] if len(bb) == 1 else ir.Or(bb)))
            else:
                boxes = None
                f = base
            plan = planner.plan(f, q.hints())
            if restricted:
                # the restriction prunes via the plan's WINDOWS and via
                # traced box scalars inside the kNN aggregation — the
                # compiled predicate stays location-free, so one jitted
                # kernel serves every location and radius (a baked-in box
                # with a location-blind cache token returned stale-box
                # results — r4 review)
                plan.compiled = base_compiled
            plan.__dict__["cache_token"] = (
                "knn", q.ecql, None if auths is None else tuple(auths),
            )
            plan.__dict__["window_token"] = (
                plan.__dict__["cache_token"],
                round(x, 9), round(y, 9), restricted and round(r, 9),
            )
            self._apply_visibility(st, plan, auths)
            if hasattr(ex, "knn_features"):  # partitioned: per-partition top-k
                batch = ex.knn_features(plan, x, y, k, boxes=boxes)
            else:
                idx, _ = ex.knn(plan, x, y, k, boxes=boxes)
                table = st.tables[plan.index_name]
                batch = table.host_gather_positions(np.sort(idx))
            order = np.zeros(0, np.int64)
            kth_m = math.inf
            if batch.n:
                d = haversine_m(
                    batch.columns[geom + "__x"], batch.columns[geom + "__y"],
                    x, y,
                )
                order = np.argsort(d)[:k]
                kth_m = float(d[order[-1]])
            if not restricted:
                break
            # exact iff the k-th neighbor lies inside the searched bbox's
            # inscribed circle (domain-clamped edges hold no points beyond
            # the lon/lat domain, so clamping never loses candidates)
            if len(order) >= k and kth_m <= r * deg_m:
                break
            if batch.n == prev_n and batch.n < k:
                # a doubling added no candidates and we're still short of
                # k: the base filter is the limiting factor, not the box —
                # jump straight to the unrestricted pass
                r = full_span
            else:
                r *= 2.0
            prev_n = batch.n
        batch = ColumnBatch(
            {kk: v[order] for kk, v in batch.columns.items()}, len(order)
        )
        return FeatureCollection(st.ft, batch, st.dicts)

    def proximity(self, name: str, wkt_or_geom, distance_m: float,
                  query: "str | Query" = "INCLUDE") -> FeatureCollection:
        """ProximitySearchProcess analog: features within distance of a geometry."""
        from geomesa_tpu.utils import geometry as geo

        g = (
            geo.parse_wkt(wkt_or_geom) if isinstance(wkt_or_geom, str) else wkt_or_geom
        )
        st = self._store(name)
        base = query.ecql if isinstance(query, Query) else query
        f = ir.And((
            parse_ecql(base),
            ir.DWithin(st.ft.geom_field, g, distance_m),
        ))
        planner = QueryPlanner(st)
        st.flush()
        q = query if isinstance(query, Query) else Query()
        plan = planner.plan(f, q.hints())
        self._apply_visibility(st, plan, self._effective_auths(q))
        batch = self._executor(st).features(plan)
        return FeatureCollection(st.ft, batch, st.dicts)

    # -- process library delegates (geomesa-process parity) ----------------
    def tube_select(self, name: str, tube_xy, tube_times_ms, buffer_m: float,
                    query: "str | Query" = "INCLUDE", **kw) -> FeatureCollection:
        from geomesa_tpu import processes

        return processes.tube_select(
            self, name, tube_xy, tube_times_ms, buffer_m, query, **kw
        )

    def spatial_join(self, points: str, polygons,
                     query: "str | Query" = "INCLUDE",
                     weight: Optional[str] = None):
        from geomesa_tpu import processes

        return processes.spatial_join(self, points, polygons, query, weight)

    def join(self, left: str, right: str, left_attr: Optional[str] = None,
             right_attr: Optional[str] = None,
             left_query: "str | Query" = "INCLUDE",
             right_query: "str | Query" = "INCLUDE", *,
             predicate: Optional[str] = None, distance=None,
             dx=None, dy=None, level: Optional[int] = None):
        """Join two schemas. With ``left_attr``/``right_attr``: the
        attribute equi-join (JoinProcess analog, unchanged). With
        ``predicate``: the TPU-native SPATIAL join between two
        point-schema datasets (docs/JOIN.md) — ``"bbox"`` (envelopes of
        half-widths ``dx``/``dy`` intersect), ``"dwithin"`` (planar
        degree ``distance``), or ``"dwithin_meters"`` (haversine
        great-circle ``distance`` meters) — SFC-cell co-partitioned so
        candidate work is O(pairs-in-same-cell), returning a streaming
        :class:`SpatialJoinResult`."""
        if predicate is None:
            if left_attr is None or right_attr is None:
                raise ValueError(
                    "join needs left_attr/right_attr (equi-join) or "
                    "predicate= (spatial join)"
                )
            from geomesa_tpu import processes

            return processes.join(
                self, left, right, left_attr, right_attr,
                left_query, right_query,
            )
        return self.join_spatial(
            left, right, predicate=predicate, distance=distance, dx=dx,
            dy=dy, left_query=left_query, right_query=right_query,
            level=level,
        )

    def _join_sides(self, left: str, right: str,
                    left_query: "str | Query", right_query: "str | Query",
                    right_polygon: bool = False):
        """Plan + scan both join sides (each under its own filter /
        visibility), validating the geometry contract: both sides POINT,
        except polygon-predicate joins (``right_polygon``) where the
        right side must be a POLYGON/MULTIPOLYGON schema."""
        lst, lq, lplan = self._plan(left, left_query)
        rst, rq, rplan = self._plan(right, right_query)
        for st_, nm, poly in ((lst, left, False), (rst, right,
                                                   right_polygon)):
            g = st_.ft.geom_field
            a = None if g is None else st_.ft.attr(g)
            if poly:
                if a is None or a.type not in ("polygon", "multipolygon"):
                    raise ValueError(
                        f"[GM-ARG] polygon join requires a POLYGON "
                        f"geometry on schema {nm!r}"
                    )
            elif a is None or not a.is_point:
                raise ValueError(
                    f"[GM-ARG] spatial join requires a POINT geometry "
                    f"on schema {nm!r}"
                )
        with tracing.span("scan.join.sides"):
            lbatch = self._executor(lst).features(lplan)
            rbatch = self._executor(rst).features(rplan)
        return lst, lplan, lbatch, rst, rplan, rbatch

    @staticmethod
    def _side_xy(st: FeatureStore, batch: ColumnBatch):
        g = st.ft.geom_field
        z = np.zeros(0, np.float64)
        return (batch.columns.get(g + "__x", z),
                batch.columns.get(g + "__y", z))

    @staticmethod
    def _side_polygons(st: FeatureStore, batch: ColumnBatch):
        """The polygon side's geometries, parsed from the schema's host
        WKT column (row order == batch order, so pair indices line up)."""
        from geomesa_tpu.utils import geometry as geo

        g = st.ft.geom_field
        col = batch.columns.get(g + "__wkt")
        if col is None:
            return []
        return [geo.parse_wkt(w) for w in col]

    def _join_run(self, left: str, right: str, predicate: str, distance,
                  dx, dy, left_query, right_query, level,
                  want_pairs: bool):
        """The shared spatial-join body: sides scan -> co-partition ->
        per-cell strategy routing -> kernels over the device mesh ->
        audit. Polygon predicates route through the classify-cells
        wholesale/boundary engine; count-only joins over a partitioned
        right side stream it through window-pushdown side scans
        (docs/JOIN.md §6) instead of materializing it whole."""
        from geomesa_tpu.kernels import join as kjoin
        from geomesa_tpu.planning import join_exec

        t0 = time.perf_counter()
        metrics.inc(metrics.JOIN_QUERIES)
        prefer = self.prefer_device and self.mesh is None
        with query_deadline(self._timeout_s()):
            if predicate in kjoin.POLYGON_PREDICATES:
                lst, lplan, lbatch, rst, rplan, rbatch = self._join_sides(
                    left, right, left_query, right_query,
                    right_polygon=True,
                )
                lx, ly = self._side_xy(lst, lbatch)
                geoms = self._side_polygons(rst, rbatch)
                pairs, total, stats = join_exec.run_polygon_join(
                    lx, ly, geoms, predicate, level=level,
                    prefer_device=prefer, want_pairs=want_pairs,
                )
            elif not want_pairs and self._join_pushdown_ready(
                    right, predicate, right_query):
                (lst, lplan, lbatch, rst,
                 total, stats) = self._join_pushdown_count(
                    left, right, predicate, distance, dx, dy,
                    left_query, right_query, level, prefer,
                )
                rbatch = ColumnBatch({}, 0)
                pairs = None
            else:
                lst, lplan, lbatch, rst, rplan, rbatch = self._join_sides(
                    left, right, left_query, right_query
                )
                lx, ly = self._side_xy(lst, lbatch)
                rx, ry = self._side_xy(rst, rbatch)
                pairs, total, stats = join_exec.run_join(
                    lx, ly, rx, ry, predicate, distance=distance, dx=dx,
                    dy=dy, level=level, prefer_device=prefer,
                    want_pairs=want_pairs,
                )
        hints = {
            "op": "join", "index": lplan.index_name, "right": right,
            "predicate": predicate, "level": stats.level,
            "cells_joint": stats.cells_joint,
            "candidate_pairs": stats.candidate_pairs,
            "naive_pairs": stats.naive_pairs,
            "strip_fraction": round(stats.strip_fraction, 4),
            "adaptive": stats.adaptive,
        }
        if stats.strategy_cells:
            # the decision trail: joint cells per strategy (docs/JOIN.md §5)
            hints["strategies"] = dict(stats.strategy_cells)
        if stats.wholesale_pairs:
            hints["wholesale_pairs"] = stats.wholesale_pairs
        if stats.pushdown:
            hints["pushdown"] = dict(stats.pushdown)
        if stats.skipped:
            hints["degraded"] = list(stats.skipped)
        tid = tracing.current_trace_id()
        if tid is not None:
            hints["trace_id"] = tid
        hints.update(self._plan_audit_extras(lplan))
        self.audit.record(
            left, lplan.ecql, hints,
            lplan.__dict__.get("plan_time_ms", 0.0),
            (time.perf_counter() - t0) * 1e3, total,
            user=self.serving.current_user() or "",
            scanned=lplan.__dict__.get("scanned_rows", 0),
            table_rows=lplan.__dict__.get("table_rows", 0),
        )
        return SpatialJoinResult(
            lst, lbatch, rst, rbatch, pairs, total, stats
        )

    def _join_pushdown_ready(self, right: str, predicate: str,
                             right_query: "str | Query") -> bool:
        """Whether the count-only join can stream the right side through
        lake window-pushdown side scans (docs/JOIN.md §6): planar
        predicate (``dwithin_meters`` needs per-row latitude-dependent
        reach plus antimeridian wrap — its windows are not OR-of-bbox),
        a plain right query (row-set-dependent hints fall back), and a
        partitioned right store that can serve statistics-pruned
        children."""
        from geomesa_tpu.kernels import join as kjoin

        if predicate not in (kjoin.JOIN_BBOX, kjoin.JOIN_DWITHIN):
            return False
        on = config.JOIN_PUSHDOWN.to_bool()
        if not (True if on is None else bool(on)):
            return False
        if isinstance(right_query, Query) and (
                right_query.max_features is not None
                or right_query.sampling is not None
                or right_query.sample_by is not None
                or right_query.sort_by or right_query.properties):
            return False
        try:
            st = self._store(right)
        except KeyError:
            return False
        from geomesa_tpu.index.partitioned import PartitionedFeatureStore

        g = st.ft.geom_field
        return (isinstance(st, PartitionedFeatureStore)
                and g is not None and st.ft.attr(g).is_point)

    def _join_pushdown_count(self, left: str, right: str, predicate: str,
                             distance, dx, dy, left_query, right_query,
                             level, prefer: bool):
        """Count-only join with window-pushdown side scans: the LEFT
        side's occupied cells chunk into groups of
        ``geomesa.join.pushdown.cells``; each chunk re-plans the right
        side under ``(right_query) AND (OR of chunk cell boxes inflated
        by reach + 2 margins)`` and streams it through the partitioned
        executor's lake window (footer-pruned per-cell ranged reads) —
        the right side is never materialized whole on the host.

        Exactly-once accounting: a left row's cell lives in exactly one
        chunk, and any right row whose reach box touches a chunk cell
        lies inside that chunk's inflated window with a full
        CLASSIFY_MARGIN to spare (one margin funds the strip contract,
        the second funds the scan filter kernel's f32 edge uncertainty,
        and the window bounds round OUTWARD to fixed-point ECQL), so
        chunk counts partition the pair set."""
        from dataclasses import replace as _dc_replace

        from geomesa_tpu.cache import cells as gcells
        from geomesa_tpu.cache.cells import CLASSIFY_MARGIN
        from geomesa_tpu.kernels import join as kjoin
        from geomesa_tpu.planning import join_exec

        lst, lq, lplan = self._plan(left, left_query)
        g = lst.ft.geom_field
        if g is None or not lst.ft.attr(g).is_point:
            raise ValueError(
                f"[GM-ARG] spatial join requires a POINT geometry "
                f"on schema {left!r}"
            )
        rst = self._store(right)
        rgeom = rst.ft.geom_field
        with tracing.span("scan.join.sides"):
            lbatch = self._executor(lst).features(lplan)
        lx, ly = self._side_xy(lst, lbatch)
        lx = np.asarray(lx, np.float64)
        ly = np.asarray(ly, np.float64)
        p0, p1 = kjoin.pair_params(predicate, distance=distance, dx=dx,
                                   dy=dy)
        if predicate == kjoin.JOIN_BBOX:
            reach_x, reach_y = float(p0), float(p1)
        else:
            reach_x = reach_y = float(distance)
        if level is None:
            # level votes from the LEFT side only — the right side is
            # never whole on the host, so its density cannot vote
            bounds = None
            if len(lx):
                bounds = (float(lx.min()), float(ly.min()),
                          float(lx.max()), float(ly.max()))
            level = join_exec.choose_level(
                len(lx), len(lx), max(reach_x, reach_y), bounds
            )
        stats = join_exec.JoinStats(level=level, n_left=len(lx))
        if not len(lx):
            return lst, lplan, lbatch, rst, 0, stats
        # the WINDOW grid is finer than the join grid: the join level
        # optimizes pairwise tile occupancy (cells can span many
        # degrees), but pruning power needs boxes comparable to a row
        # group's footprint — size window cells to the reach (the pad is
        # then a fraction of the cell, not a multiple). Exactness never
        # depends on this choice: each chunk's inflated windows are a
        # provable superset of its left rows' matches at ANY level.
        wlevel = int(np.clip(int(np.floor(np.log2(
            360.0 / max(2.0 * (max(reach_x, reach_y) + CLASSIFY_MARGIN),
                        1e-9)))), level, 15))
        ix, iy = gcells.point_cells(lx, ly, wlevel)
        cell = join_exec._cell_ids(ix, iy)
        order = np.argsort(cell, kind="stable")
        ucell, starts = np.unique(cell[order], return_index=True)
        ends = np.concatenate([starts[1:], [len(order)]])
        uix = ix[order][starts]
        uiy = iy[order][starts]
        stats.cells_left = len(ucell)
        per = config.JOIN_PUSHDOWN_CELLS.to_int() or 256
        per = max(int(per), 1)
        base = right_query.ecql if isinstance(right_query, Query) \
            else right_query
        rq_base = right_query if isinstance(right_query, Query) \
            else Query(ecql=right_query)
        pad_x = reach_x + 2.0 * CLASSIFY_MARGIN
        pad_y = reach_y + 2.0 * CLASSIFY_MARGIN

        def _lo(v):
            return f"{np.floor(v * 1e9) / 1e9:.9f}"

        def _hi(v):
            return f"{np.ceil(v * 1e9) / 1e9:.9f}"

        total = 0
        bytes_loaded = groups_loaded = 0
        bytes_side = groups_side = 0
        chunks = 0
        # one residency cache spans the whole chunk loop: adjacent chunks'
        # reach-inflated windows overlap, so boundary row groups surviving
        # pruning in both chunks decode once (docs/JOIN.md §11)
        from geomesa_tpu.lake.residency import GroupResidencyCache

        residency = GroupResidencyCache.from_config()
        for clo in range(0, len(ucell), per):
            chi = min(clo + per, len(ucell))
            chunks += 1
            boxes = gcells.cell_boxes(wlevel, uix[clo:chi], uiy[clo:chi])
            clause = " OR ".join(
                f"BBOX({rgeom}, {_lo(b[0] - pad_x)}, {_lo(b[1] - pad_y)},"
                f" {_hi(b[2] + pad_x)}, {_hi(b[3] + pad_y)})"
                for b in boxes
            )
            ecql = clause if base.strip().upper() == "INCLUDE" \
                else f"({base}) AND ({clause})"
            rst2, _rq2, rplan2 = self._plan(
                right, _dc_replace(rq_base, ecql=ecql)
            )
            if residency is not None:
                rplan2.__dict__["residency"] = residency
            ex = self._executor(rst2)
            scan = getattr(ex, "features_pushdown", None) or ex.features
            with tracing.span("scan.join.side.window", chunk=chunks):
                rb = scan(rplan2)
            rx, ry = self._side_xy(rst2, rb)
            stats.n_right += len(rx)
            sel = order[starts[clo]: ends[chi - 1]]
            plan = join_exec.co_partition(
                lx[sel], ly[sel], rx, ry, predicate, reach_x, reach_y,
                level=level, p0=p0, p1=p1,
            )
            _, cnt = join_exec.execute_predicate(
                plan, lx[sel], ly[sel], rx, ry, predicate,
                prefer_device=prefer, want_pairs=False,
            )
            total += cnt
            cst = plan.stats
            stats.cells_joint += cst.cells_joint
            stats.candidate_pairs += cst.candidate_pairs
            stats.strip_entries += cst.strip_entries
            stats.tiles += cst.tiles
            stats.devices = max(stats.devices, cst.devices)
            stats.adaptive = cst.adaptive
            for k, v in cst.strategy_cells.items():
                stats.strategy_cells[k] = stats.strategy_cells.get(k, 0) + v
            for k, v in cst.est_pairs.items():
                stats.est_pairs[k] = stats.est_pairs.get(k, 0) + v
            for k, v in cst.dispatched_pairs.items():
                stats.dispatched_pairs[k] = \
                    stats.dispatched_pairs.get(k, 0) + v
            stats.skipped.extend(
                f"chunk{chunks - 1}:{s}" for s in cst.skipped
            )
            acct = rplan2.__dict__.get("lake_acct") or {}
            bytes_loaded += int(acct.get("bytes_loaded", 0))
            groups_loaded += int(acct.get("groups_loaded", 0))
            # one chunk's payload/groups_total IS the whole side (every
            # chunk scan sees every row group's footer): the honest
            # full-materialization baseline for the fraction
            bytes_side = max(bytes_side, int(acct.get("bytes_payload", 0)))
            groups_side = max(groups_side, int(acct.get("groups_total", 0)))
        stats.matched = total
        res_hits = residency.hits if residency is not None else 0
        res_saved = residency.bytes_saved if residency is not None else 0
        stats.pushdown = {
            "chunks": chunks, "cells": len(ucell),
            "bytes_loaded": bytes_loaded, "bytes_side": bytes_side,
            "groups_loaded": groups_loaded, "groups_side": groups_side,
            "residency_hits": res_hits,
            "bytes_saved_residency": res_saved,
        }
        metrics.inc(metrics.JOIN_PUSHDOWN_RESIDENCY_HITS, res_hits)
        metrics.inc(metrics.JOIN_PUSHDOWN_RESIDENCY_BYTES, res_saved)
        metrics.inc(metrics.JOIN_CELLS, stats.cells_joint)
        metrics.inc(metrics.JOIN_CANDIDATE_PAIRS, stats.candidate_pairs)
        for s, k in stats.strategy_cells.items():
            metrics.inc(metrics.JOIN_CELLS_STRATEGY + s, k)
        metrics.inc(metrics.JOIN_PAIRS, total)
        metrics.inc(metrics.JOIN_PUSHDOWN_BYTES, bytes_loaded)
        tracing.add_cost("join_pushdown_bytes", float(bytes_loaded))
        tracing.add_cost("join_cells", float(stats.cells_joint))
        tracing.add_cost("join_candidate_pairs",
                         float(stats.candidate_pairs))
        return lst, lplan, lbatch, rst, total, stats

    @_traced("join")
    def join_spatial(self, left: str, right: str, *, predicate: str,
                     distance=None, dx=None, dy=None,
                     left_query: "str | Query" = "INCLUDE",
                     right_query: "str | Query" = "INCLUDE",
                     level: Optional[int] = None) -> "SpatialJoinResult":
        """Spatial join of two point schemas (docs/JOIN.md): matched
        pairs stream as ColumnBatches (``SpatialJoinResult.batches()``,
        right columns prefixed ``right.``). Runs through serving
        admission / deadlines like every public op; under
        ``resilience.allow_partial()`` per-tile-slice failures degrade
        with exact survivor totals (``result.stats.skipped``)."""
        return self._join_run(left, right, predicate, distance, dx, dy,
                              left_query, right_query, level,
                              want_pairs=True)

    @_traced("join")
    def join_count(self, left: str, right: str, *, predicate: str,
                   distance=None, dx=None, dy=None,
                   left_query: "str | Query" = "INCLUDE",
                   right_query: "str | Query" = "INCLUDE",
                   level: Optional[int] = None) -> int:
        """The join's aggregate form: exact matched-pair count without
        materializing pairs (the [C, B, P] verdict mask never leaves the
        device — only per-tile counts transfer). Slots into the serving
        batch/fusion path as a repeat-fusable op (docs/SERVING.md)."""
        res = self._join_run(left, right, predicate, distance, dx, dy,
                             left_query, right_query, level,
                             want_pairs=False)
        return res.count

    def explain_join(self, left: str, right: str, *, predicate: str,
                     distance=None, dx=None, dy=None,
                     left_query: "str | Query" = "INCLUDE",
                     right_query: "str | Query" = "INCLUDE",
                     level: Optional[int] = None,
                     analyze: bool = False) -> str:
        """Join plan explain (docs/JOIN.md): the co-partition's pruning
        account — cells, candidate pairs vs naive N*M, boundary-strip
        fraction — plus (``analyze=True``) the executed match count."""
        from geomesa_tpu.kernels import join as kjoin
        from geomesa_tpu.planning import join_exec

        exp = Explainer(enabled=True)
        with tracing.start("explain_join", schema=left), \
                self.serving.admit("explain"):
            if predicate in kjoin.POLYGON_PREDICATES:
                lst, lplan, lbatch, rst, rplan, rbatch = self._join_sides(
                    left, right, left_query, right_query,
                    right_polygon=True,
                )
                lx, ly = self._side_xy(lst, lbatch)
                geoms = self._side_polygons(rst, rbatch)
                t0 = time.perf_counter()
                _, total, st = join_exec.run_polygon_join(
                    lx, ly, geoms, predicate, level=level,
                    prefer_device=analyze and self.prefer_device
                    and self.mesh is None,
                    want_pairs=False,
                )
                exp.push("Join")
                exp.kv("predicate", predicate)
                exp.kv("sides", f"{left} ({st.n_left} rows) x "
                       f"{right} ({st.n_right} polygons)")
                exp.kv("cell level", st.level)
                exp.kv("cells", f"{st.cells_left} occupied point cells")
                exp.pop()
                exp.push("Adaptive")
                exp.kv("cells[interior]",
                       f"{st.strategy_cells.get('interior', 0)} "
                       f"(wholesale: {st.wholesale_pairs} pairs, zero "
                       f"kernel work)")
                exp.kv("cells[boundary]",
                       f"{st.strategy_cells.get('boundary', 0)} "
                       f"(kernel: {st.candidate_pairs} candidate pairs)")
                exp.kv("statistics read",
                       "classify_cells(cell box, polygon, "
                       "CLASSIFY_MARGIN) per candidate cell")
                if analyze:
                    exp.kv("matched (analyze)", total)
                    exp.kv("kernel ms",
                           round((time.perf_counter() - t0) * 1e3, 3))
                    if st.skipped:
                        exp.kv("degraded", ", ".join(st.skipped))
                exp.pop()
                return str(exp)
            lst, lplan, lbatch, rst, rplan, rbatch = self._join_sides(
                left, right, left_query, right_query
            )
            lx, ly = self._side_xy(lst, lbatch)
            rx, ry = self._side_xy(rst, rbatch)
            p0, p1 = kjoin.pair_params(predicate, distance=distance,
                                       dx=dx, dy=dy)
            wrap_x = False
            if predicate == kjoin.JOIN_BBOX:
                reach_x, reach_y = float(p0), float(p1)
            elif predicate == kjoin.JOIN_DWITHIN_METERS:
                reach_x, reach_y = join_exec.meters_reach_deg(
                    float(distance), ry
                )
                wrap_x = True
            else:
                reach_x = reach_y = float(distance)
            plan = join_exec.co_partition(
                lx, ly, rx, ry, predicate, reach_x, reach_y, level=level,
                p0=p0, p1=p1, wrap_x=wrap_x,
            )
            st = plan.stats
            exp.push("Join")
            exp.kv("predicate", predicate)
            exp.kv("sides", f"{left} ({st.n_left} rows) x "
                   f"{right} ({st.n_right} rows)")
            exp.kv("co-partition level", st.level)
            exp.kv("cells", f"{st.cells_left} build, {st.cells_right} "
                   f"probe, {st.cells_joint} joint (dispatched)")
            exp.kv("candidate pairs",
                   f"{st.candidate_pairs} of {st.naive_pairs} naive "
                   f"({st.candidate_fraction:.4f})")
            exp.kv("boundary-strip fraction",
                   round(st.strip_fraction, 4))
            exp.kv("tiles", f"{st.tiles} ({plan.Bp} x {plan.Pp} padded, "
                   f"{len(plan.sections)} section(s))")
            exp.pop()
            # the adaptive decision trail (docs/JOIN.md §5): what each
            # joint cell's routing read and what it chose
            exp.push("Adaptive")
            exp.kv("enabled", str(bool(st.adaptive)).lower())
            for strat in ("pairwise", "brute", "split.l", "split.r"):
                if strat not in st.strategy_cells:
                    continue
                exp.kv(f"cells[{strat}]",
                       f"{st.strategy_cells[strat]} "
                       f"(est {st.est_pairs.get(strat, 0)} pairs, "
                       f"dispatched {st.dispatched_pairs.get(strat, 0)} "
                       f"slots)")
            exp.kv("statistics read",
                   "per-cell (n_build, n_probe); thresholds: brute <= "
                   f"{config.JOIN_ADAPTIVE_BRUTE_PAIRS.to_int() or 256} "
                   "pairs, skew >= "
                   f"{config.JOIN_ADAPTIVE_SKEW_RATIO.to_int() or 8}:1 "
                   "over tile")
            if analyze:
                t0 = time.perf_counter()
                _, total = join_exec.execute_predicate(
                    plan, lx, ly, rx, ry, predicate,
                    prefer_device=self.prefer_device and self.mesh is None,
                    want_pairs=False,
                )
                exp.kv("matched (analyze)", total)
                exp.kv("pairwise ms",
                       round((time.perf_counter() - t0) * 1e3, 3))
                if st.skipped:
                    exp.kv("degraded", ", ".join(st.skipped))
            exp.pop()
        return str(exp)

    def sample(self, name: str, one_in_n: int,
               query: "str | Query" = "INCLUDE") -> FeatureCollection:
        from geomesa_tpu import processes

        return processes.sample(self, name, one_in_n, query)

    def point2point(self, name: str, group_by: str,
                    query: "str | Query" = "INCLUDE", break_on_day=False):
        from geomesa_tpu import processes

        return processes.point2point(self, name, group_by, query, break_on_day)

    def track_label(self, name: str, track_attr: str,
                    query: "str | Query" = "INCLUDE") -> FeatureCollection:
        from geomesa_tpu import processes

        return processes.track_label(self, name, track_attr, query)

    def route_search(self, name: str, route, buffer_m: float,
                     query: "str | Query" = "INCLUDE", **kw) -> FeatureCollection:
        from geomesa_tpu import processes

        return processes.route_search(self, name, route, buffer_m, query, **kw)

    def export_bin(self, name: str, query: "str | Query" = "INCLUDE",
                   track: Optional[str] = None, label: Optional[str] = None,
                   sort: bool = True) -> bytes:
        """Query results as packed BIN records (BinAggregatingScan /
        BinConversionProcess analog): 16 bytes/record, 24 with a label."""
        from geomesa_tpu.io import bin_format

        fc = self.query(name, query)
        st = self._store(name)
        if fc.batch.n == 0:
            return b""
        return bin_format.pack_batch(st.ft, fc.batch, st.dicts, track, label, sort)

    # -- Arrow interchange (geomesa-arrow / ArrowScan analog) --------------
    def to_arrow(self, name: str, query: "str | Query" = "INCLUDE",
                 properties=None):
        """Query results as an Arrow table (dictionary-encoded strings)."""
        import pyarrow as pa

        from geomesa_tpu.io import arrow_io

        if isinstance(query, str):
            q = Query(ecql=query)
        else:
            import dataclasses

            q = dataclasses.replace(query)
        if properties is not None:
            q.properties = list(properties)
        fc = self.query(name, q)
        st = self._store(name)
        if fc.batch.n == 0:
            # schema of the empty table must match non-empty results: a
            # non-point geometry is utf8 WKT iff the store carries __wkt
            return arrow_io.arrow_schema(
                st.ft, q.properties, st.wkt_geoms()
            ).empty_table()
        rb = arrow_io.batch_to_arrow(st.ft, fc.batch, st.dicts, q.properties)
        return pa.Table.from_batches([rb])

    def export_arrow(self, name: str, path: str,
                     query: "str | Query" = "INCLUDE", properties=None):
        """Write query results to an Arrow IPC file."""
        from geomesa_tpu.io import arrow_io

        table = self.to_arrow(name, query, properties)
        arrow_io.write_ipc(path, table.to_batches(), table.schema)

    def ingest_arrow(self, name: str, source) -> int:
        """Ingest an Arrow table / record batch / IPC file path."""
        import pyarrow as pa

        from geomesa_tpu.io import arrow_io

        if isinstance(source, str):
            source = arrow_io.read_ipc(source)
        st = self._store(name)
        data, fids = arrow_io.table_to_data(st.ft, source)
        return self.insert(name, data, fids)

    # -- persistence (shard-manifest checkpoint, SURVEY.md §5) -------------
    def _save_flat_chunks(self, path: str, name: str, st,
                          prev_entry: Optional[dict]) -> dict:
        """Incremental flat-store checkpoint (TableBasedMetadata
        incrementality analog): the master batch is append-only between
        non-append mutations (tracked by ``mutation_epoch``), so a
        re-save after appends writes ONE new chunk covering the fresh
        rows and leaves every existing chunk file untouched. Deletes /
        column adds change the epoch and force a full rewrite."""
        n = st._all.n if st._all is not None else 0
        prev = prev_entry.get("chunks") if prev_entry else None
        incremental = (
            prev is not None
            and prev_entry.get("epoch") == st.mutation_epoch
            and prev_entry.get("rows", -1) <= n
            and all(os.path.exists(os.path.join(path, f)) for f in prev)
        )
        if not incremental:
            chunks, lo = [], 0
        else:
            chunks, lo = list(prev), int(prev_entry["rows"])
        cdir_rel = f"{name}_chunks"
        os.makedirs(os.path.join(path, cdir_rel), exist_ok=True)
        if n > lo:
            # uuid-suffixed chunk name: a full rewrite NEVER overwrites a
            # chunk the previous (still-live) manifest references — every
            # old file stays untouched until the new manifest is durably
            # published, so a crash at any point mid-save leaves the old
            # checkpoint + its files fully consistent (and the journal
            # still holds everything past it). save() sweeps the
            # unreferenced files after the manifest replace; legacy v1
            # ``{name}.npz`` files sweep the same way.
            fname = (f"{cdir_rel}/chunk-{len(chunks):05d}-{lo}-{n}"
                     f"-{uuid.uuid4().hex[:8]}.npz")
            resilience.fault_point("fs.save.chunk", schema=name, file=fname)
            cols = {
                k: (v[lo:n].astype("U") if v.dtype.kind == "O"
                    else v[lo:n])
                for k, v in st._all.columns.items()
            }
            with open(os.path.join(path, fname), "wb") as fh:
                np.savez_compressed(fh, **cols)
                fh.flush()
                os.fsync(fh.fileno())
            resilience.fsync_dir(os.path.join(path, cdir_rel))
            chunks.append(fname)
        return {"chunks": chunks, "rows": n, "epoch": st.mutation_epoch}

    # -- aggregate-cache persistence (docs/CACHE.md, docs/LAKE.md) ---------
    def persist_cache(self, path: str) -> Dict[str, Any]:
        """Write the aggregate cache's warm entries (flat cells,
        hierarchy nodes, curve chunks, whole results) to one lake-tier
        file, so a restarted process can :meth:`restore_cache` them and
        answer warm zoom-outs with zero device dispatches. Entries are
        only persisted while their epoch matches the store (a snapshot in
        time); returns a per-schema entry-count summary."""
        from geomesa_tpu.lake import persist as lake_persist

        return lake_persist.save_cache(self, path)

    def restore_cache(self, path: str) -> Dict[str, Any]:
        """Re-admit persisted cache entries for every schema whose data
        still matches the persisted guard (row count + spec) — typically
        right after :meth:`load` of the checkpoint the cache was warmed
        against. Imports ride the normal LRU budget and the store's
        CURRENT epoch, so later mutations invalidate as usual."""
        from geomesa_tpu.lake import persist as lake_persist

        return lake_persist.restore_cache(self, path)

    def save(self, path: str, names: Optional[Sequence[str]] = None):
        """Checkpoint to ``path``. ``names`` restricts the save to those
        schemas — other schemas' manifest entries (and files) carry over
        VERBATIM from the existing checkpoint, so a fleet write commit
        (docs/RESILIENCE.md §7) costs the mutated schema, not the whole
        dataset. A named schema that no longer exists locally is REMOVED
        from the manifest (the delete path).

        With the journal attached (docs/RESILIENCE.md §8), save is the
        CHECKPOINT, not the commit: each saved schema's entry is stamped
        with the journal position it captures (``journal_seq``), the
        manifest publishes durably (tmp + fsync + rename + dir fsync),
        and journal segments every schema has checkpointed past are
        truncated. Attachment stays explicit (attach_journal / load) —
        saving to a scratch path must not bind this dataset's
        durability to it."""
        from geomesa_tpu.index.partitioned import PartitionedFeatureStore

        os.makedirs(path, exist_ok=True)
        prev_manifest = {}
        mpath = os.path.join(path, "manifest.json")
        if os.path.exists(mpath):
            with open(mpath) as fh:
                prev_manifest = json.load(fh).get("schemas", {})
        j = self._journal
        if j is not None and os.path.abspath(j.root) != os.path.abspath(path):
            j = None  # saving elsewhere must not stamp/truncate OUR journal
        jpos = j.last_seq() if j is not None else None
        manifest = {"version": 2, "schemas": {}}
        if names is not None:
            keep = set(names)
            manifest["schemas"] = {
                k: v for k, v in prev_manifest.items() if k not in keep
            }
        for name, st in self._stores.items():
            if names is not None and name not in names:
                continue
            st.flush()
            entry = {
                "spec": st.ft.spec(),
                "n_shards": st.n_shards,
                "dicts": {k: d.to_list() for k, d in st.dicts.items()},
                "stats": {k: v.to_json() for k, v in st.stats.items()},
            }
            if jpos is not None:
                entry["journal_seq"] = jpos
            if self.standing is not None:
                # standing subscriptions checkpoint WITH the schema: the
                # save truncates their journal records, so the manifest
                # must carry them for load to re-register
                # (docs/STANDING.md §7)
                standing = self.standing.subscriptions(name)
                if standing:
                    entry["standing"] = standing
            if isinstance(st, PartitionedFeatureStore):
                # incremental: only dirty partitions rewrite their snapshot
                parts = st.checkpoint_into(os.path.join(path, f"{name}_parts"))
                entry["partitions"] = {
                    str(b): os.path.relpath(d, path) for b, d in parts.items()
                }
            else:
                entry.update(self._save_flat_chunks(
                    path, name, st, prev_manifest.get(name)))
            manifest["schemas"][name] = entry
            # our own checkpoint moved the entry; record it so the next
            # refresh_schema against this root stays incremental
            self._ckpt_fp[name] = self._entry_fp(entry)
        resilience.fault_point("fs.save.manifest", path=mpath)
        resilience.durable_write_json(mpath, manifest, indent=2)
        self._sweep_orphan_chunks(path, manifest["schemas"], names)
        if j is not None:
            # truncate segments every schema in the (new) manifest has
            # checkpointed past; a carried-over entry without a stamp pins
            # the whole journal (safe: replay is idempotent-ordered)
            resilience.fault_point("journal.checkpoint", root=path)
            upto = min((int(e.get("journal_seq", 0))
                        for e in manifest["schemas"].values()),
                       default=jpos)
            j.checkpoint(min(upto, jpos))
            for name in list(self._applied_seq):
                if names is None or name in set(names):
                    self._applied_seq[name] = max(
                        self._applied_seq.get(name, 0), jpos)

    def _sweep_orphan_chunks(self, path: str, schemas: Dict[str, Any],
                             names: Optional[Sequence[str]]) -> None:
        """Remove chunk dirs / legacy npz no longer referenced by the
        just-published manifest — the deferred half of the crash-consistent
        save (old files outlive the save until the new manifest is durable;
        only then do they become sweepable orphans). Restricted to the
        schemas this save touched."""
        ref_dirs = set()
        ref_files = set()
        for entry in schemas.values():
            for rel in entry.get("chunks") or []:
                ref_files.add(rel)
                d = os.path.dirname(rel)
                if d:
                    ref_dirs.add(d)
        swept = set(self._stores) if names is None else set(names)
        try:
            listing = os.listdir(path)
        except OSError:
            return
        for name in swept:
            for fn in listing:
                full = os.path.join(path, fn)
                if fn.startswith(f"{name}_chunks") and os.path.isdir(full):
                    if fn not in ref_dirs:
                        shutil.rmtree(full, ignore_errors=True)
                        continue
                    # referenced dir: sweep the chunk FILES a full rewrite
                    # orphaned (uuid-named, so the live ones were never
                    # overwritten)
                    for cf in os.listdir(full):
                        if f"{fn}/{cf}" not in ref_files:
                            try:
                                os.remove(os.path.join(full, cf))
                            except OSError:
                                pass
                elif fn == f"{name}.npz" and fn not in ref_files:
                    entry = schemas.get(name)
                    # a carried-over v1 entry without "chunks" still loads
                    # through the npz fallback — never sweep that
                    if entry is not None and not entry.get("chunks"):
                        continue
                    try:
                        os.remove(full)
                    except OSError:
                        pass

    @staticmethod
    def load(path: str, mesh=None, prefer_device: bool = True) -> "GeoDataset":
        from geomesa_tpu.fs import journal as _jr

        mpath = os.path.join(path, "manifest.json")
        has_journal = (config.JOURNAL_ENABLED.to_bool()
                       and _jr.journal_exists(path))
        manifest: Dict[str, Any] = {"schemas": {}}
        if os.path.exists(mpath):
            with open(mpath) as fh:
                manifest = json.load(fh)
        elif not has_journal:
            # keep the pre-journal contract: loading a root with neither a
            # manifest nor a journal is an error
            with open(mpath) as fh:  # raises FileNotFoundError
                manifest = json.load(fh)
        ds = GeoDataset(mesh=mesh, prefer_device=prefer_device)
        ckpt: Dict[str, int] = {}
        for name, meta in manifest["schemas"].items():
            ds._attach_schema_entry(path, name, meta)
            ckpt[name] = int(meta.get("journal_seq", 0))
            ds._applied_seq[name] = ckpt[name]
        ds.n_shards = None
        if config.JOURNAL_ENABLED.to_bool():
            ds.attach_journal(path)
            if has_journal:
                # recovery: re-apply records past each schema's checkpointed
                # position, in order; torn tails truncate cleanly here
                if ds._journal_replay(ckpt, truncate=True):
                    ds.flush()
        return ds

    @staticmethod
    def _entry_fp(meta: Dict) -> int:
        """Fingerprint of a manifest schema entry, stable across the JSON
        round trip — what :meth:`refresh_schema` compares to decide whether
        the root's checkpoint moved underneath the journal."""
        return zlib.crc32(json.dumps(
            meta, sort_keys=True, separators=(",", ":"),
            default=str).encode()) & 0xFFFFFFFF

    def _attach_schema_entry(self, path: str, name: str, meta: Dict) -> None:
        """Create + populate ONE schema's store from a checkpoint manifest
        entry (the per-schema half of :meth:`load`; also the fleet epoch
        refresh path — docs/RESILIENCE.md §7)."""
        self._ckpt_fp[name] = self._entry_fp(meta)
        prev_shards = self.n_shards
        ft = FeatureType.from_spec(name, meta["spec"])
        self.n_shards = meta["n_shards"]
        try:
            # attaching FROM a checkpoint is not a new mutation: it must
            # not journal a schema-create record
            with self._replay_scope():
                self.create_schema(ft)
        finally:
            self.n_shards = prev_shards
        st = self._store(name)
        st.dicts = {
            k: DictionaryEncoder(v) for k, v in meta["dicts"].items()
        }
        st.stats = {k: sk.Stat.from_json(v) for k, v in meta["stats"].items()}
        if "partitions" in meta:
            st.attach_snapshots({
                int(b): os.path.join(path, rel)
                for b, rel in meta["partitions"].items()
            })
            self._standing_restore(name, meta)
            return
        # v2 chunked layout, with the v1 single-npz fallback
        chunk_files = meta.get("chunks")
        if chunk_files is None:
            npz_path = os.path.join(path, f"{name}.npz")
            chunk_files = ([os.path.relpath(npz_path, path)]
                           if os.path.exists(npz_path) else [])
        parts = []
        for rel in chunk_files:
            with np.load(os.path.join(path, rel),
                         allow_pickle=False) as z:
                cols = {}
                for k in z.files:
                    v = z[k]
                    cols[k] = (v.astype(object) if v.dtype.kind == "U"
                               else v)
                if cols:
                    parts.append(ColumnBatch(
                        cols, len(next(iter(cols.values())))))
        if parts:
            from geomesa_tpu.schema.columns import schema_null_fills

            # schema-derived fills: mixed-vintage chunks (e.g. saved
            # before a column existed) null-fill per the layout's
            # convention, not a dtype guess
            st._all = (parts[0] if len(parts) == 1
                       else ColumnBatch.concat(
                           parts, fills=schema_null_fills(ft)))
            if "epoch" in meta:
                st.mutation_epoch = meta["epoch"]
            key_cols = dict(st._all.columns)
            for ks in st.keyspaces:
                key_cols.update(ks.index_keys(ft, st._all))
                st.tables[ks.name].rebuild(key_cols, st.dicts)
            # seed the key cache so the next flush appends incrementally
            st._key_cols = {
                k: v for k, v in key_cols.items()
                if k not in st._all.columns
            }
        self._standing_reattach(name)
        self._standing_restore(name, meta)

    def _standing_restore(self, name: str, meta: Dict) -> None:
        """Re-register the checkpoint's standing subscriptions (manifest
        ``entry["standing"]``, written by :meth:`save`) under their
        ORIGINAL ids — each snapshot anchor re-evaluates against the
        freshly attached store (docs/STANDING.md §7). A spec that no
        longer validates (schema drift since the checkpoint) degrades
        through the skip trail instead of failing the load."""
        recs = meta.get("standing") or []
        if not recs:
            return
        from geomesa_tpu.subscribe.spec import StandingSpec

        for rec in recs:
            try:
                self._standing_engine().register(
                    StandingSpec.from_dict(rec["spec"]),
                    sub_id=rec["sub_id"])
            except Exception as e:
                resilience.record_skip(
                    "standing.restore", f"{name}:{rec.get('sub_id')}", e,
                    phase="load")

    def _standing_reattach(self, name: str) -> None:
        if self.standing is not None and self.standing.active(name):
            # the store object was swapped under the standing groups:
            # recompile viewports against the fresh dicts and re-scan
            self.standing.reattach(name)

    def refresh_schema(self, name: str, path: str) -> bool:
        """Replace schema ``name``'s in-memory state with what the shared
        checkpoint at ``path`` holds — the replica-side half of fleet
        epoch propagation (docs/RESILIENCE.md §7): a replica whose known
        fleet epoch trails an incoming request's re-reads the schema from
        the shared root BEFORE serving, so a restarted or failed-over
        replica can never answer from a pre-mutation store or cache
        (the replaced store's covers drop with its uid, exactly like a
        local mutation epoch bump). Handles remote creates (schema in the
        manifest but not here), remote deletes (here but gone from the
        manifest), and plain data changes. Returns True when anything
        changed."""
        mpath = os.path.join(path, "manifest.json")
        schemas: Dict[str, Any] = {}
        if os.path.exists(mpath):
            with open(mpath) as fh:
                schemas = json.load(fh).get("schemas", {})
        meta = schemas.get(name)
        old = self._stores.get(name)
        j = self._journal
        use_journal = (
            j is not None
            and os.path.abspath(j.root) == os.path.abspath(path)
        )
        ckpt = int(meta.get("journal_seq", 0)) if meta is not None else 0
        if use_journal:
            have = self._applied_seq.get(name)
            # the incremental shortcut is valid only while the root's
            # manifest entry is the one we attached (journal-only growth):
            # an entry rewritten out-of-band — a writer checkpointing
            # without the journal — must force the full re-attach below
            # or the rewrite is never observed
            unmoved = (meta is None
                       or self._ckpt_fp.get(name) == self._entry_fp(meta))
            if old is not None and have is not None and have >= ckpt \
                    and unmoved:
                # incremental catch-up (docs/RESILIENCE.md §8): this replica
                # already holds the schema at journal position ``have`` —
                # re-apply only the shared journal's records past it. A
                # one-row fleet insert costs one record here, never a full
                # schema re-attach; version bumps invalidate covers exactly
                # like a local mutation.
                applied = self._journal_replay({name: have}, schema=name)
                if applied and name in self._stores:
                    self.flush(name)
                return applied > 0
            if meta is None:
                # schema not checkpointed yet: it exists (if at all) only in
                # the journal — rebuild it from records alone
                if old is not None:
                    with self._replay_scope():
                        self.delete_schema(name)
                    self._plan_cache_clear(name)
                    self._drop_executors(name)
                applied = self._journal_replay({name: 0}, schema=name)
                if applied and name in self._stores:
                    self.flush(name)
                return applied > 0 or old is not None
        if meta is None:
            if old is None:
                return False
            with self._replay_scope():
                self.delete_schema(name)  # invalidates the old uid's covers
            self._plan_cache_clear(name)
            self._drop_executors(name)
            return True
        if old is not None:
            self.cache.store.invalidate(old.uid)
            del self._stores[name]
            self.metadata.pop(name, None)
            self._plan_cache_clear(name)
            self._drop_executors(name)
        self._attach_schema_entry(path, name, meta)
        self._applied_seq[name] = ckpt
        if use_journal:
            # replay the journal's records past the checkpoint this entry
            # captured (the trailing-replica recovery half of §8)
            if self._journal_replay({name: ckpt}, schema=name):
                if name in self._stores:
                    self.flush(name)
        return True
