"""Partition-at-a-time query execution over a PartitionedFeatureStore.

The runtime role of the reference's per-partition range scans + client merge
(TablePartition tables scanned per partition, AbstractBatchScan.scala:32
bounded-queue streaming; FeatureReducer merge in QueryPlanner.runQuery):
prune partitions by the plan's time bounds, stream each pruned partition
through RAM/HBM (loading spilled ones from disk, evicting over budget), run
the ordinary :class:`Executor` against it, and merge the additive results.
One plan → one traced kernel shared by every partition (kernel shapes are
bucketed in IndexTable.shard_len / windows).

**Sharded scan** (docs/SCALE.md): with more than one local device and
``geomesa.mesh.devices`` not disabled, additive aggregates (count /
density / density_curve / stats) fan the pruned partitions out
ROUND-ROBIN over the devices — partition i (in pruned-bin order) pins to
device i % D, its scan dispatches asynchronously (jax dispatch returns
before execution, so device d runs partition i while the one query thread
dispatches partition i+1 to the next device — the jit discipline is
untouched), and the per-device partials merge in the fixed order
:func:`geomesa_tpu.parallel.devices.tree_merge` documents. The merge
order depends only on the pruned-bin order, never on device assignment or
completion timing, and the serial path uses the SAME tree merge — so the
sharded scan is bit-identical to the single-device path by construction.
Non-additive ops (features/top/knn) keep the serial partition stream."""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from geomesa_tpu import config, metrics, resilience, tracing, utilization
from geomesa_tpu.parallel import health as phealth
from geomesa_tpu.filter import ir
from geomesa_tpu.index.partitioned import PartitionedFeatureStore
from geomesa_tpu.kernels.registry import KernelRegistry
from geomesa_tpu.kernels import stats_scan as kstats
from geomesa_tpu.parallel import devices as pdev
from geomesa_tpu.planning.executor import Executor, check_deadline
from geomesa_tpu.planning.planner import QueryPlan
from geomesa_tpu.resilience import QueryTimeoutError
from geomesa_tpu.schema.columns import ColumnBatch
from geomesa_tpu.stats import sketches as sk

_SKIPPED = object()  # sentinel: partition degraded away (fn may return None)
_UNSET = object()


def _synced(finish):
    """A partition ``finish`` that reads its partial on the host: the
    partition's dispatch stamps close after the read (utilization.py). A
    finish that keeps the merge on the device (density) is left
    unwrapped, and its stamps close with the operation."""
    def read(b, p, mdev):
        finish(b, p, mdev)
        utilization.settle()
    return read


def _coalesce_boxes(boxes: List[Tuple[float, float, float, float]]
                    ) -> List[Tuple[float, float, float, float]]:
    """Coalesce exactly-tiling boxes into a compact cover — the
    group-scoped plan-bounds pass for fleet-scattered sub-queries
    (docs/RESILIENCE.md §7): a scatter group's filter carries one BBOX
    per owned SFC cell (dozens of boxes in row-major runs), and every
    lake row group would otherwise test disjointness against each one.
    Two boxes merge only when their union is (up to one float ulp) a
    box: identical y-span and x-ranges that touch, overlap, or are one
    ulp apart — cell boxes are CLOSED realizations of half-open cells,
    so adjacent cells sit exactly one ulp apart — then the transpose
    pass for columns of identical x-span. Closing an ulp seam can only
    WIDEN the cover, which is always safe for pruning (a row group is
    dropped only when disjoint from every box; a wider box never drops
    more). Adjacent cell boxes in a row collapse to one strip, stacked
    strips to one window."""
    def _pass(bs, flip):
        def key(b):
            return (b[1], b[3], b[0]) if not flip else (b[0], b[2], b[1])

        bs = sorted(bs, key=key)
        out = [bs[0]]
        for b in bs[1:]:
            p = out[-1]
            if not flip and p[1] == b[1] and p[3] == b[3] \
                    and b[0] <= np.nextafter(p[2], np.inf):
                out[-1] = (p[0], p[1], max(p[2], b[2]), p[3])
            elif flip and p[0] == b[0] and p[2] == b[2] \
                    and b[1] <= np.nextafter(p[3], np.inf):
                out[-1] = (p[0], p[1], p[2], max(p[3], b[3]))
            else:
                out.append(b)
        return out

    if len(boxes) < 2:
        return boxes
    return _pass(_pass(boxes, flip=False), flip=True)


class PartitionedExecutor:
    def __init__(self, store: PartitionedFeatureStore, mesh=None,
                 prefer_device: bool = True, device=None):
        self.store = store
        self.mesh = mesh
        self.prefer_device = prefer_device
        #: serving-pool device pin: a slot executor streams every partition
        #: through ITS device (the pool owns one device per dispatch
        #: thread), which also disables the sharded fan-out below — two
        #: threads must never dispatch to one device (docs/SERVING.md)
        self.device = device
        #: jitted-kernel LRU shared across every partition child AND every
        #: aggregate-cache cell query (version-stable keys — docs/PERF.md).
        #: Also shared across the sharded scan's per-device executors AND
        #: every serving-pool slot's PartitionedExecutor over this store:
        #: hosted on the STORE (the same ``_kernel_registry`` slot plain
        #: Executors use via version_source), because keys are device-free
        #: — D devices or N pool slots cost ONE trace per kernel shape.
        reg = store.__dict__.get("_kernel_registry")
        if reg is None:
            reg = store.__dict__["_kernel_registry"] = KernelRegistry()
        self._kernel_fns = reg
        self._execs: Dict[int, Executor] = {}

    def kernel_registry(self) -> KernelRegistry:
        return self._kernel_fns

    # -- partition pruning (the TimePartition.partitions() analog) ---------
    def prune(self, plan: QueryPlan) -> List[int]:
        store = self.store
        bins = store.partition_bins()
        if plan.is_empty:
            return []
        kp = plan.key_plan
        if (
            kp.bins is not None
            and store.partition_period == store.ft.time_period
        ):
            sel = {int(x) for x in np.asarray(kp.bins).ravel()}
            return [b for b in bins if b in sel]
        dtg = store.ft.dtg_field
        iv = ir.extract_intervals(plan.filter, dtg) if dtg else None
        if iv is not None and not iv.is_empty:
            sel = set()
            for lo, hi in iv.values:
                if lo is None or hi is None:
                    return bins
                sel.update(
                    int(x) for x in store.binned.bins_between(int(lo), int(hi))
                )
            return [b for b in bins if b in sel]
        return bins

    def _executor_for(self, b: int, child, device=_UNSET) -> Executor:
        if device is _UNSET:
            device = self.device
        ex = self._execs.get(b)
        if ex is None or ex.store is not child \
                or getattr(ex, "device", None) is not device:
            ex = Executor(
                child, self.mesh, self.prefer_device,
                kernel_fns=self._kernel_fns, version_source=self.store,
                device=device,
            )
            self._execs[b] = ex
        return ex

    # -- multi-device sharded scan (docs/SCALE.md) -------------------------
    def _scan_devices(self):
        """Devices for the sharded fan-out, or None when it cannot engage:
        an explicit GSPMD mesh shards WITHIN partitions instead; a pinned
        (serving-pool slot) executor owns exactly one device; the host
        path has nothing to fan out; and ``geomesa.mesh.devices`` can turn
        it off (parallel/devices.py also stands down while a >1-executor
        pool runs)."""
        if self.mesh is not None or self.device is not None \
                or not self.prefer_device:
            return None
        return pdev.scan_devices()

    # -- double-buffered partition pipeline --------------------------------
    def _stage(self, child, plan: QueryPlan) -> None:
        """Prefetch-thread half of the double buffer: pull the partition's
        columns off disk (lazy snapshot members) and assemble the stacked
        [S, L] HOST arrays the device upload will consume. Pure host work —
        no jax calls, so all compile/dispatch stays on the query thread
        (the PR 1 one-query-thread jit discipline)."""
        names = plan.__dict__.get("needed_cols")
        if child is None or not names:
            return
        t = child.tables.get(plan.index_name)
        if t is not None and t.n:
            staged = t.stage_host(names)
            if staged:
                # per-query cost ledger: host bytes assembled for upload.
                # The prefetch worker adopted the query's span context, so
                # this lands on the right trace (docs/OBSERVABILITY.md)
                tracing.add_cost("bytes_staged", float(staged))
            metrics.inc(metrics.PIPELINE_PREFETCH)

    # -- lake row-group pushdown (docs/LAKE.md) ----------------------------
    def _push_window(self, plan: QueryPlan) -> Optional[Dict]:
        """The plan's conservative spatial/temporal bounds as a lake
        pruning window, or None when pushdown cannot engage (disabled,
        sampling hints — the 1-in-n counter is row-set dependent — or a
        filter that constrains neither axis). Extraction reuses the same
        ``ir.extract_*`` machinery partition/file pruning already trusts:
        a row group whose statistics are disjoint from every extracted
        bound provably holds no matching row."""
        if not config.LAKE_PUSHDOWN.to_bool():
            return None
        h = plan.hints
        if h.sampling is not None or h.sample_by is not None:
            return None
        ft = self.store.ft
        boxes = times = None
        geom = ft.geom_field
        if geom is not None and ft.attr(geom).is_point:
            fv = ir.extract_geometries(plan.filter, geom)
            if fv.disjoint:
                boxes = []
            elif not fv.is_empty:
                boxes = _coalesce_boxes([
                    tuple(float(v) for v in g.bounds())
                    for g in fv.values
                ])
        dtg = ft.dtg_field
        if dtg is not None:
            iv = ir.extract_intervals(plan.filter, dtg)
            if iv.disjoint:
                times = []
            elif not iv.is_empty:
                inf = float("inf")
                times = [
                    (-inf if lo is None else float(lo),
                     inf if hi is None else float(hi))
                    for lo, hi in iv.values
                ]
        if boxes is None and times is None:
            return None
        window = {"index": plan.index_name, "boxes": boxes, "times": times}
        # cross-chunk residency cache (docs/JOIN.md §11): the join's chunk
        # loop plants one cache on each re-planned side plan so boundary
        # row groups shared by adjacent chunk windows decode once
        residency = plan.__dict__.get("residency")
        if residency is not None:
            window["residency"] = residency
        return window

    def _get_child(self, b: int, window: Optional[Dict]):
        """Load one partition for the scan: statistics-pruned ephemeral
        child when a window is pushed down, the ordinary resident load
        otherwise (and always on plain FeatureStore children)."""
        if window is not None:
            sc = getattr(self.store, "scan_child", None)
            if sc is not None:
                return sc(b, window)
        return self.store.child(b)

    def _note_lake(self, plan: QueryPlan, note: Dict) -> None:
        """Fold one pruned partial load's account into the plan (explain
        ``exec_path``, the audit event, and the per-query cost ledger)."""
        acct = plan.__dict__.setdefault("lake_acct", {
            "groups_total": 0, "groups_loaded": 0, "groups_pruned": 0,
            "bytes_payload": 0, "bytes_loaded": 0, "bytes_skipped": 0,
        })
        for k in acct:
            acct[k] += int(note.get(k, 0))
        plan.__dict__.setdefault("exec_path", {})["lake"] = (
            f"{acct['groups_loaded']}/{acct['groups_total']} rowgroups, "
            f"{acct['bytes_loaded']}/{acct['bytes_payload']} bytes"
        )
        tracing.add_cost("lake_bytes_read", float(note["bytes_loaded"]))
        tracing.add_cost("lake_bytes_skipped",
                         float(note["bytes_skipped"]))
        metrics.inc(metrics.LAKE_PUSHDOWN_SCANS)

    def _children(self, plan: QueryPlan, bins: Optional[List[int]] = None,
                  window: Optional[Dict] = None):
        """(bin, child) over pruned partitions through the serial
        (one-staging-slot) prefetch pipeline — see :meth:`_pipeline`.
        ``bins`` overrides the plan's own pruning (the query-axis batch
        path scans the UNION of its members' pruned bins)."""
        if bins is None:
            bins = self.prune(plan)
        for _i, b, child in self._pipeline(plan, bins, window=window):
            yield b, child

    def _stage_device(self, child, plan: QueryPlan, dev) -> None:
        """device_put half of the sharded prefetch overlap (docs/PERF.md):
        upload the staged host arrays for the partition's assigned device
        FROM THE PREFETCH THREAD, overlapping the previous partition's
        execution on another device. Safe under the one-jit-thread-per-
        device discipline: device_put is a pure transfer — it never traces
        or compiles (the PR 1 wedge was jit compilation on foreign
        threads) — and it populates the same device cache, through the
        same per-device sharding singleton, the query thread would have
        populated itself, so results are bit-identical with the overlap
        off (gated by ``geomesa.pipeline.device-put``)."""
        names = plan.__dict__.get("needed_cols")
        if not names or child is None:
            return
        t = child.tables.get(plan.index_name)
        if t is None or not t.n:
            return
        t.device_columns(tuple(names), pdev.device_sharding(dev))
        metrics.inc(metrics.PIPELINE_DEVICE_PUT)

    def _pipeline(self, plan: QueryPlan, bins: List[int], devs=None,
                  window: Optional[Dict] = None):
        """(i, bin, child) over pruned partitions — THE prefetch
        pipeline, serial and sharded in one body. With
        ``geomesa.pipeline.prefetch`` (default on), a single worker
        thread stages partition host columns ahead of the consumer,
        granted ONE STAGING SLOT PER DEVICE (serial ``devs=None`` = one
        slot = the classic double buffer: partition i+1's load overlaps
        partition i's execution). With ``devs`` and
        ``geomesa.pipeline.device-put``, the worker also uploads each
        staged partition to its assigned device (a pure transfer — never
        traces or compiles — through the shared per-device sharding
        singleton; docs/PERF.md §3), so every device has its next
        partition's columns resident the moment its current scan drains.

        Consumption order is pruned-bin order in both modes; a load
        error re-raises on the query thread at the same point it would
        have sequentially; config overrides and the span context cross
        the thread boundary via snapshot/adopt (staged (name, L) keys
        and trace nesting must match the query thread exactly)."""
        # cost ledger: partition pruning effectiveness for this scan
        # (pruned = bins the plan's time bounds excluded outright)
        total_bins = len(self.store.partition_bins())
        tracing.add_cost("partitions_scanned", float(len(bins)))
        tracing.add_cost("partitions_pruned",
                         float(max(total_bins - len(bins), 0)))
        if len(bins) < 2 or not config.PIPELINE_PREFETCH.to_bool():
            for i, b in enumerate(bins):
                try:
                    child = self._get_child(b, window)
                except BaseException as e:
                    self._contain_load(plan, b, e)
                    continue
                if child is not None:
                    note = child.__dict__.get("_lake_note")
                    if note is not None:
                        self._note_lake(plan, note)
                yield i, b, child
            return
        out: "queue.Queue" = queue.Queue()
        stop = threading.Event()
        slot = threading.Semaphore(0)  # one permit per granted load
        overlap = devs is not None \
            and bool(config.PIPELINE_DEVICE_PUT.to_bool())
        ov = config.snapshot_overrides()
        tspan = tracing.snapshot()

        def worker():
            config.adopt_overrides(ov)
            tracing.adopt(tspan)
            try:
                for i, b in enumerate(bins):
                    while not slot.acquire(timeout=0.1):
                        if stop.is_set():
                            return
                    if stop.is_set():
                        return
                    attrs = {"part": int(b)}
                    dev = None
                    if devs is not None:
                        dev = devs[i % len(devs)]
                        attrs["device"] = int(dev.id)
                    child = err = None
                    try:
                        child = self._get_child(b, window)
                    except BaseException as e:
                        err = e  # a LOAD failure: _contain_load decides
                    if err is None and child is not None:
                        # staging (host assembly + device upload) is a
                        # best-effort OVERLAP, never the dispatch: a
                        # staging failure must not fail — or mislabel as
                        # a spill-load skip — a partition the dispatch
                        # can still serve by assembling on demand, and a
                        # fenced lane must stop receiving uploads
                        try:
                            with tracing.span("scan.stage", **attrs):
                                self._stage(child, plan)
                                if overlap:
                                    if phealth.registry().usable(dev.id):
                                        self._stage_device(child, plan,
                                                           dev)
                        except Exception:
                            pass  # dispatch re-stages on demand
                        except BaseException as e:
                            err = e  # interpreter teardown etc.: surface
                    out.put((i, b, child if err is None else None, err))
            finally:
                out.put(None)

        t = threading.Thread(
            target=worker, daemon=True,
            name="geomesa-part-prefetch" if devs is None
            else "geomesa-shard-prefetch",
        )
        t.start()
        for _ in range(1 if devs is None else len(devs)):
            slot.release()  # the first load(s) start immediately
        try:
            while True:
                item = out.get()
                if item is None:
                    return
                # grant the NEXT load now: it overlaps this partition's
                # execution — at most one in-flight partition per slot
                slot.release()
                i, b, child, err = item
                if err is not None:
                    self._contain_load(plan, b, err)
                    continue
                if child is not None:
                    # lake accounting folds on the CONSUMER thread — the
                    # plan dict is single-thread-mutated like every other
                    # counter (the worker only loads)
                    note = child.__dict__.get("_lake_note")
                    if note is not None:
                        self._note_lake(plan, note)
                yield i, b, child
        finally:
            stop.set()
            # JOIN, not fire-and-forget: an early consumer exit
            # (max_features, deadline) must not leave the worker mutating
            # the partition map under a follow-up query's unlocked readers
            # (partition_bins, flush loops). The wait is bounded by the
            # in-flight loads (worker observes `stop` right after each).
            t.join()
            # free staged host arrays of prefetched-but-never-executed
            # partitions (their loop-body cleanup never ran)
            while True:
                try:
                    item = out.get_nowait()
                except queue.Empty:
                    break
                if item is None:
                    continue
                _, _, child, _ = item
                if child is not None:
                    tb = child.tables.get(plan.index_name)
                    if tb is not None:
                        tb._host_stage.clear()

    def _dispatch_reassign(self, plan: QueryPlan, b: int, child, i: int,
                           op: str, dispatch, live: List, state: Dict):
        """One partition's dispatch under the device fault-tolerance
        contract (docs/RESILIENCE.md §6). The partition pins to
        ``live[i % len(live)]`` — pruned-bin round-robin over the devices
        still SURVIVING this scan (cordoned/broken lanes are skipped; a
        lane that fails here is dropped, so its pending partitions requeue
        onto the survivors). Each attempt passes the
        ``scan.device.dispatch`` fault point; a failed attempt feeds the
        device's breaker (``parallel/health.py``) and retries on the next
        survivor under a seeded RetryPolicy (``geomesa.retry.*``, seed =
        the partition bin — a chaos run replays identically). Exhausted
        retries, or no survivors, re-raise into ``_scan_part``'s
        degradation contract: exact survivor totals under
        ``allow_partial()``, typed failure otherwise, never a wedge.

        Bit-identity holds by construction: whichever device computes a
        partial, it enters the tree reduction in pruned-bin order — the
        only order :func:`~geomesa_tpu.parallel.devices.tree_merge` ever
        sees — so a recovered run is bit-identical to a healthy one
        (asserted by tests/test_chaos.py)."""
        hreg = phealth.registry()
        policy = resilience.RetryPolicy.from_config(seed=int(b))
        attempts = max(policy.attempts, 1)
        delays = policy.delays_ms()
        last: Optional[BaseException] = None
        removed_here: List = []  # lanes this PARTITION's attempts removed
        for attempt in range(attempts):
            # rotate past lanes health has fenced since the scan started
            while live and not hreg.usable(live[i % len(live)].id):
                live.pop(i % len(live))
            if not live:
                break
            dev = live[i % len(live)]
            try:
                resilience.fault_point(
                    "scan.device.dispatch", bin=int(b),
                    device=int(dev.id), op=op, attempt=attempt,
                )
                ex = self._executor_for(b, child, device=dev)
                r = dispatch(ex)
            except QueryTimeoutError:
                raise
            except Exception as e:
                last = e
                hreg.record_failure(dev.id, e)
                try:
                    live.remove(dev)
                    removed_here.append(dev)
                except ValueError:
                    pass
                if attempt + 1 >= attempts or not live:
                    break
                # requeue onto the next survivor (round-robin continues
                # over the shrunken rotation)
                hreg.note_reassigned(dev.id)
                metrics.inc(metrics.SCAN_REASSIGNED)
                tracing.event("scan.reassigned", part=int(b),
                              device=int(dev.id), error=type(e).__name__)
                d = delays[attempt] if attempt < len(delays) else 0.0
                if d > 0:
                    policy.sleep(d / 1000.0)
                check_deadline()
                continue
            # success on a survivor: lanes removed above STAY removed —
            # the same partition worked elsewhere, so the evidence is
            # lane-scoped. The device's own breaker success is recorded
            # at SYNC time (_finish_oldest), where execution errors
            # actually surface — an enqueue is not evidence of health.
            state["device"] = dev
            return r
        # the partition failed on EVERY lane it tried: the evidence is
        # PARTITION-scoped (bad data / oversized staging), not lane-
        # scoped — restore the lanes it removed so one poison partition
        # cannot fence the whole mesh off for the rest of the scan
        # (their breakers keep the charge; genuinely dead lanes still
        # accumulate consecutive failures across partitions)
        for dev in removed_here:
            if hreg.usable(dev.id) and dev not in live:
                live.append(dev)
        if last is not None:
            raise last
        raise RuntimeError(
            "no surviving devices for the sharded scan (all cordoned or "
            "broken mid-scan)"
        )

    def _contain_load(self, plan: QueryPlan, b: int, err: BaseException):
        """Degradation contract for a partition LOAD failure (a corrupt
        or unreadable spill snapshot — ``index/partitioned.py``'s
        ``index.spill.load`` edge): under ``allow_partial()`` the
        partition is skipped with a recorded degradation (exact survivor
        totals, same as a scan failure); strict mode — and any deadline
        expiry or non-Exception — re-raises at the point the sequential
        load would have. Before this, a spill-load failure took the whole
        query down even in degraded mode (ROADMAP resilience item)."""
        if isinstance(err, QueryTimeoutError) \
                or not isinstance(err, Exception) \
                or not resilience.partial_allowed():
            raise err
        rec = resilience.record_skip(
            "index.spill.load", f"bin:{b}", err, phase="load"
        )
        plan.__dict__.setdefault("degraded", []).append(rec)

    def _sharded_scan(self, plan: QueryPlan, op: str, dispatch, finish,
                      devs, bins: List[int],
                      window: Optional[Dict] = None) -> None:
        """Round-robin fan-out of one additive op over ``devs``:
        ``dispatch(ex)`` runs per pruned partition against an executor
        pinned to the partition's device (it must return WITHOUT forcing
        a device sync). Each partial is handed to ``finish(bin, partial,
        merge_device)`` in pruned-bin order — the only order the merge
        ever sees — but DEFERRED until D further partitions have been
        dispatched (or the scan ends), so every device keeps executing
        while older partials sync/merge and at most D partials plus the
        reducer spine are ever outstanding (never all P). finish runs
        under the same degradation guard as the scan, attributing a
        sync-time device failure to its partition; its sync wall time
        feeds the device's latency-outlier detector (a straggler lane is
        fenced like a failing one — parallel/health.py). Dispatch
        failures requeue the partition onto surviving devices
        (:meth:`_dispatch_reassign`). Each partition's dispatch stamps
        (utilization.py) ride with its partial and are open again when
        its finish runs (:func:`_synced`)."""
        metrics.inc(metrics.SCAN_SHARDED)
        from collections import deque

        # (bin, partial, device, shape, stamps) awaiting finish
        pending: "deque" = deque()
        mdev = devs[0]  # the device the serial path computes on
        hreg = phealth.registry()
        #: devices still surviving THIS scan (failed lanes drop out and
        #: their pending partitions requeue round-robin onto the rest)
        live: List = list(devs)

        def _finish_oldest():
            fb, fr, fdev, fshape, stamps = pending.popleft()
            t0 = time.perf_counter()

            def _fin():
                # jax dispatch is async: execution errors surface HERE,
                # at the blocking sync — so health verdicts are recorded
                # at sync time, not enqueue time (an enqueue that
                # "succeeded" on a dead device is not evidence of
                # health, and must not reset its breaker)
                try:
                    out = finish(fb, fr, mdev)
                except QueryTimeoutError:
                    raise
                except Exception as e:
                    if fdev is not None:
                        hreg.record_failure(fdev.id, e)
                    raise
                if fdev is not None:
                    hreg.record_success(fdev.id)
                return out

            utilization.attach(stamps)
            self._scan_part(plan, fb, op, _fin,
                            probe=False, spanned=False)
            if fdev is not None:
                # baseline keyed by kernel shape (op + padded-length
                # bucket): heterogeneous ops/partition sizes each compare
                # against their own trailing median (RESILIENCE.md §6)
                hreg.record_latency(fdev.id, time.perf_counter() - t0,
                                    shape=fshape)

        tot_scanned = tot_rows = 0
        try:
            for i, b, child in self._pipeline(plan, bins, devs,
                                              window=window):
                check_deadline()
                if child is None or child.count == 0:
                    continue
                plan.__dict__.pop("scanned_rows", None)
                plan.__dict__.pop("table_rows", None)
                state: Dict = {}
                r = self._scan_part(
                    plan, b, op,
                    lambda b=b, i=i, child=child, state=state:
                        self._dispatch_reassign(plan, b, child, i, op,
                                                dispatch, live, state),
                    device=live[i % len(live)] if live else None,
                )
                tot_scanned += plan.__dict__.pop("scanned_rows", 0)
                tot_rows += plan.__dict__.pop("table_rows", 0)
                dev = state.get("device")
                if dev is not None:
                    metrics.inc(f"{metrics.SCAN_SHARDED_DEVICE}.{dev.id}")
                if r is not _SKIPPED and r is not None:
                    # kernel-shape key: the op plus the partition's padded-
                    # length bucket (geomesa.partition.shard.bucket rounds
                    # child tables to multiples, so equal buckets share a
                    # compiled kernel shape)
                    lbucket = config.SHARD_LEN_BUCKET.to_int() or 65536
                    shape = (op, -(-child.count // max(lbucket, 1)))
                    pending.append((b, r, dev, shape, utilization.detach()))
                # dispatched work holds its own buffer references: staged
                # host arrays and evicted children free safely here even
                # while the device is still executing
                t = child.tables.get(plan.index_name)
                if t is not None:
                    t._host_stage.clear()
                self.store.evict()
                resident = self.store.partitions
                for bb in list(self._execs):
                    if self._execs[bb].store is not resident.get(bb):
                        del self._execs[bb]
                while len(pending) > len(devs):
                    _finish_oldest()
            while pending:
                _finish_oldest()
        finally:
            plan.__dict__["scanned_rows"] = tot_scanned
            plan.__dict__["table_rows"] = tot_rows
        self._note_sharded(plan, len(bins), len(devs))

    def _note_sharded(self, plan: QueryPlan, n_parts: int, n_devs: int):
        plan.__dict__.setdefault("exec_path", {}).update(
            sharded=f"{n_parts} partitions over {n_devs} devices"
        )

    def _additive_scan(self, plan: QueryPlan, op: str, dispatch,
                       finish, bins: Optional[List[int]] = None,
                       push: bool = False) -> None:
        """Drive one additive op over the pruned partitions, delivering
        each partition's partial to ``finish(bin, partial, merge_device)``
        in pruned-bin order. The sharded fan-out serves when it engages
        (merge_device = the first local device — where the serial path
        computes — so the merge is bit-identical); otherwise the serial
        partition stream runs finish immediately after each partition
        (merge_device None), exactly the pre-sharding cadence. Both
        paths guard finish with the _scan_part degradation contract, so
        a device failure surfacing at sync time skips that partition
        with exact survivor totals instead of failing the query under
        ``allow_partial()``. ``bins`` overrides the plan's pruning (the
        query-axis batch path scans its members' pruned-bin UNION).

        ``push=True``: the op's partial merge is exact over any superset
        of the matching rows (count / unweighted density / unweighted
        density_curve / stats), so spilled lake partitions may serve a
        statistics-pruned PARTIAL load (docs/LAKE.md) — row groups whose
        bbox/time statistics are disjoint from the plan's bounds never
        leave disk, and the surviving groups decode into the same
        prefetch pipeline bit-identically."""
        window = self._push_window(plan) if push else None
        try:
            devs = self._scan_devices()
            if devs is not None:
                if bins is None:
                    bins = self.prune(plan)
                if len(bins) >= 2:
                    self._sharded_scan(plan, op, dispatch, finish, devs,
                                       bins, window=window)
                    return
            for b, ex in self._each(plan, bins=bins, window=window):
                r = self._scan_part(plan, b, op, lambda: dispatch(ex))
                if r is not _SKIPPED and r is not None:
                    self._scan_part(plan, b, op,
                                    lambda: finish(b, r, None),
                                    probe=False, spanned=False)
        finally:
            self._note_pushdown_fallbacks(plan, window)

    @staticmethod
    def _note_pushdown_fallbacks(plan: QueryPlan,
                                 window: Optional[Dict]) -> None:
        """Fold the partitions pushdown could NOT serve pruned (exotic /
        unbuildable keyspace, pre-lake snapshot — recorded on the window
        by ``scan_child``) into explain/audit ``exec_path``, so a full
        load never reads as "pushdown covered everything"
        (docs/LAKE.md §10)."""
        fallbacks = (window or {}).get("fallbacks") if window else None
        if not fallbacks:
            return
        reasons: Dict[str, int] = {}
        for _b, reason in fallbacks:
            reasons[reason] = reasons.get(reason, 0) + 1
        plan.__dict__.setdefault("exec_path", {})["lake_fallback"] = (
            f"{len(fallbacks)} partition(s) full-loaded: "
            + ", ".join(f"{r} x{n}" for r, n in sorted(reasons.items()))
        )

    def _each(self, plan: QueryPlan,
              bins: Optional[List[int]] = None,
              window: Optional[Dict] = None) -> Iterator[Tuple[int, Executor]]:
        """Stream (bin, executor) over pruned partitions under the residency
        budget; accumulates the selectivity counters across partitions."""
        tot_scanned = tot_rows = 0
        try:
            for b, child in self._children(plan, bins, window=window):
                check_deadline()
                if child is None or child.count == 0:
                    continue
                plan.__dict__.pop("scanned_rows", None)
                plan.__dict__.pop("table_rows", None)
                yield b, self._executor_for(b, child)
                tot_scanned += plan.__dict__.pop("scanned_rows", 0)
                tot_rows += plan.__dict__.pop("table_rows", 0)
                # free staged host arrays the scan didn't consume (host
                # path, projection change): staging is per-partition-pass,
                # never a resident duplicate of the device columns
                t = child.tables.get(plan.index_name)
                if t is not None:
                    t._host_stage.clear()
                self.store.evict()
                resident = self.store.partitions
                for bb in list(self._execs):
                    if self._execs[bb].store is not resident.get(bb):
                        del self._execs[bb]  # frees the child's device arrays
        finally:
            # an early consumer exit (features() hitting max_features)
            # closes the generator AT the yield: the just-scanned
            # partition's counters are still on the plan — fold them in
            tot_scanned += plan.__dict__.get("scanned_rows", 0)
            tot_rows += plan.__dict__.get("table_rows", 0)
            plan.__dict__["scanned_rows"] = tot_scanned
            plan.__dict__["table_rows"] = tot_rows

    def _scan_part(self, plan: QueryPlan, b: int, op: str, fn, device=None,
                   probe: bool = True, spanned: bool = True):
        """One partition's scan under the degradation contract
        (docs/RESILIENCE.md): strict mode re-raises; under
        ``resilience.allow_partial()`` / ``geomesa.scan.partial`` a failing
        partition is recorded (collector + audit trail + the plan, for the
        query audit event) and skipped — returns the ``_SKIPPED`` sentinel.
        Deadline expiry always propagates: a timed-out scan must never
        masquerade as a degraded-but-complete one. ``device``: the sharded
        scan's assigned device — stamped on the span (per-device
        attribution, docs/OBSERVABILITY.md); on that path the span covers
        dispatch only (execution is async by design). ``probe=False`` /
        ``spanned=False``: the finish (sync/merge) half of a partition —
        same degradation handling, but no second fault-injection probe
        (one probe per partition keeps seeded chaos tests deterministic)
        and no second scan.partition span (sync time attributes to the
        op's parent span, as the pre-sharding merges did)."""
        try:
            if probe:
                resilience.fault_point("exec.partition.scan", bin=b, op=op)
            if not spanned:
                return fn()
            attrs = {"part": int(b), "op": op}
            if device is not None:
                attrs["device"] = int(device.id)
            with tracing.span("scan.partition", **attrs):
                return fn()
        except QueryTimeoutError:
            raise
        except Exception as e:
            if not resilience.partial_allowed():
                raise
            rec = resilience.record_skip(
                "exec.partition.scan", f"bin:{b}", e, phase=op
            )
            plan.__dict__.setdefault("degraded", []).append(rec)
            return _SKIPPED

    # -- public operations (Executor surface) ------------------------------
    # Additive aggregates collect per-partition partials (async-dispatched
    # round-robin over the local devices when the sharded scan engages)
    # and merge in pruned-bin order via the fixed tree reduction
    # parallel/devices.tree_merge documents — serial and sharded paths
    # share the merge code, so they are bit-identical by construction.
    def count(self, plan: QueryPlan) -> int:
        # counts merge as exact host integers (a device tree-add would
        # accumulate in int32 and overflow past 2^31 total rows); on the
        # sharded path each int() waits on a partial whose device was
        # dispatched D partitions ago, so the devices stay concurrent
        totals: List[int] = []
        self._additive_scan(
            plan, "count", lambda ex: ex.count_partial(plan),
            _synced(lambda b, p, mdev: totals.append(int(p))),
            push=True,
        )
        return sum(totals)

    def density(self, plan: QueryPlan, bbox, width: int, height: int,
                weight: Optional[str] = None, as_numpy: bool = True):
        import jax

        # merge ON DEVICE (per-partition grid downloads would ride the
        # host link once per partition per call) through the streaming
        # tree reduction — bit-identical to tree_merge over all partials,
        # holding O(log P) grids instead of P; sharded partials first
        # transfer to the merge device (jax.devices()[0], where the
        # serial path computes)
        red = pdev.TreeReducer(lambda a, b: a + b)

        def finish(b, p, mdev):
            if mdev is not None:
                p = jax.device_put(p, pdev.device_sharding(mdev))
            red.push(p)

        self._additive_scan(
            plan, "density",
            lambda ex: ex.density(plan, bbox, width, height, weight,
                                  as_numpy=False),
            finish,
            # unweighted grids are integer-valued (exact adds); weighted
            # grids keep full loads — a NaN/-0.0 weight on a pruned-away
            # non-matching row could still perturb the masked scatter
            push=weight is None,
        )
        out = red.result()
        if out is None:
            return np.zeros((height, width), np.float32)
        return np.asarray(out) if as_numpy else out

    def density_curve(self, plan: QueryPlan, level: int, block_window,
                      weight=None) -> np.ndarray:
        # decode syncs each partition's partial (deferred D partitions on
        # the sharded path) and the f64 host grids reduce in pruned-bin
        # tree order (integer counts are exact to 2^53; identical bits on
        # both paths)
        red = pdev.TreeReducer(lambda a, b: a + b)
        self._additive_scan(
            plan, "density_curve",
            lambda ex: ex.density_curve_raw(plan, level, block_window,
                                            weight),
            _synced(lambda b, p, mdev: red.push(Executor.decode_curve(p))),
            push=weight is None,  # see density: integer block counts only
        )
        out = red.result()
        if out is None:
            ix0, iy0, ix1, iy1 = block_window
            out = np.zeros((iy1 - iy0 + 1, ix1 - ix0 + 1), np.float64)
        return out

    def density_curve_batch(self, plan: QueryPlan, level: int,
                            block_windows, weight=None):
        """Fused tile batch over the partitioned store: each pruned
        partition executes ONE stacked device pass for every member crop
        (Executor.density_curve_batch), and per-member grids tree-merge
        across partitions — M concurrent tile queries cost one scan of the
        pruned partitions, not M (docs/SERVING.md)."""
        # one streaming reduction over the per-partition member LISTS:
        # elementwise combine keeps every member's association identical
        # to a per-member tree_merge over the same partials
        red = pdev.TreeReducer(
            lambda A, B: [a + b for a, b in zip(A, B)]
        )
        self._additive_scan(
            plan, "density_curve",
            lambda ex: ex.density_curve_batch_raw(
                plan, level, block_windows, weight
            ),
            _synced(
                lambda b, p, mdev: red.push(Executor.decode_curve_batch(p))
            ),
        )
        merged = red.result()
        outs = []
        for i, (ix0, iy0, ix1, iy1) in enumerate(block_windows):
            g = merged[i] if merged is not None else None
            if g is None:
                g = np.zeros((iy1 - iy0 + 1, ix1 - ix0 + 1), np.float64)
            outs.append(g)
        return outs

    def density_curve_filter_batch(self, plans: List[QueryPlan], spec,
                                   level: int, block_windows, weight=None):
        """M DISTINCT-filter curve crops over the partitioned store in
        one stacked device pass per pruned partition (None = ineligible;
        docs/SERVING.md "Query-axis batching", curve extension). Members'
        pruned-bin UNION scans once; per-member grids tree-merge across
        partitions exactly like :meth:`density_curve_batch`."""
        if spec is None:
            return None
        agg_cols = [weight] if weight else []
        bins = self._union_bins(plans)
        if not self._batch_ok(plans, spec, bins, agg_cols):
            return None
        red = pdev.TreeReducer(
            lambda A, B: [a + b for a, b in zip(A, B)]
        )

        def dispatch(ex):
            r = ex.density_curve_filter_batch_raw(
                plans, spec, level, block_windows, weight
            )
            if r is None:
                # partition-local ineligibility (e.g. surviving f32 band
                # rows in THIS partition): degrade this partition to
                # per-member serial curves — exact, never dropped — while
                # the other partitions keep the batched pass
                return ("serial", [
                    Executor.decode_curve(
                        ex.density_curve_raw(p, level, bw, weight)
                    )
                    for p, bw in zip(plans, block_windows)
                ])
            return r

        def finish(b, p, mdev):
            if isinstance(p, tuple) and len(p) == 2 and p[0] == "serial":
                red.push(p[1])
            else:
                red.push(Executor.decode_curve_filter_batch(p))

        self._additive_scan(plans[0], "density_curve", dispatch,
                            _synced(finish), bins=bins)
        merged = red.result()
        outs = []
        for i, (ix0, iy0, ix1, iy1) in enumerate(block_windows):
            g = merged[i] if merged is not None else None
            if g is None:
                g = np.zeros((iy1 - iy0 + 1, ix1 - ix0 + 1), np.float64)
            outs.append(g)
        return outs

    # -- query-axis batched aggregates (docs/SERVING.md "Query-axis
    # batching"): each pruned partition executes ONE stacked device pass
    # for every member viewport, and per-member partials accumulate
    # through the SAME pruned-bin tree-merge order the serial and sharded
    # paths share — so the batch composes with the device mesh and a
    # degraded partition skips for every member alike (exact per-member
    # survivor totals).
    def _union_bins(self, plans: List[QueryPlan]) -> List[int]:
        """Members' pruned-bin UNION, in store partition order. A member
        whose own pruning excludes a bin contributes an all-empty window
        set there — a zero partial, which is the additive identity, so
        per-member results equal their serial (member-pruned) runs."""
        sel = set()
        for p in plans:
            sel.update(self.prune(p))
        return [b for b in self.store.partition_bins() if b in sel]

    def _batch_ok(self, plans: List[QueryPlan], spec, bins: List[int],
                  agg_cols=()) -> bool:
        """Partition-invariant batch eligibility, decided once from the
        first non-empty pruned partition (children share the schema,
        dictionaries, and column layout). ``bins`` is the caller's
        already-computed union (pruning M plans is not free — compute it
        once, probe and scan with the same list); ``agg_cols`` must be
        the op's aggregation columns — a host-only weight column flips
        ``use_device`` off, and the probe must see it or the per-
        partition dispatches would fail where the caller expects the
        None degrade."""
        if self.mesh is not None or not self.prefer_device:
            return False
        for b in bins:
            child = self.store.child(b)
            if child is None or child.count == 0:
                continue
            ex = self._executor_for(b, child)
            bs = ex._batch_setups(plans, spec, agg_cols)
            return bs is not None
        return True  # nothing to scan: zeros for everyone

    def count_batch(self, plans: List[QueryPlan], spec):
        """M distinct counts over the partitioned store in one device
        dispatch per pruned partition (None = ineligible)."""
        bins = self._union_bins(plans)
        if not self._batch_ok(plans, spec, bins):
            return None
        M = len(plans)
        totals = [0] * M
        carrier = plans[0]

        def finish(b, p, mdev):
            for m, v in enumerate(Executor.decode_count_batch(p, M)):
                totals[m] += v

        def dispatch(ex):
            r = ex.count_batch_partial(plans, spec)
            if r is None:
                # eligibility is partition-invariant (checked up front):
                # a None here is a bug, and returning it would silently
                # DROP this partition's contribution — fail loudly into
                # the degradation contract instead
                raise RuntimeError("batched count ineligible mid-scan")
            return r

        self._additive_scan(
            carrier, "count", dispatch,
            _synced(finish), bins=bins,
        )
        return totals

    def density_batch(self, plans: List[QueryPlan], spec, bboxes,
                      width: int, height: int, weight=None):
        """M distinct heatmaps over the partitioned store (None =
        ineligible). Per-member grids reduce across partitions in the
        shared tree-merge order; a member's extra (member-pruned-away)
        partitions contribute exact-zero grids — the additive identity."""
        geom = self.store.ft.geom_field
        agg_cols = [geom + "__x", geom + "__y"] \
            + ([weight] if weight else [])
        bins = self._union_bins(plans)
        if not self._batch_ok(plans, spec, bins, agg_cols):
            return None
        M = len(plans)
        red = pdev.TreeReducer(lambda A, B: [a + b for a, b in zip(A, B)])

        def finish(b, p, mdev):
            red.push(Executor.decode_density_batch(p, M, width, height))

        def dispatch(ex):
            r = ex.density_batch_partial(plans, spec, bboxes, width,
                                         height, weight)
            if r is None:  # see count_batch: never drop silently
                raise RuntimeError("batched density ineligible mid-scan")
            return r

        self._additive_scan(
            plans[0], "density", dispatch,
            _synced(finish), bins=bins,
        )
        merged = red.result()
        if merged is None:
            return [np.zeros((height, width), np.float32)
                    for _ in range(M)]
        return merged

    def stats_batch(self, plans: List[QueryPlan], spec, stats):
        """M distinct stats scans over the partitioned store (None =
        ineligible). Per-member partials absorb in pruned-bin order —
        the exact absorb sequence each member's serial scan performs."""
        if any(not kstats.batch_supported(s) for s in stats):
            return None
        bins = self._union_bins(plans)
        if not self._batch_ok(plans, spec, bins):
            return None
        saw_ineligible = [False]

        def finish(b, p, mdev):
            Executor.absorb_stats_batch(p, stats, self.store.dicts)

        def dispatch(ex):
            if saw_ineligible[0]:
                # the batch is already doomed to the query-at-a-time
                # fallback: don't burn device passes on partitions whose
                # partials will be discarded
                return None
            r = ex.stats_batch_partials(plans, spec, stats)
            if r is None:
                # a partition whose band rows force the host path: the
                # whole batch must degrade to query-at-a-time (raising
                # here would only skip the partition under allow_partial)
                saw_ineligible[0] = True
                return None
            return r

        self._additive_scan(
            plans[0], "stats", dispatch, _synced(finish),
            bins=bins,
        )
        if saw_ineligible[0]:
            return None
        return stats

    def _stats_device_ok(self, plan: QueryPlan, stat: sk.Stat) -> bool:
        """Can every leaf of ``stat`` update on device? Decided once from
        the first non-empty pruned partition (children share the schema
        and dictionaries, so the answer is partition-invariant)."""
        for b in self.prune(plan):
            child = self.store.child(b)
            if child is None or child.count == 0:
                continue
            ex = self._executor_for(b, child)
            return ex._stats_bundle(plan, stat) is not None
        return False

    def stats(self, plan: QueryPlan, stat: sk.Stat) -> sk.Stat:
        if self._scan_devices() is not None \
                and self._stats_device_ok(plan, stat):
            # absorb in pruned-bin order — the exact sequence of
            # absorb_partials calls the serial loop performs (deferred D
            # partitions behind dispatch on the fan-out)
            self._additive_scan(
                plan, "stats",
                lambda ex: ex.stats_partials(plan, stat)[1],
                _synced(lambda b, p, mdev: kstats.absorb_partials(
                    stat, p, self.store.dicts
                )),
                push=True,  # sketches observe only matching rows
            )
            return stat
        window = self._push_window(plan)
        try:
            for b, ex in self._each(plan, window=window):
                self._scan_part(plan, b, "stats",
                                lambda: ex.stats(plan, stat))
        finally:
            self._note_pushdown_fallbacks(plan, window)
        return stat

    def features_iter(self, plan: QueryPlan, batch_rows: Optional[int] = None,
                      window: Optional[Dict] = None):
        """Stream matching rows partition-at-a-time: peak memory is one
        partition's gather, never the whole result (AbstractBatchScan /
        ArrowScan streaming contract). ``window``: an optional lake
        pruning window (``_push_window``) — spilled partitions then load
        only the row groups whose footer statistics intersect it; the
        residual filter still runs on every loaded row, so the yielded
        rows are exactly the plan's matches (``features_pushdown`` is
        the materializing wrapper that builds the window)."""
        got = 0
        limit = plan.hints.max_features if not plan.hints.sort_by else None
        for b, ex in self._each(plan, window=window):
            if resilience.partial_allowed():
                # degraded mode: materialize the partition before any yield,
                # so a failing partition drops WHOLE — never half-streamed
                batches = self._scan_part(
                    plan, b, "features",
                    lambda: list(ex.features_iter(plan, batch_rows)),
                )
                if batches is _SKIPPED:
                    continue
            else:
                # strict mode streams chunk-at-a-time (the ArrowScan
                # contract): max_features can return mid-partition without
                # gathering the rest
                resilience.fault_point("exec.partition.scan", bin=b,
                                       op="features")
                batches = ex.features_iter(plan, batch_rows)
            for batch in batches:
                if not batch.n:
                    continue
                if limit is not None:
                    if got >= limit:
                        return
                    if got + batch.n > limit:
                        keep = limit - got
                        yield ColumnBatch(
                            {k: v[:keep] for k, v in batch.columns.items()},
                            keep,
                        )
                        return
                got += batch.n
                yield batch
            if limit is not None and got >= limit:
                return

    def features(self, plan: QueryPlan) -> ColumnBatch:
        batches = list(self.features_iter(plan))
        return ColumnBatch.concat(batches) if batches else ColumnBatch({}, 0)

    def features_pushdown(self, plan: QueryPlan) -> ColumnBatch:
        """Materialize matching rows with the lake statistics window
        engaged: spilled partitions load only the row groups whose
        footer bbox/time statistics intersect the plan's extracted
        bounds (docs/LAKE.md). EXACT for row retrieval — a pruned
        group's statistics prove it holds no row inside the plan's
        bounds, so the surviving groups contain every matching row and
        the residual filter runs bit-identically on the loaded subset.
        Falls back to the plain full load whenever the window cannot
        engage (``_push_window`` returns None) or a partition cannot
        serve pruned (``_note_pushdown_fallbacks`` records those). The
        adaptive join's side scan streams the probe side through this
        per cell-group window instead of materializing it whole
        (docs/JOIN.md §10)."""
        window = self._push_window(plan)
        try:
            batches = list(self.features_iter(plan, window=window))
        finally:
            self._note_pushdown_fallbacks(plan, window)
        return ColumnBatch.concat(batches) if batches else ColumnBatch({}, 0)

    def top_batch(self, plan: QueryPlan, attr: str, descending: bool,
                  k: int, names=None,
                  include_ties: bool = False) -> Optional[ColumnBatch]:
        """Candidate rows for a sorted+limited query over the partitioned
        store: each pruned partition contributes ITS OWN device-selected
        top-k candidates (threshold select, boundary ties included when
        asked), so the union provably contains the global top-k — the
        caller's exact host sort + truncate finishes the job. Partitions
        whose device selection declines (tie overflow, NaN-keyed
        underfill) contribute their full match set instead, which is
        still a superset. The reference sorts client-side after merging
        per-partition scans (QueryPlanner.runQuery); here each partition
        ships at most k + tie-slack rows to the host."""
        parts: List[ColumnBatch] = []
        pushed = 0
        for b, ex in self._each(plan):
            def one_part(ex=ex):
                idx = ex.top_rows(plan, attr, descending, k,
                                  include_ties=include_ties)
                if idx is None:
                    return None, ex.features(plan)
                if len(idx) == 0:
                    return True, None  # device ran and found nothing
                table = ex.store.tables[plan.index_name]
                return True, table.host_gather_positions(idx, names)

            got = self._scan_part(plan, b, "top", one_part)
            if got is _SKIPPED:
                continue
            dev, batch = got
            if dev:
                pushed += 1
            if batch is not None and batch.n:
                parts.append(batch)
        if pushed == 0:
            # no partition device-selected anything: report None so the
            # caller runs (and its audit records) the plain gather path
            return None
        if not parts:
            return ColumnBatch({}, 0)
        return ColumnBatch.concat(parts)

    def knn_features(self, plan: QueryPlan, x: float, y: float,
                     k: int, boxes=None) -> ColumnBatch:
        """Per-partition top-k gathered and merged; the union of partition
        top-ks contains the global top-k (caller orders and truncates)."""
        parts = []
        for b, ex in self._each(plan):
            def one_part(ex=ex):
                idx, _ = ex.knn(plan, x, y, k, boxes=boxes)
                if len(idx) == 0:
                    return None
                table = ex.store.tables[plan.index_name]
                mask = np.zeros(table.n_shards * table.shard_len, bool)
                mask[idx] = True
                return table.host_gather(mask)

            batch = self._scan_part(plan, b, "knn", one_part)
            if batch is not _SKIPPED and batch is not None:
                parts.append(batch)
        return ColumnBatch.concat(parts) if parts else ColumnBatch({}, 0)
