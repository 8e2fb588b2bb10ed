"""Query planner: (filter, hints) -> (index choice, scan windows, compiled
predicate, aggregation program).

Pipeline parity with the reference (SURVEY.md §3.1 call stack):
``configureQuery`` (hints + filter optimize) -> ``FilterSplitter`` (candidate
indices) -> ``CostBasedStrategyDecider`` (stats-estimated counts,
StrategyDecider.scala:79-191) -> key space ranges -> guards
(FullTableScanQueryGuard / TemporalQueryGuard analogs) -> QueryPlan.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from geomesa_tpu import config
from geomesa_tpu.filter import compile_filter, ir, parse_ecql
from geomesa_tpu.filter.compile import CompiledFilter
from geomesa_tpu.index.keyspace import (
    AttributeKeySpace, IdKeySpace, KeyPlan, XZ2KeySpace, XZ3KeySpace,
    Z2KeySpace, Z3KeySpace,
)
from geomesa_tpu.index.store import FeatureStore
from geomesa_tpu.planning.explain import Explainer
from geomesa_tpu.stats import sketches as sk


@dataclass
class QueryHints:
    """Per-query hints (the reference's QueryHints surface, SURVEY.md §5)."""

    #: force a specific index by name (QUERY_INDEX hint)
    query_index: Optional[str] = None
    #: skip fine predicate when the key filter is sufficient (LOOSE_BBOX)
    loose_bbox: bool = False
    #: 1-in-n sampling (SAMPLING hint)
    sampling: Optional[int] = None
    #: per-key sampling attribute (SAMPLE_BY hint): 1-in-n per key value
    sample_by: Optional[str] = None
    #: max features
    max_features: Optional[int] = None
    #: attribute projection
    properties: Optional[List[str]] = None
    #: sort: list of (attribute, descending)
    sort_by: Optional[List[tuple]] = None


@dataclass
class QueryPlan:
    """Everything the executor needs (reference QueryPlan.scala:30-94)."""

    schema: str
    filter: ir.Filter
    ecql: str
    compiled: CompiledFilter
    key_plan: KeyPlan
    index_name: str
    hints: QueryHints
    explain: Explainer
    est_count: float = 0.0

    @property
    def is_empty(self) -> bool:
        return self.key_plan.disjoint or isinstance(self.filter, ir.Exclude)


class QueryPlanner:
    """Plans queries for one FeatureStore (QueryPlanner.scala:36 analog)."""

    def __init__(self, store: FeatureStore):
        self.store = store

    def plan(
        self,
        ecql: "str | ir.Filter" = "INCLUDE",
        hints: Optional[QueryHints] = None,
        explain: Optional[Explainer] = None,
    ) -> QueryPlan:
        store = self.store
        ft = store.ft
        hints = hints or QueryHints()
        exp = explain or Explainer(enabled=False)

        if isinstance(ecql, ir.Filter):
            f, text = ecql, "<ir>"
        else:
            text = ecql
            f = parse_ecql(ecql)
        exp.push(f"Planning '{ft.name}' query")
        exp.line(f"Filter: {text}")

        # pluggable rewrite hooks (QueryInterceptor.scala:51 analog)
        from geomesa_tpu.planning import interceptors

        f2 = interceptors.apply_rewrite(ft, f)
        if f2 is not f:
            exp.line("Filter rewritten by interceptor")
            f = f2

        # candidate key plans (FilterSplitter.getQueryOptions analog)
        candidates = []
        for ks in store.keyspaces:
            if hints.query_index and ks.name != hints.query_index:
                continue
            kp = ks.plan(ft, f)
            if kp is not None:
                candidates.append(kp)
        if not candidates:
            if hints.query_index:
                raise ValueError(
                    f"index {hints.query_index!r} cannot serve this query"
                )
            # full scan on the first index
            kp = KeyPlan(store.keyspaces[0], full_scan=True)
            candidates = [kp]

        exp.push(f"Candidate indices: {[c.keyspace.name for c in candidates]}")
        chosen, cost = self._decide(candidates, f, exp)
        exp.pop()
        exp.line(
            f"Chosen index: {chosen.keyspace.name} "
            f"(estimated count {cost:.0f}, {len(chosen.lo)} ranges"
            + (f", {len(chosen.bins)} time bins" if chosen.bins is not None else "")
            + ")"
        )

        self._guard(chosen, f, exp)

        compiled = compile_filter(f, ft, store.dicts)
        exp.line(f"Predicate columns: {compiled.columns}")
        exp.pop()
        plan = QueryPlan(
            schema=ft.name, filter=f, ecql=text, compiled=compiled,
            key_plan=chosen, index_name=chosen.keyspace.name, hints=hints,
            explain=exp, est_count=cost,
        )
        # pluggable guard hooks may veto the chosen plan (raise)
        interceptors.apply_guards(ft, plan)
        return plan

    # -- cost-based decider (StrategyDecider.scala:148-191 analog) ---------
    def _decide(self, candidates: List[KeyPlan], f: ir.Filter, exp: Explainer):
        store = self.store
        total = float(store.count)
        if config.STRATEGY_DECIDER.get() != "cost" and candidates:
            return candidates[0], total
        best, best_cost = None, None
        for kp in candidates:
            cost = self._estimate(kp, f, total)
            # index preference multipliers: id lookups cheapest, then
            # temporal+spatial, spatial, attribute (mirrors the reference's
            # per-index cost multipliers)
            mult = {
                "id": 0.5, "z3": 1.0, "xz3": 1.0, "s3": 1.0,
                "z2": 1.5, "xz2": 1.5, "s2": 1.5, "attr": 2.0,
            }.get(kp.keyspace.kind, 2.0)
            weighted = cost * mult if not kp.disjoint else -1.0
            exp.line(f"{kp.keyspace.name}: estimated {cost:.0f} (weighted {weighted:.0f})")
            if best_cost is None or weighted < best_cost:
                best, best_cost = kp, weighted
        return best, max(best_cost, 0.0)

    def _estimate(self, kp: KeyPlan, f: ir.Filter, total: float) -> float:
        store = self.store
        if kp.disjoint:
            return 0.0
        if kp.full_scan:
            return total
        name = kp.keyspace.kind
        if name in ("z3", "xz3") and kp.bins is not None:
            z3h = store.stats.get("z3-histogram")
            if isinstance(z3h, sk.Z3HistogramStat) and not z3h.is_empty and name == "z3":
                return z3h.estimate_count(kp.bins, kp.ranges)
            return total * kp.coverage
        if name == "z2":
            z2h = store.stats.get("z2-histogram")
            if isinstance(z2h, sk.Z2HistogramStat) and not z2h.is_empty:
                return z2h.estimate_count(kp.ranges)
            return total * min(1.0, kp.coverage * 4)
        if name == "xz2":
            return total * min(1.0, kp.coverage * 4)
        if name == "id":
            return float(len(getattr(kp, "_ids", ())))
        if name == "attr":
            attr = kp.keyspace.attr
            enum = store.stats.get(f"enum-{attr}")
            if isinstance(enum, sk.EnumerationStat) and not enum.is_empty:
                est = 0.0
                d = store.dicts.get(attr)
                for lo, hi in getattr(kp, "_bounds", []):
                    if lo == hi and d is not None:
                        est += enum.counts.get(d.code_of(str(lo)), 0)
                    else:
                        est += total * 0.1
                return est
            mm = store.stats.get(f"minmax-{attr}")
            if isinstance(mm, sk.MinMax) and not mm.is_empty:
                span = float(mm.hi) - float(mm.lo) or 1.0
                est = 0.0
                for lo, hi in getattr(kp, "_bounds", []):
                    lo2 = float(mm.lo) if lo is None else float(lo)
                    hi2 = float(mm.hi) if hi is None else float(hi)
                    est += total * max(0.0, min(hi2, float(mm.hi)) - max(lo2, float(mm.lo))) / span
                return est
            return total * 0.1
        return total * kp.coverage

    # -- guards (QueryInterceptor.guard analogs) ---------------------------
    def _guard(self, kp: KeyPlan, f: ir.Filter, exp: Explainer):
        if kp.full_scan and config.BLOCK_FULL_TABLE_SCANS.to_bool():
            raise ValueError(
                "full-table scan blocked (geomesa.scan.block-full-table=true); "
                "add spatial/temporal/attribute predicates"
            )
        max_days = config.TEMPORAL_GUARD_MAX_DAYS.to_int()
        if max_days and self.store.ft.dtg_field:
            iv = ir.extract_intervals(f, self.store.ft.dtg_field)
            if iv.is_empty:
                raise ValueError(
                    f"temporal guard: query must constrain {self.store.ft.dtg_field!r}"
                )
            span_ms = sum(hi - lo for lo, hi in iv.values)
            if span_ms > max_days * 86_400_000:
                raise ValueError(
                    f"temporal guard: query spans {span_ms / 86_400_000:.1f} days "
                    f"> limit {max_days}"
                )
