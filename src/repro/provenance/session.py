"""QuerySession — the planner/executor behind the lazy builder.

A session owns the physical machinery for one :class:`ProvenanceIndex`:

* the **per-op vectorized walk** (:mod:`repro.core.query` — packed-bitset
  attr propagation, one ragged CSR gather per hop covering a whole probe
  batch);
* the **composed hop-cache** (:class:`repro.core.hopcache.ComposedIndex`,
  shared with every other session on the index via
  ``ProvenanceIndex.composed()``) whose relations now sum over *all*
  producer paths of the DAG, not just the unique chain;

and picks between them per :class:`QueryPlan`:

====================  ====================================================
plan shape            strategy
====================  ====================================================
``transformations``   metadata scan (no tensors touched)
``cells`` / ``how``   vectorized walk (attr bitplanes / hop traces live
                      on the per-op pass)
record-level          composed-relation probe when the relation is already
                      cached, or when the cost model estimates amortized
                      compose-then-probe under the walk; walk otherwise
====================  ====================================================

Record-level routing is driven by :class:`repro.core.costmodel.CostModel`
(shared with the hop-cache): per-pair chain statistics feed an estimated
walk cost (hops × batched gather) vs composition cost amortized over the
cumulative probe demand seen for the pair — so a stream of tiny probes to
one far pair flips to the hop-cache once demand accumulates.  The legacy
``hopcache_min_batch`` batch-size heuristic is DEPRECATED but still honored
when passed explicitly (with a ``DeprecationWarning``).

``run_many`` additionally **fuses** submitted plans that share a fuse key
(kind, direction, endpoints, via/anchor, how, attr-presence) into ONE packed
pass: the probe mask stacks concatenate along the batch axis, a single
physical execution answers the union, and results split back per plan.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import query as Q
from repro.core.spans import span
from repro.provenance.plan import QueryPlan

__all__ = ["QuerySession", "run_many_fused"]


def _flatnonzeros(mask_stack: np.ndarray) -> List[np.ndarray]:
    return [np.flatnonzero(m) for m in mask_stack]


def run_many_fused(plans: Sequence, run_one, execute_batched,
                   counters: Dict[str, int]) -> List:
    """The shared ``run_many`` fusion contract: group plans by
    ``fuse_key()``, concatenate each group's probe mask stacks along the
    batch axis, execute ONE batched pass per group via ``execute_batched``
    (returns one payload per probe), and split results back in submission
    order.  Singleton groups and ``transformations`` plans fall back to
    ``run_one``.  Both :class:`QuerySession` and the catalog-level
    :class:`~repro.provenance.federation.FederatedSession` run on this, so
    fusion semantics cannot drift between the single-index and federated
    surfaces."""
    plans = [p if isinstance(p, QueryPlan) else p.plan() for p in plans]
    results: List = [None] * len(plans)
    groups: Dict[tuple, List[int]] = {}
    for i, p in enumerate(plans):
        groups.setdefault(p.fuse_key(), []).append(i)
    for key, idxs in groups.items():
        if len(idxs) == 1 or key[0] == "transformations":
            for i in idxs:
                results[i] = run_one(plans[i])
            continue
        sub = [plans[i] for i in idxs]
        fused = dataclasses.replace(
            sub[0],
            rows=np.concatenate([p.rows for p in sub], axis=0),
            attrs=(
                np.concatenate([p.attrs for p in sub], axis=0)
                if sub[0].attrs is not None
                else None
            ),
            batched=True,
        )
        counters["plans"] += len(idxs)
        counters["fused_groups"] += 1
        counters["fused_plans"] += len(idxs)
        per = execute_batched(fused)
        off = 0
        for i in idxs:
            p = plans[i]
            chunk = per[off : off + p.n_probes]
            off += p.n_probes
            results[i] = chunk if p.batched else chunk[0]
    return results


class QuerySession:
    """Planner + executor over one index; share one per serving tier."""

    def __init__(
        self,
        index,
        composed=None,
        *,
        use_hopcache: bool = True,
        fused_walk: Optional[bool] = None,
        hopcache_min_batch: Optional[int] = None,
    ) -> None:
        self.index = index
        self.composed = composed if composed is not None else index.composed()
        self.use_hopcache = use_hopcache
        # tri-state like use_pallas: None -> fused kernel walk iff on TPU;
        # True forces it everywhere (the parity-test path), False disables
        self.fused_walk = fused_walk
        if hopcache_min_batch is not None:
            warnings.warn(
                "hopcache_min_batch is deprecated: the QuerySession now "
                "routes record-level plans with a cost model (see "
                "repro.core.costmodel); passing hopcache_min_batch keeps the "
                "legacy batch-size heuristic for this session.",
                DeprecationWarning,
                stacklevel=2,
            )
            hopcache_min_batch = int(hopcache_min_batch)
        self.hopcache_min_batch = hopcache_min_batch
        # shared with the hop-cache so chain statistics are computed once
        self.costmodel = self.composed.costmodel
        self.counters: Dict[str, int] = {
            "plans": 0,
            "walk": 0,
            "hopcache": 0,
            "fused_walk": 0,
            # fused walks answered from a composed row map on the device
            # (chains whose every hop is structured)
            "gather_walk": 0,
            # and, over those walks, the probe rows looked up, the answer
            # rows found and the walked maps' links
            "gather_walk_rows": 0,
            "gather_walk_hits": 0,
            "gather_walk_links": 0,
            # structured chains whose answer table was too large for the
            # row map, walked through dense planes instead
            "gather_walk_declined": 0,
            # linear chains the fused walk declined for size (host walk)
            "fused_walk_oversize": 0,
            "meta": 0,
            "fused_groups": 0,
            "fused_plans": 0,
            # nanoseconds in each span of the record path (bytes for
            # walk_put_bytes): session.execute, then inside it walk.fused
            # (with walk.gather for a row-map walk, and walk.put and
            # walk.wait), walk.host or hopcache.probe
            "execute_ns": 0,
            "fused_walk_ns": 0,
            "gather_walk_ns": 0,
            "walk_put_ns": 0,
            "walk_put_bytes": 0,
            "walk_wait_ns": 0,
            "host_walk_ns": 0,
            "hopcache_probe_ns": 0,
            # the fused walk's operands kept on the device (planes: one
            # per chain and direction; row maps: one per chain): lookups,
            # LRU evictions, resident bytes
            "walk_plane_hits": 0,
            "walk_plane_misses": 0,
            "walk_plane_evictions": 0,
            "walk_plane_bytes": 0,
        }
        self.walk_planes = Q.WalkPlaneStore(self.counters)

    # -- planning --------------------------------------------------------------
    def _plan_pairs(self, plan: QueryPlan) -> Optional[List[Tuple[str, str]]]:
        """EVERY (upstream, downstream) relation the hop-cache strategy would
        probe for this plan — two legs for the co-queries, matching their
        executors — or None when the plan cannot route through the
        hop-cache."""
        if plan.kind == "record":
            return [
                (plan.source, plan.target)
                if plan.direction == "fwd"
                else (plan.target, plan.source)
            ]
        if plan.kind == "co_contributory":
            if plan.via is None:
                return None  # per-probe via needs the walk's reach map
            return [(plan.source, plan.via), (plan.target, plan.via)]
        # co_dependency: back-probe (d1, d2) then forward-probe (d1, d3)
        return [(plan.anchor, plan.source), (plan.anchor, plan.target)]

    def _choose(self, plan: QueryPlan, note: bool) -> Optional[Dict[str, object]]:
        """The cost model's verdict for a cost-routable plan — summed over
        every relation leg the hopcache strategy would compose (pricing only
        one leg of a co-query would compare the walk against half the real
        cost) — or None when the decision never reaches the cost model."""
        if self.hopcache_min_batch is not None:
            return None
        pairs = self._plan_pairs(plan)
        if pairs is None:
            return None
        uncached = [p for p in pairs if not self.composed.contains(*p)]
        if not uncached:
            return None  # every leg already composed: contains-path decides
        probe_rows = (np.count_nonzero(plan.rows) / max(plan.n_probes, 1)
                      if plan.rows is not None else 1.0)
        legs = [
            self.costmodel.choose(
                p[0], p[1], plan.n_probes, probe_rows, note=note,
                budget_bytes=self.composed.memory_budget_bytes)
            for p in uncached
        ]
        walk = sum(leg["walk_ns"] for leg in legs)
        hopcache = sum(leg["hopcache_ns"] for leg in legs)
        return {
            "strategy": "hopcache" if hopcache < walk else "walk",
            "walk_ns": walk,
            "hopcache_ns": hopcache,
            "compose_ns": sum(leg["compose_ns"] for leg in legs),
            "demand": min(leg["demand"] for leg in legs),
            "retainable": all(leg["retainable"] for leg in legs),
            # every leg estimated implicit: the hop-cache would hold this
            # plan's relations as gather arrays, not CSRs/bitplanes
            "structured": all(bool(leg.get("structured")) for leg in legs),
            "legs": legs if len(legs) > 1 else None,
        }

    def _strategy(self, plan: QueryPlan, note: bool = True) -> str:
        if plan.kind == "transformations":
            return "meta"
        if plan.kind == "cells" or plan.how:
            return "walk"  # attr bitplanes / hop traces live on the walk
        if not self.use_hopcache:
            return "walk"
        pairs = self._plan_pairs(plan)
        if pairs is None:
            return "walk"
        if all(self.composed.contains(*p) for p in pairs):
            return "hopcache"  # relations already composed: probe them
        if self.hopcache_min_batch is not None:  # deprecated legacy heuristic
            return ("hopcache" if plan.n_probes >= self.hopcache_min_batch
                    else "walk")
        return self._choose(plan, note)["strategy"]

    def explain(self, plan: QueryPlan) -> Dict[str, object]:
        """The planner's choice for ``plan``, without executing it (and
        without advancing the cost model's per-pair demand counters).
        Includes the cost model's estimates when they decided the routing.
        """
        out: Dict[str, object] = {"plan": plan.describe()}
        cost = None
        if plan.kind not in ("transformations", "cells") and not plan.how \
                and self.use_hopcache:
            cost = self._choose(plan, note=False)
        if cost is not None:
            out["strategy"] = cost["strategy"]
            out["cost"] = cost
        else:
            out["strategy"] = self._strategy(plan, note=False)
        pairs = None
        if plan.kind not in ("transformations", "cells"):
            pairs = self._plan_pairs(plan)
        if pairs is not None:
            # spill-tier residency per relation leg: "ram" / "spilled" (one
            # mmap fault on first probe) / "uncomposed" — no LRU touch
            out["residency"] = [
                {"pair": p, "state": self.composed.residency(*p)
                 or "uncomposed"}
                for p in pairs
            ]
        # where the routing constants came from (default vs calibration file)
        from repro.core.costmodel import constants_provenance

        out["constants"] = constants_provenance()
        return out

    def _fused_walk_on(self) -> bool:
        """Resolve the tri-state ``fused_walk`` flag; the None default means
        "fused kernel iff on TPU"."""
        if self.fused_walk is not None:
            return bool(self.fused_walk)
        from repro.kernels import ops as K

        return K.on_tpu()

    # -- execution -------------------------------------------------------------
    def run(self, plan: QueryPlan):
        """Execute one plan.  Single-probe plans return legacy-shaped results
        (one index array / cell list / ``(recs, hops)``); batched plans
        return one such result per probe."""
        self.counters["plans"] += 1
        if plan.kind == "transformations":
            self.counters["meta"] += 1
            return self._exec_transformations(plan)
        per = self._execute(plan)
        return per if plan.batched else per[0]

    def run_many(self, plans: Sequence) -> List:
        """Execute a batch of plans, fusing same-fuse-key plans into one
        physical pass each.  Results come back in submission order."""
        return run_many_fused(plans, self.run, self._execute, self.counters)

    def run_masks(self, plan: QueryPlan) -> np.ndarray:
        """Execute a plain record-level plan and return the RAW ``(B,
        n_target)`` boolean mask stack — no per-probe index conversion.

        The federation's per-segment entry point: intermediate segment
        results feed straight into the next boundary stitch, so
        materializing index arrays per probe would be pure overhead.
        Routing and counters are identical to :meth:`run` (they share
        :meth:`_record_masks`, the one record executor).
        """
        if plan.kind != "record" or plan.how:
            raise ValueError("run_masks handles plain record plans only")
        self.counters["plans"] += 1
        with span("session.execute", self.counters, "execute_ns"):
            strategy = self._strategy(plan)
            self.counters[strategy] += 1
            return self._record_masks(plan, strategy)

    def run_record_terms(self, entry_masks: Dict[str, np.ndarray],
                         direction: str, collect_hops: bool = False):
        """Record propagation from MULTIPLE seed datasets in one pass.

        The federation's how-provenance segment hook: ``entry_masks`` maps
        dataset id -> ``(B, n_rows)`` bool probe stacks, and the return is
        the full reachable ``{dataset: (B, n) bool}`` dict (plus per-probe
        :class:`~repro.core.query.Hop` traces with ``collect_hops``).
        Seeding every boundary entry at once keeps the hop trace identical
        to a merged index's single walk — per entry/exit passes would
        re-record shared ops.  Always walks (hop traces live on the
        per-op pass).
        """
        self.counters["plans"] += 1
        self.counters["walk"] += 1
        return Q.record_masks_terms_batch(self.index, entry_masks, direction,
                                          collect_hops=collect_hops)

    def run_attr_terms(self, entry_terms, direction: str,
                       collect_hops: bool = False):
        """Attr-term propagation from MULTIPLE seed datasets in one pass.

        The federation's cells/how segment hook (the attribute-level
        analogue of :meth:`run_masks`): ``entry_terms`` maps dataset id ->
        lists of ``((B, n_rows) bool, (B, nw) uint32)`` packed terms, and
        the return is the full reachable terms dict plus per-probe hop
        traces (``(terms, B, hops)`` with ``collect_hops``, else
        ``(terms, B)``).  Attr bitplanes live on the per-op walk, so this
        never routes through the hop-cache.
        """
        self.counters["plans"] += 1
        self.counters["walk"] += 1
        return Q.attr_propagate_terms_batch(self.index, entry_terms,
                                            direction,
                                            collect_hops=collect_hops)

    # -- executors (each returns one payload per probe) -------------------------
    def _execute(self, plan: QueryPlan) -> List:
        with span("session.execute", self.counters, "execute_ns"):
            return self._execute_plan(plan)

    def _execute_plan(self, plan: QueryPlan) -> List:
        strategy = self._strategy(plan)
        self.counters[strategy] += 1
        if plan.kind == "record":
            return self._exec_record(plan, strategy)
        if plan.kind == "cells":
            return self._exec_cells(plan)
        if plan.kind == "co_contributory":
            return self._exec_co_contributory(plan, strategy)
        if plan.kind == "co_dependency":
            return self._exec_co_dependency(plan, strategy)
        raise ValueError(f"unexpected plan kind {plan.kind!r}")

    def _record_masks(self, plan: QueryPlan, strategy: str,
                      per_probe: bool = False):
        """The one plain-record executor: (B, n_target) bool per strategy.
        Both :meth:`run` (via ``_exec_record``) and :meth:`run_masks` (the
        federation's segment hook) answer through this, so routing and
        fallback shapes cannot diverge between the two surfaces.  With
        ``per_probe`` a fused walk may answer a list of each probe's rows
        instead (the gather route, which then makes no dense stack)."""
        if strategy == "hopcache":
            probe = (self.composed.probe_forward if plan.direction == "fwd"
                     else self.composed.probe_backward)
            with span("hopcache.probe", self.counters, "hopcache_probe_ns"):
                return probe(plan.rows, plan.source, plan.target)
        if self._fused_walk_on():
            fused = Q.fused_walk_record_masks_batch(
                self.index, plan.source, plan.target, plan.rows,
                plan.direction, counters=self.counters,
                planes=self.walk_planes, per_probe=per_probe,
            )
            if fused is not None:  # non-linear chains fall through to the walk
                self.counters["fused_walk"] += 1
                return fused
        walker = (
            Q.forward_record_masks_batch
            if plan.direction == "fwd"
            else Q.backward_record_masks_batch
        )
        with span("walk.host", self.counters, "host_walk_ns"):
            masks = walker(self.index, plan.source, plan.rows)
        return masks.get(
            plan.target,
            np.zeros((plan.n_probes, self.index.datasets[plan.target].n_rows),
                     dtype=bool),
        )

    def _exec_record(self, plan: QueryPlan, strategy: str) -> List:
        if not plan.how:
            out = self._record_masks(plan, strategy, per_probe=True)
            return out if isinstance(out, list) else _flatnonzeros(out)
        # how-traces only live on the walk (see _strategy)
        walker = (
            Q.forward_record_masks_batch
            if plan.direction == "fwd"
            else Q.backward_record_masks_batch
        )
        masks, hops = walker(self.index, plan.source, plan.rows,
                             collect_hops=True)
        out = masks.get(
            plan.target,
            np.zeros((plan.n_probes, self.index.datasets[plan.target].n_rows),
                     dtype=bool),
        )
        return list(zip(_flatnonzeros(out), hops))

    def _exec_cells(self, plan: QueryPlan) -> List:
        B = plan.n_probes
        ds = self.index.datasets[plan.target]
        if plan.how:
            terms, _, hops = Q._attr_propagate_batch(
                self.index, plan.source, plan.rows, plan.attrs, plan.direction,
                collect_hops=True,
            )
        else:
            terms, _ = Q._attr_propagate_batch(
                self.index, plan.source, plan.rows, plan.attrs, plan.direction
            )
        cells = Q._cells_batch(terms.get(plan.target, []), B, ds.n_rows, ds.n_cols)
        if plan.how:
            return list(zip(cells, hops))
        return cells

    def _exec_co_contributory(self, plan: QueryPlan, strategy: str) -> List:
        d1, d2, via = plan.source, plan.target, plan.via
        if strategy == "hopcache":
            via_masks = self.composed.probe_forward(plan.rows, d1, via)
            back = self.composed.probe_backward(via_masks, via, d2)
            return _flatnonzeros(back)
        B = plan.n_probes
        fwd = Q.forward_record_masks_batch(self.index, d1, plan.rows)
        results: List[np.ndarray] = [np.zeros(0, dtype=np.int64)] * B
        groups: Dict[str, List[int]] = {}
        for b in range(B):
            v = via if via is not None else Q._pick_via(self.index, d1, d2, fwd, b)
            if v is None or v not in fwd or not fwd[v][b].any():
                continue
            groups.setdefault(v, []).append(b)
        for v, bs in groups.items():
            back = Q.backward_record_masks_batch(self.index, v, fwd[v][bs])
            if d2 not in back:
                continue
            for i, b in enumerate(bs):
                results[b] = np.flatnonzero(back[d2][i])
        return results

    def _exec_co_dependency(self, plan: QueryPlan, strategy: str) -> List:
        d2, d1, d3 = plan.source, plan.anchor, plan.target
        B = plan.n_probes
        if strategy == "hopcache":
            anc = self.composed.probe_backward(plan.rows, d2, d1)
            fwd = self.composed.probe_forward(anc, d1, d3)
            return _flatnonzeros(fwd)
        back = Q.backward_record_masks_batch(self.index, d2, plan.rows)
        empty = [np.zeros(0, dtype=np.int64)] * B
        if d1 not in back or not back[d1].any():
            return list(empty)
        fwd = Q.forward_record_masks_batch(self.index, d1, back[d1])
        if d3 not in fwd:
            return list(empty)
        return _flatnonzeros(fwd[d3])

    def _exec_transformations(self, plan: QueryPlan) -> List[Dict]:
        return [
            {
                "op_id": op.op_id,
                "op": op.info.op_name,
                "category": op.info.category.value,
                "contextual": op.info.contextual,
                "inputs": op.input_ids,
                "output": op.output_id,
            }
            for op in self.index.upstream_ops(plan.source)
        ]

    # -- introspection ----------------------------------------------------------
    def stats(self) -> Dict:
        """Planner counters + the shared hop-cache's counters
        (hits/misses/evictions/bytes) — assert on these to catch
        cache-routing regressions.  ``index`` names the owning index so a
        federation can aggregate per-index stats attributably."""
        return {"index": self.index.name, "planner": dict(self.counters),
                "hopcache": self.composed.stats()}
