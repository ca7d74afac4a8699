"""The ProvenanceIndex — the paper's Figure 2 model, array-resident.

Holds, per pipeline: dataset records, operation records with precedence
(a DAG), each operation's provenance tensor and schema annotations, and the
materialization policy (§III-E): source/sink datasets always kept, inputs of
*contextual* operations materialized, everything else recomputable.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.capture import build_tensor
from repro.core.opcat import CaptureInfo, OpCategory
from repro.core.provtensor import ProvTensor
from repro.dataprep.table import Table

__all__ = ["DatasetRecord", "OpRecord", "ProvenanceIndex"]


@dataclasses.dataclass
class DatasetRecord:
    dataset_id: str
    n_rows: int
    n_cols: int
    columns: List[str]
    table: Optional[Table] = None       # materialized content (policy-driven)
    is_source: bool = False
    is_sink: bool = False

    @property
    def materialized(self) -> bool:
        return self.table is not None


@dataclasses.dataclass
class OpRecord:
    op_id: int
    info: CaptureInfo
    tensor: ProvTensor
    input_ids: List[str]
    output_id: str


class ProvenanceIndex:
    """In-memory (in-HBM when sharded) index of one pipeline's provenance."""

    def __init__(self, name: str = "pipeline", spill=None) -> None:
        self.name = name
        self.datasets: Dict[str, DatasetRecord] = {}
        self.ops: List[OpRecord] = []
        self.producer: Dict[str, int] = {}          # dataset -> producing op
        self.consumers: Dict[str, List[int]] = {}   # dataset -> consuming ops
        self.version = 0                            # bumped per recorded op;
        self._composed = None                       # hop-caches key on it
        self._session = None                        # shared QuerySession
        self._record_hooks: List = []               # capture observers
        # out-of-core op-tensor residency (None = everything stays in RAM):
        # accepts True / a path / a SpillStore / a SpillPolicy — cold tensors
        # serialize to the compact on-disk log and fault back on access
        if spill is not None and spill is not False:
            from repro.core.spill import TensorSpiller, resolve_spill

            self._spill = TensorSpiller(self, resolve_spill(spill))
        else:
            self._spill = None

    # -- capture hooks ---------------------------------------------------------
    def add_record_hook(self, fn):
        """Register a capture observer called on every :meth:`record`, after
        input validation and BEFORE the provenance tensor is built, as
        ``fn(input_ids, output_id, out_table, info, input_tables)``.

        This is the supported way to mirror the capture stream into a second
        system (the Chapman baseline in the benches, an audit log, a metrics
        sink) — replacing the old ``idx.record = wrapper`` monkeypatching,
        which silently broke whenever ``record`` grew a parameter.  Returns
        ``fn`` so it can be used as a decorator."""
        self._record_hooks.append(fn)
        return fn

    def remove_record_hook(self, fn) -> None:
        """Unregister a hook added with :meth:`add_record_hook`."""
        self._record_hooks.remove(fn)

    # -- registration ---------------------------------------------------------
    def add_source(self, dataset_id: str, table: Table) -> str:
        """Pipeline input datasets are always materialized (paper §III-E)."""
        self.datasets[dataset_id] = DatasetRecord(
            dataset_id=dataset_id,
            n_rows=table.n_rows,
            n_cols=table.n_cols,
            columns=list(table.columns),
            table=table,
            is_source=True,
        )
        return dataset_id

    def record(
        self,
        input_ids: Sequence[str],
        output_id: str,
        out_table: Table,
        info: CaptureInfo,
        keep_output: bool = False,
        input_tables: Optional[Sequence[Table]] = None,
    ) -> str:
        """Register one executed operation.  ``keep_output`` marks pipeline
        sinks (always materialized).  ``input_tables`` lets the caller hand
        over inputs so the §III-E policy can materialize them for contextual
        ops (TrackedTable always passes them)."""
        if output_id in self.datasets:
            # every dataset has exactly ONE producer; silently overwriting
            # would leave both ops in the DAG and corrupt every walk (and the
            # hop-cache's keep-on-append invalidation policy relies on it)
            raise ValueError(
                f"{info.op_name}: output dataset {output_id!r} already exists"
            )
        for k, d in enumerate(input_ids):
            if d not in self.datasets:
                raise KeyError(f"unknown input dataset {d}")
            if self.datasets[d].n_rows != info.n_in[k]:
                raise ValueError(
                    f"{info.op_name}: input {d} has {self.datasets[d].n_rows} rows, "
                    f"capture says {info.n_in[k]}"
                )
        for hook in self._record_hooks:
            hook(list(input_ids), output_id, out_table, info, input_tables)
        tensor = build_tensor(info)
        op = OpRecord(
            op_id=len(self.ops),
            info=info,
            tensor=tensor,
            input_ids=list(input_ids),
            output_id=output_id,
        )
        self.ops.append(op)
        if self._spill is not None:
            self._spill.on_record(op)
        self.version += 1
        self.producer[output_id] = op.op_id
        for d in input_ids:
            self.consumers.setdefault(d, []).append(op.op_id)
        self.datasets[output_id] = DatasetRecord(
            dataset_id=output_id,
            n_rows=out_table.n_rows,
            n_cols=out_table.n_cols,
            columns=list(out_table.columns),
            table=out_table if keep_output else None,
            is_sink=keep_output,
        )
        # materialization policy: contextual ops keep their INPUT datasets
        if info.contextual:
            for k, d in enumerate(input_ids):
                rec = self.datasets[d]
                if rec.table is None:
                    if input_tables is not None and input_tables[k] is not None:
                        rec.table = input_tables[k]
                    else:
                        raise RuntimeError(
                            f"contextual op {info.op_name} needs materialized input {d}; "
                            "pass input_tables (TrackedTable does this automatically)"
                        )
        return output_id

    # -- graph helpers ---------------------------------------------------------
    def downstream_ops(self, dataset_id: str) -> List[OpRecord]:
        """Ops reachable forward from ``dataset_id``, topologically ordered
        (op registration order is already topological — pipelines execute in
        precedence order)."""
        reach = {dataset_id}
        out = []
        for op in self.ops:
            if any(d in reach for d in op.input_ids):
                out.append(op)
                reach.add(op.output_id)
        return out

    def upstream_ops(self, dataset_id: str) -> List[OpRecord]:
        """Ops contributing to ``dataset_id``, topologically ordered."""
        reach = {dataset_id}
        out = []
        for op in reversed(self.ops):
            if op.output_id in reach:
                out.append(op)
                reach.update(op.input_ids)
        return list(reversed(out))

    def path_exists(self, src: str, dst: str) -> bool:
        if src == dst:
            return True
        reach = {src}
        for op in self.ops:
            if any(d in reach for d in op.input_ids):
                reach.add(op.output_id)
        return dst in reach

    def sources(self) -> List[str]:
        return [d for d, r in self.datasets.items() if r.is_source]

    def sinks(self) -> List[str]:
        produced = set(self.producer)
        consumed = set(self.consumers)
        return [d for d in produced if d not in consumed]

    def composed(self, **kwargs):
        """The index's shared :class:`~repro.core.hopcache.ComposedIndex`.

        Created lazily (late import — hopcache builds on compose which builds
        on this module); pass kwargs (e.g. ``memory_budget_bytes``) on first
        call to configure it."""
        from repro.core.hopcache import ComposedIndex  # circular at module scope

        if self._composed is None:
            self._composed = ComposedIndex(self, **kwargs)
        elif kwargs:
            raise ValueError("composed() already configured; use index.composed()")
        return self._composed

    def session(self, **kwargs):
        """The index's shared :class:`~repro.provenance.session.QuerySession`
        — the planner/executor behind ``repro.provenance.prov(index)`` and
        the legacy ``q1``-``q11`` shims.  It wraps :meth:`composed`, so every
        caller (examples, the serving tier, ``bench/``) probes the same composed
        relations.  Pass kwargs (e.g. ``hopcache_min_batch``) on first call
        to configure it."""
        from repro.provenance import QuerySession  # circular at module scope

        if self._session is None:
            self._session = QuerySession(self, **kwargs)
        elif kwargs:
            raise ValueError("session() already configured; use index.session()")
        return self._session

    def export(self, dataset_id: str):
        """Mint a read-only :class:`~repro.provenance.catalog.BoundaryHandle`
        over ``dataset_id`` — the capability another party (a serving tier,
        a downstream pipeline's catalog) registers to trace lineage back
        through this index WITHOUT receiving the index itself.  The handle
        can probe relations among the ancestors of the boundary dataset and
        nothing else: no ``record()``/``add_source()``, no non-ancestor
        datasets.  The ancestor closure is fixed at export time (the op DAG
        is append-only with one producer per dataset, so no later write can
        extend an existing dataset's ancestry)."""
        from repro.provenance.catalog import BoundaryHandle  # circular at module scope

        return BoundaryHandle(self, dataset_id)

    # -- memory accounting (Table IX / Table XI) --------------------------------
    def prov_nbytes(self) -> int:
        """Bytes of the provenance encoding proper: tensors (COO + built CSR
        halves) + schema bitsets/permutation lists.  Materialized datasets are
        NOT provenance — they are accounted separately."""
        total = 0
        for op in self.ops:
            total += op.tensor.nbytes()
            for amap in op.info.attr_maps:
                total += amap.nbytes()
        return total

    def materialized_nbytes(self) -> int:
        return sum(r.table.nbytes() for r in self.datasets.values() if r.table is not None)

    def stats(self) -> Dict[str, float]:
        out = {
            "ops": len(self.ops),
            "datasets": len(self.datasets),
            "prov_bytes": self.prov_nbytes(),
            "materialized_bytes": self.materialized_nbytes(),
            "nnz": sum(op.tensor.nnz for op in self.ops),
        }
        if self._spill is not None:
            out["spill"] = self._spill.stats()
        return out
