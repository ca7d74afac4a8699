"""Record lineage plans, with their plain reference and control.

A request probes sets of rows of the source (``fwd``: which sink rows do
they reach) or of the sink (``bwd``: which source rows do they come
from).  The reference composes the pipeline's row maps with numpy, from
the maps the benchmark itself chose (``bench/deploy.py``), and imports
nothing of the program.

The configuration's guarantee is exact lineage: every contributing row
and no other.  The control breaks it as a lossy index would: every
non-empty answer loses its last row.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def plan(dep, direction: str, rows: np.ndarray):
    """The program's plan: ``rows`` (one probe set, or a stack of them)
    of the source (``fwd``) or the sink (``bwd``)."""
    from repro.provenance import prov

    q = prov(dep.index).source(dep.source if direction == "fwd" else dep.sink)
    q = q.rows_batch(rows) if rows.ndim == 2 else q.rows(rows)
    q = q.forward().to(dep.sink) if direction == "fwd" else \
        q.backward().to(dep.source)
    return q.plan()


def probe_side(dep, direction: str) -> int:
    """How many rows a probe of ``direction`` draws from."""
    return dep.n_source if direction == "fwd" else dep.n_sink


class Reference:
    """Forward (source rows -> sink rows) and backward (sink rows ->
    source rows) record lineage, from the composed row maps."""

    def __init__(self, n_source: int,
                 row_steps: Sequence[Optional[np.ndarray]]) -> None:
        src_of = np.arange(n_source)
        for row_map in row_steps:
            if row_map is not None:
                src_of = src_of[row_map]
        self.src_of_sink = src_of

    def answer(self, direction: str, rows) -> np.ndarray:
        rows = np.asarray(rows, dtype=np.int64)
        if direction == "fwd":
            return np.flatnonzero(np.isin(self.src_of_sink, rows))
        return np.unique(self.src_of_sink[rows])


def reference(dep) -> Reference:
    return Reference(dep.n_source, dep.row_steps)


class Control(Reference):
    """The reference with every non-empty answer short of its last row."""

    def answer(self, direction: str, rows) -> np.ndarray:
        return super().answer(direction, rows)[:-1]


def control(dep) -> Control:
    return Control(dep.n_source, dep.row_steps)


def wrong(ref: Reference, served) -> int:
    """How many served answers differ from the reference's.  ``served`` is
    an iterable of ``(direction, probe rows, answer)``."""
    count = 0
    for direction, rows, got in served:
        want = ref.answer(direction, rows)
        got = np.asarray(got)
        if got.shape != want.shape or not np.array_equal(got, want):
            count += 1
    return count


class ControlSession:
    """The control in the program's place: every plan answered by
    :class:`Control`; everything else is the real session's."""

    def __init__(self, real, ref: Reference) -> None:
        self._real = real
        self._ref = ref

    def __getattr__(self, name):
        return getattr(self._real, name)

    def run(self, plan):
        per = [self._ref.answer(plan.direction, np.flatnonzero(m))
               for m in plan.rows]
        return per if plan.batched else per[0]

    def run_many(self, plans):
        return [self.run(p) for p in plans]


def control_session(real, dep) -> ControlSession:
    return ControlSession(real, control(dep))
