"""Build one configuration's deployment from its file and a seed.

The configuration file states the source table's shape, the pipeline as a
list of steps, and the session and tier options.  This module makes the
source table from the seed and captures the pipeline through the
program's ``track`` API, one step at a time through the step's own module
(``bench/steps/<kind>.py``).  Beside the index it keeps what the reference
needs: each step's row map (per output row, the input row it comes from;
None where every row stays where it is), decided here from the generated
data and never read back from the program.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from bench import spec


@dataclasses.dataclass
class Deployment:
    index: object            # repro ProvenanceIndex
    source: str              # source dataset id
    sink: str                # sink dataset id
    n_source: int
    n_sink: int
    row_steps: List[Optional[np.ndarray]]   # per step: its row map, or None


def make_source(table_cfg: Dict, seed):
    """The source table: ``rows`` x ``cols`` float32, the first
    ``categorical`` columns small integer codes, the rest normal values,
    each cell null with probability ``null_frac``.  Every seed gives the
    same shape; only the values differ."""
    from repro.dataprep.table import Table

    rng = np.random.default_rng(seed)
    n, c, n_cat = table_cfg["rows"], table_cfg["cols"], table_cfg["categorical"]
    cols, nulls = {}, {}
    for j in range(c):
        name = f"a{j}"
        if j < n_cat:
            cols[name] = rng.integers(0, 4 + j % 5, size=n).astype(np.float32)
        else:
            cols[name] = rng.normal(0, 1 + j % 3, size=n).astype(np.float32)
        nulls[name] = rng.random(n) < table_cfg["null_frac"]
    return Table.from_columns(cols, null=nulls), cols


class SourceValues:
    """The seeded source values of the rows the pipeline holds so far, for
    a step that picks rows by value.  A column an earlier step changed
    holds other values in the program, so asking for it is an error."""

    def __init__(self, raw: Dict[str, np.ndarray]) -> None:
        self.raw = raw
        self.rows = np.arange(len(next(iter(raw.values()))))
        self.changed: set = set()

    def values(self, col: str) -> np.ndarray:
        if col in self.changed:
            raise ValueError(f"a step picks rows by {col!r} after another "
                             "step changed it: its values are not the source's")
        return self.raw[col][self.rows]

    def advance(self, step: Dict, row_map: Optional[np.ndarray]) -> None:
        if row_map is not None:
            self.rows = self.rows[row_map]
        args = step.get("args", {})
        self.changed |= set(args.get("cols", []))
        if "col" in args:
            self.changed.add(args["col"])


def capture(cfg: Dict, seed, name: str, root: str = spec.ROOT) -> Deployment:
    """Capture ``cfg["pipeline"]`` over the seeded source table."""
    from repro.core.pipeline import ProvenanceIndex
    from repro.dataprep.tracked import track

    table, raw = make_source(cfg["table"], seed)
    index = ProvenanceIndex(name)
    src = f"{name}_src"
    d = track(table, index, src)
    values = SourceValues(raw)
    row_steps: List[Optional[np.ndarray]] = []
    for step in cfg["pipeline"]:
        d, row_map = spec.module("steps", step["kind"], root).apply(
            d, step, values)
        values.advance(step, row_map)
        row_steps.append(row_map)
    sink = d.mark_sink()
    return Deployment(index, src, sink.dataset_id, table.n_rows,
                      index.datasets[sink.dataset_id].n_rows, row_steps)


def session(dep: Deployment, opts: Dict):
    """The query session the configuration states."""
    from repro.provenance import QuerySession

    if not opts:
        return dep.index.session()
    return QuerySession(dep.index, **opts)
