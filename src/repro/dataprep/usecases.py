"""The paper's three use-case pipelines + TPC-DI-style synthetic join data.

Shapes follow Table VIII exactly:

  German  4 ops  1000  rows  21 attrs ->  1000 rows  60 attrs
  Compas  7 ops  7214  rows  53 attrs ->  6907 rows   8 attrs
  Census  5 ops  32561 rows  15 attrs -> 32561 rows 104 attrs

Data content is synthetic (the originals are external downloads; offline
container), but the OPERATION MIX matches the published pipelines: impute /
normalize / onehot for German-credit-style categorical expansion, filtering +
column pruning for Compas, heavy one-hot expansion for Census.  The TPC-DI
generator reproduces Table XI's join cardinalities per scale factor.  The
deep chain is a linear many-op pipeline over one table, the shape the fused
K-hop walk and multi-hop composition are measured on.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import numpy as np

from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import TrackedTable, track

__all__ = [
    "make_german",
    "make_compas",
    "make_census",
    "run_german",
    "run_compas",
    "run_census",
    "make_tpcdi_join_inputs",
    "make_chain_table",
    "chain_step",
    "build_deep_chain",
    "USECASES",
    "TPCDI_SCALES",
]


def _rand_table(n_rows: int, n_cols: int, n_cat: int, seed: int, null_frac: float = 0.02) -> Table:
    rng = np.random.default_rng(seed)
    cols = {}
    nulls = {}
    for j in range(n_cols):
        name = f"a{j}"
        if j < n_cat:
            cols[name] = rng.integers(0, 4 + j % 5, size=n_rows).astype(np.float32)
        else:
            cols[name] = rng.normal(0, 1 + j % 3, size=n_rows).astype(np.float32)
        nulls[name] = rng.random(n_rows) < null_frac
    t = Table.from_columns(cols, null=nulls)
    return t


# ---------------------------------------------------------------------------
# German credit: 1000 x 21 -> 1000 x 60 in 4 ops
# ---------------------------------------------------------------------------
def make_german(seed: int = 0) -> Table:
    return _rand_table(1000, 21, n_cat=13, seed=seed)


def run_german(index: ProvenanceIndex, t: Table) -> TrackedTable:
    d = track(t, index, "german_src")
    d = d.impute([f"a{j}" for j in range(13, 21)], strategy="mean")         # 1
    d = d.normalize([f"a{j}" for j in range(13, 21)], kind="zscore")        # 2
    d = d.onehot("a0", n_values=9)                                          # 3: 21+9=30
    d = d.onehot("a1", n_values=30)                                         # 4: 30+30=60
    return d.mark_sink()


# ---------------------------------------------------------------------------
# Compas: 7214 x 53 -> 6907 x 8 in 7 ops
# ---------------------------------------------------------------------------
def make_compas(seed: int = 1) -> Table:
    return _rand_table(7214, 53, n_cat=20, seed=seed)


def run_compas(index: ProvenanceIndex, t: Table) -> TrackedTable:
    d = track(t, index, "compas_src")
    d = d.impute(["a25", "a30"], strategy="median")                          # 1
    # keep the top-6907 rows by a21 (value-driven threshold, exact Table VIII count)
    vals = np.asarray(d.table.col("a21"))
    thresh = np.partition(vals, len(vals) - 6907)[len(vals) - 6907]
    kept = np.flatnonzero(vals >= thresh)[:6907]
    m2 = np.zeros(len(vals), dtype=bool)
    m2[kept] = True
    d = d.filter_rows(m2, op_name="filter:days_b_screening")                 # 2 -> 6907 rows
    d = d.value_transform("a22", "clip", lo=-3.0, hi=3.0)                    # 3
    d = d.binarize("a23", threshold=0.0)                                     # 4
    d = d.discretize("a24", n_bins=4, kind="quantile")                       # 5
    d = d.normalize(["a22"], kind="minmax")                                  # 6
    d = d.select_columns([f"a{j}" for j in (0, 5, 21, 22, 23, 24, 25, 30)])  # 7 -> 8 attrs
    return d.mark_sink()


# ---------------------------------------------------------------------------
# Census (adult): 32561 x 15 -> 32561 x 104 in 5 ops
# ---------------------------------------------------------------------------
def make_census(seed: int = 2) -> Table:
    return _rand_table(32561, 15, n_cat=9, seed=seed)


def run_census(index: ProvenanceIndex, t: Table) -> TrackedTable:
    d = track(t, index, "census_src")
    d = d.impute([f"a{j}" for j in range(9, 15)], strategy="mean")           # 1
    d = d.normalize([f"a{j}" for j in range(9, 15)], kind="zscore")          # 2
    d = d.onehot("a0", n_values=9)                                           # 3: 15+9=24
    d = d.onehot("a1", n_values=16)                                          # 4: 24+16=40
    d = d.onehot("a2", n_values=64)                                          # 5: 40+64=104
    return d.mark_sink()


USECASES: Dict[str, Tuple[Callable[[int], Table], Callable]] = {
    "german": (make_german, run_german),
    "compas": (make_compas, run_compas),
    "census": (make_census, run_census),
}


# ---------------------------------------------------------------------------
# TPC-DI-like synthetic join inputs (Table XI cardinalities per scale factor)
# ---------------------------------------------------------------------------
TPCDI_SCALES = {
    3: (362342, 390978),
    5: (602956, 650412),
    9: (1085239, 1171107),
    15: (1807703, 1951236),
    20: (2411006, 2601648),
}


def make_tpcdi_join_inputs(scale: int, seed: int = 7, n_attrs: int = 8) -> Tuple[Table, Table]:
    """Two key-sharing tables whose inner join has ~|left| matches (each left
    row matches one right row, mirroring the DimTrade/DimSecurity-style
    surrogate-key joins TPC-DI performs)."""
    n_l, n_r = TPCDI_SCALES[scale]
    rng = np.random.default_rng(seed)
    # left keys: subset of right key space (1:1 matches, some dangling rights)
    right_keys = np.arange(n_r, dtype=np.float32)
    left_keys = rng.choice(n_r, size=n_l, replace=False).astype(np.float32) \
        if n_l <= n_r else rng.integers(0, n_r, size=n_l).astype(np.float32)
    lcols = {"key": left_keys}
    rcols = {"key": right_keys}
    for j in range(n_attrs - 1):
        lcols[f"l{j}"] = rng.normal(size=n_l).astype(np.float32)
        rcols[f"r{j}"] = rng.normal(size=n_r).astype(np.float32)
    return Table.from_columns(lcols), Table.from_columns(rcols)


# ---------------------------------------------------------------------------
# Deep linear chain (multi-hop composition / fused-walk workload)
# ---------------------------------------------------------------------------
def chain_step(d: TrackedTable, i: int) -> TrackedTable:
    """Op ``i`` of the deterministic deep chain (replayable: the same step
    sequence builds a merged AND a federated variant identically)."""
    kind = i % 4
    if kind == 0:
        return d.value_transform("x", "scale", factor=1.01)
    if kind == 1:
        mask = np.ones(d.table.n_rows, dtype=bool)
        mask[i :: 17] = False                         # drop a sliver per hop
        return d.filter_rows(mask)
    if kind == 2:
        return d.normalize(["x"], kind="zscore")
    return d.oversample(frac=0.05, seed=i)


def make_chain_table(seed: int, n: int) -> Table:
    rng = np.random.default_rng(seed)
    return Table.from_columns({
        "k": rng.integers(0, n // 2, n).astype(np.float32),
        "x": rng.normal(size=n).astype(np.float32),
        "g": rng.integers(0, 4, n).astype(np.float32),
    })


def build_deep_chain(seed: int = 0, n: int = 4000,
                     n_ops: int = 12) -> Tuple[ProvenanceIndex, str]:
    """A linear ``n_ops``-op chain over an ``n``-row table; returns the
    index and the sink dataset id (the source is ``"chain_src"``)."""
    idx = ProvenanceIndex("deep-chain")
    d = track(make_chain_table(seed, n), idx, "chain_src")
    for i in range(n_ops):
        d = chain_step(d, i)
    d.mark_sink()
    return idx, d.dataset_id
