"""Hypothesis property tests on whole-pipeline invariants.

Random pipelines are generated as op sequences over a random source table;
the invariants hold for ANY data-preparation pipeline:

  P1  backward(forward(r)) ∋ r      whenever forward(r) is non-empty
  P2  forward(backward(o)) ∋ o      for every output record o
  P3  einsum composition == chained slice/project queries
  P4  every output record's backward set ⊆ source rows
  P5  provenance bytes scale with nnz, not with cell count (the paper's
      memory claim in its asymptotic form)
"""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import query as Q
from repro.core.compose import dataset_lineage
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track


def _random_pipeline(seed: int, op_codes):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(12, 60))
    idx = ProvenanceIndex("prop")
    t = Table.from_columns({
        "k": rng.integers(0, max(2, n // 4), n).astype(np.float32),
        "x": rng.normal(size=n).astype(np.float32),
        "y": rng.normal(size=n).astype(np.float32),
    })
    cur = track(t, idx, "src")
    for code in op_codes:
        tab = cur.table
        if code == 0:
            mask = np.asarray(tab.col("x")) > float(rng.normal(-1.0, 0.3))
            if not mask.any():
                mask[0] = True
            cur = cur.filter_rows(mask)
        elif code == 1:
            cur = cur.value_transform("x", "scale", factor=1.5)
        elif code == 2:
            cur = cur.oversample(frac=0.4, seed=int(rng.integers(1e6)))
        elif code == 3:
            cur = cur.onehot("k", n_values=int(tab.col("k").max()) + 1)
        elif code == 4:
            keep = [c for c in tab.columns if c != "y"] or list(tab.columns)
            cur = cur.select_columns(keep)
        elif code == 5:
            r = Table.from_columns({
                "k": np.arange(max(2, n // 4), dtype=np.float32),
                "z": rng.normal(size=max(2, n // 4)).astype(np.float32),
            })
            other = track(r, idx)
            cur = cur.join(other, on="k", how="inner")
            if cur.table.n_rows == 0:
                return None
    cur.mark_sink()
    return idx, cur


ops_strategy = st.lists(st.integers(0, 5), min_size=1, max_size=5)


@given(st.integers(0, 10_000), ops_strategy)
@settings(max_examples=40, deadline=None)
def test_p1_p2_roundtrips(seed, op_codes):
    built = _random_pipeline(seed, op_codes)
    if built is None:
        return
    idx, sink = built
    n_src = idx.datasets["src"].n_rows
    n_out = idx.datasets[sink.dataset_id].n_rows
    for r in range(0, n_src, max(1, n_src // 5)):
        fwd = Q.q1_forward(idx, "src", [r], sink.dataset_id)
        if len(fwd):
            back = Q.q2_backward(idx, sink.dataset_id, fwd, "src")
            assert r in back.tolist()                      # P1
    for o in range(0, n_out, max(1, n_out // 5)):
        back = Q.q2_backward(idx, sink.dataset_id, [o], "src")
        assert set(back.tolist()) <= set(range(n_src))     # P4
        if len(back):
            fwd = Q.q1_forward(idx, "src", back, sink.dataset_id)
            assert o in fwd.tolist()                       # P2


@given(st.integers(0, 10_000), ops_strategy)
@settings(max_examples=20, deadline=None)
def test_p3_composition_equals_chained_queries(seed, op_codes):
    built = _random_pipeline(seed, op_codes)
    if built is None:
        return
    idx, sink = built
    rel = dataset_lineage(idx, "src", sink.dataset_id, use_pallas=False)
    n_src = idx.datasets["src"].n_rows
    for r in range(0, n_src, max(1, n_src // 4)):
        want = set(Q.q1_forward(idx, "src", [r], sink.dataset_id).tolist())
        assert set(np.flatnonzero(rel[r]).tolist()) == want


@given(st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_p5_memory_scales_with_nnz(seed):
    built = _random_pipeline(seed, [0, 1, 2])
    if built is None:
        return
    idx, _ = built
    total_nnz = sum(op.tensor.nnz for op in idx.ops)
    # COO storage: (1+k) int32 per nnz; CSR at most doubles it per direction
    assert idx.prov_nbytes() <= total_nnz * 5 * 4 + 64 * len(idx.ops)
