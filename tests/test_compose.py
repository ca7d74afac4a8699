"""Einsum composition chain == hop-by-hop queries; sharded == local."""
import numpy as np
import pytest

from repro.core import query as Q
from repro.core.compose import compose_chain, dataset_lineage, plan_chain
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track


def _pipeline(seed=0, n=64):
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex("c")
    t = Table.from_columns({
        "k": rng.integers(0, n // 2, n).astype(np.float32),
        "x": rng.normal(size=n).astype(np.float32),
        "g": rng.integers(0, 2, n).astype(np.float32),
    })
    r = Table.from_columns({
        "k": np.arange(n // 2, dtype=np.float32),
        "y": rng.normal(size=n // 2).astype(np.float32),
    })
    tt, tr = track(t, idx, "src"), track(r, idx, "ref")
    tj = tt.join(tr, on="k", how="inner")
    tf = tj.filter_rows(np.asarray(tj.table.col("x")) > -0.5)
    tv = tf.value_transform("x", "scale", factor=2.0)
    to = tv.oversample(frac=0.3, seed=seed).mark_sink()
    return idx, to


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("optimize", [False, True])
def test_compose_matches_hops(seed, optimize):
    idx, to = _pipeline(seed)
    rel_bits = compose_chain(idx, "src", to.dataset_id, use_pallas=False,
                             optimize=optimize)
    from repro.core.provtensor import unpack_bitplane
    rel = unpack_bitplane(rel_bits, idx.datasets[to.dataset_id].n_rows)
    n_src = idx.datasets["src"].n_rows
    for row in range(0, n_src, 7):
        want = set(Q.q1_forward(idx, "src", [row], to.dataset_id).tolist())
        got = set(np.flatnonzero(rel[row]).tolist())
        assert got == want


def test_compose_with_pallas_interpret():
    idx, to = _pipeline(3, n=40)
    a = compose_chain(idx, "src", to.dataset_id, use_pallas=False)
    b = compose_chain(idx, "src", to.dataset_id, use_pallas=True)
    np.testing.assert_array_equal(a, np.asarray(b))


def test_plan_chain_is_optimal_order():
    # classic example: (10x100)(100x5)(5x50) -> ((A B) C) costs 7500 < 75000
    order = plan_chain([(10, 100), (100, 5), (5, 50)])
    assert order == [(0, 0), (0, 1)]


def test_dataset_lineage_identity_when_src_is_dst():
    idx, to = _pipeline(0)
    rel = dataset_lineage(idx, "src", "src", use_pallas=False)
    assert (rel == np.eye(rel.shape[0], dtype=bool)).all()


def test_sharded_compose_and_audit_match_local():
    import jax
    import jax.numpy as jnp
    from repro.core.distributed import (
        compose_sharded, lineage_audit_sharded, backward_frontier_sharded,
        shard_relation)
    from repro.kernels.ref import pack_bits, unpack_bits
    from repro.launch.mesh import make_mesh

    idx, to = _pipeline(1, n=48)
    sink = to.dataset_id
    n_src = idx.datasets["src"].n_rows
    n_dst = idx.datasets[sink].n_rows
    rel = dataset_lineage(idx, "src", sink, use_pallas=False)

    mesh = make_mesh((1,), ("data",))
    bits = np.asarray(pack_bits(jnp.asarray(rel)))
    rb = shard_relation(bits, mesh)

    # audit: contributions per 'g' group to the first half of the output
    mask = np.zeros(n_dst, bool)
    mask[: n_dst // 2] = True
    mw = jnp.asarray(pack_bits(jnp.asarray(mask[None]))[0])
    grp = jnp.asarray(idx.datasets["src"].table.col("g").astype(np.int32))
    counts = np.asarray(lineage_audit_sharded(rb[:n_src], grp, mw, 2, mesh))
    # local oracle
    hits = (rel[:, mask]).any(axis=1)
    want = np.array([np.sum(hits & (np.asarray(grp) == g)) for g in range(2)])
    np.testing.assert_array_equal(counts, want)

    frontier = np.asarray(backward_frontier_sharded(rb[:n_src], mw, mesh))
    np.testing.assert_array_equal(frontier, hits)
