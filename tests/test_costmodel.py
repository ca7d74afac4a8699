"""The query-planning cost model (repro.core.costmodel) and its consumers:
nnz-aware chain planning, density-driven backend selection in the
ComposedIndex, demand-amortized walk-vs-compose routing in QuerySession,
the hopcache_min_batch deprecation, and the _insert byte-accounting
regression.
"""
import warnings

import numpy as np
import pytest

import test_query_parity as tqp
from repro.core import costmodel as cm
from repro.core.costmodel import CostModel, RelStats
from repro.core.hopcache import ComposedIndex
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import QuerySession, prov


def _chain_index(n=300, n_ops=8):
    """A moderately deep linear pipeline for routing tests."""
    rng = np.random.default_rng(3)
    idx = ProvenanceIndex("chain")
    t = Table.from_columns({
        "k": rng.integers(0, n // 2, n).astype(np.float32),
        "x": rng.normal(size=n).astype(np.float32),
    })
    d = track(t, idx, "src")
    for i in range(n_ops):
        if i % 3 == 1:
            mask = np.ones(d.table.n_rows, dtype=bool)
            mask[i::11] = False
            d = d.filter_rows(mask)
        else:
            d = d.value_transform("x", "scale", factor=1.01)
    d.mark_sink()
    return idx, d.dataset_id


# ===========================================================================
# RelStats + estimates
# ===========================================================================
def test_relstats_density_and_slot_accessors():
    idx, sink = _chain_index(n=50, n_ops=2)
    op = idx.ops[0]
    s = RelStats.from_slot(op.tensor, 0)
    assert s.rows == op.tensor.n_in[0] and s.cols == op.tensor.n_out
    assert s.nnz == op.tensor.slot_nnz(0)
    assert s.density == pytest.approx(op.tensor.slot_density(0))
    assert 0.0 < s.density <= 1.0
    # sentinel links (-1) are not relation entries
    from repro.core.provtensor import append_tensor
    t = append_tensor(4, 3)
    assert t.slot_nnz(0) == 4 and t.slot_nnz(1) == 3
    assert t.nnz == 7  # COO rows (one per output record)


def test_compose_est_saturates_and_preserves_shape():
    a = RelStats(100, 50, 200)
    b = RelStats(50, 80, 400)
    c = cm.compose_est(a, b)
    assert (c.rows, c.cols) == (100, 80)
    assert 0 <= c.nnz <= c.rows * c.cols
    # a full × full compose saturates at full
    full = cm.compose_est(RelStats(10, 10, 100), RelStats(10, 10, 100))
    assert full.density == pytest.approx(1.0, abs=1e-6)
    # empty operands compose to empty
    assert cm.compose_est(RelStats(10, 10, 0), b_ := RelStats(10, 10, 50)).nnz == 0


def test_spmm_cost_scales_with_nnz_not_dims():
    sparse = RelStats(10_000, 10_000, 100)
    dense = RelStats(100, 100, 10_000)
    assert cm.spmm_cost(sparse, sparse) < cm.spmm_cost(dense, dense)
    # the dims-only view would order these the other way around
    assert sparse.rows * sparse.cols > dense.rows * dense.cols


def test_pick_backend_threshold():
    assert cm.pick_backend(cm.DENSITY_THRESHOLD / 10) == "csr"
    assert cm.pick_backend(cm.DENSITY_THRESHOLD * 2) == "bitplane"
    assert cm.pick_backend(0.0, have_scipy=False) == "bitplane"


# ===========================================================================
# nnz-aware chain DP
# ===========================================================================
def test_plan_chain_stats_same_merge_contract_as_dims_dp():
    from repro.core.compose import plan_chain

    # uniform density: the nnz DP must agree with the classic dims DP on the
    # textbook example (10x100)(100x5)(5x50) -> ((A B) C)
    dims = [(10, 100), (100, 5), (5, 50)]
    stats = [RelStats(r, c, r * c // 2) for r, c in dims]
    assert cm.plan_chain_stats(stats) == plan_chain(dims)


def _canon_est(stats, lo, hi):
    acc = stats[lo]
    for j in range(lo + 1, hi + 1):
        acc = cm.compose_est(acc, stats[j])
    return acc


def _eval_order(stats, order, backend="csr"):
    """Model cost of an arbitrary merge order (the compose_chain protocol:
    (i, _) merges the segment at original index i with the next live one)."""
    segs = {i: (i, i) for i in range(len(stats))}
    cost = 0.0
    for (i, _k) in order:
        j = i + 1
        while j not in segs:
            j += 1
        (alo, ahi), (blo, bhi) = segs[i], segs[j]
        cost += cm.compose_cost_pair(_canon_est(stats, alo, ahi),
                                     _canon_est(stats, blo, bhi), backend)
        segs[i] = (alo, bhi)
        del segs[j]
    return cost


def _all_orders(n):
    def rec(live):
        if len(live) == 1:
            yield []
            return
        for x in range(len(live) - 1):
            for rest in rec(live[: x + 1] + live[x + 2:]):
                yield [(live[x], 0)] + rest
    yield from rec(list(range(n)))


def _random_stats(rng, n=4):
    stats = []
    r = int(rng.integers(5, 2000))
    for _ in range(n):
        c = int(rng.integers(5, 2000))
        density = 10 ** rng.uniform(-3, 0)
        stats.append(RelStats(r, c, max(1, int(r * c * density))))
        r = c
    return stats


@pytest.mark.parametrize("seed", range(12))
def test_plan_chain_stats_is_optimal_under_the_model(seed):
    """Brute-force every parenthesization of a random length-4 chain: the
    DP's order must achieve the minimal model cost."""
    stats = _random_stats(np.random.default_rng(seed))
    dp_cost = _eval_order(stats, cm.plan_chain_stats(stats, backend="csr"))
    best = min(_eval_order(stats, o) for o in _all_orders(len(stats)))
    assert dp_cost <= best + 1e-6


def test_plan_chain_stats_beats_dims_only_plan_on_sparse_chains():
    """Seed where the dims-only DP picks an order the nnz model prices >3x
    worse — the mis-planning this PR removes (densities span 0.1%..100%)."""
    from repro.core.compose import plan_chain

    stats = _random_stats(np.random.default_rng(5))
    dims = [(s.rows, s.cols) for s in stats]
    nnz_order = cm.plan_chain_stats(stats, backend="csr")
    dims_order = plan_chain(dims)
    assert nnz_order != dims_order
    assert _eval_order(stats, dims_order) > 3 * _eval_order(stats, nnz_order)


def test_compose_chain_parity_with_nnz_plan():
    """The nnz-aware merge order changes cost, never the relation."""
    from repro.core.compose import compose_chain

    idx, sink = _chain_index(n=60, n_ops=5)
    a = compose_chain(idx, "src", sink, use_pallas=False, optimize=False)
    b = compose_chain(idx, "src", sink, use_pallas=False, optimize=True)
    np.testing.assert_array_equal(a, b)


# ===========================================================================
# CostModel chain statistics + routing decisions
# ===========================================================================
def test_chain_stats_matches_dag_and_caches():
    idx, sink = _chain_index()
    model = CostModel(idx)
    chain = model.chain_stats("src", sink)
    assert chain is not None and len(chain) == len(idx.ops)
    assert chain[0].rows == idx.datasets["src"].n_rows
    assert chain[-1].cols == idx.datasets[sink].n_rows
    assert model.chain_stats("src", sink) is chain          # cached
    assert model.chain_stats(sink, "src") is None           # no reverse path
    assert model.chain_stats("src", "src") == []


def test_choose_amortizes_demand_for_small_probe_streams():
    idx, sink = _chain_index()
    model = CostModel(idx)
    first = model.choose("src", sink, 1, 1.0)
    assert first["strategy"] == "walk"        # one tiny probe: walking wins
    # keep pushing single-probe demand at the same pair: the one-time compose
    # cost amortizes away and the decision flips to the hop-cache
    decisions = [model.choose("src", sink, 1, 1.0)["strategy"]
                 for _ in range(200)]
    assert "hopcache" in decisions
    flip = decisions.index("hopcache")
    assert all(d == "hopcache" for d in decisions[flip:])   # flips ONCE


def test_choose_routes_large_cold_batch_to_hopcache():
    idx, sink = _chain_index(n=1000)
    model = CostModel(idx)
    assert model.choose("src", sink, 64, 4.0)["strategy"] == "hopcache"


def test_composed_estimate_models_the_dag_not_a_chain():
    """On a diamond, the composed estimate must accumulate the way the
    executor does — compose along edges, union sibling branches — instead
    of folding parallel branch ops into one bogus linear chain."""
    idx, sink = tqp._diamond_pipeline(0)
    model = CostModel(idx)
    rel, cost = model.composed_estimate("src", sink)
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    assert (rel.rows, rel.cols) == (n_src, n_sink)
    assert 0 < rel.nnz <= n_src * n_sink
    assert cost > 0
    # estimate is the same object the routing decision consumes, and cached
    assert model.composed_estimate("src", sink) is model.composed_estimate("src", sink)
    # no path -> (None, 0)
    assert model.composed_estimate(sink, "src") == (None, 0.0)
    # an adjacent pair reuses the op's own relation: zero compose work
    first_out = idx.ops[0].output_id
    rel1, cost1 = model.composed_estimate("src", first_out)
    assert cost1 == 0.0 and rel1.nnz == idx.ops[0].tensor.slot_nnz(0)


def test_unretainable_relation_never_flips_to_hopcache():
    """Regression: with a cache budget too small to retain the composed
    relation, accumulated demand must NOT flip routing to 'hopcache' —
    that would recompose the whole chain on every probe, forever."""
    idx, sink = _chain_index(n=1000)
    ci = ComposedIndex(idx, memory_budget_bytes=1024)
    sess = QuerySession(idx, ci)
    for i in range(40):
        sess.run(prov(idx).source("src").rows([i % 10]).forward().to(sink).plan())
    assert sess.counters["hopcache"] == 0 and sess.counters["walk"] == 40
    # and the model reports why: the relation is not retainable
    c = sess.explain(prov(idx).source("src").rows([0]).forward().to(sink).plan())
    assert c["cost"]["retainable"] is False


def test_relT_materialization_respects_budget():
    """Regression: the lazy transposed plane must not push a sole cached
    entry past memory_budget_bytes (un-evictable), only retain when it
    fits."""
    from repro.core.hopcache import _Entry
    from repro.core.provtensor import pack_bitplane, unpack_bitplane

    idx, sink = _chain_index(n=40, n_ops=2)
    rng = np.random.default_rng(1)
    dense = rng.random((60, 300)) < 0.3
    rel = pack_bitplane(dense)                        # 60 x 10 words = 2400 B
    entry = _Entry("bitplane", rel, 60, 300, int(dense.sum()))
    ci = ComposedIndex(idx, backend="bitplane",
                       memory_budget_bytes=entry.nbytes() + 100)  # relT won't fit
    ci._insert(("a", "b"), entry)
    relT = ci._entry_relT(("a", "b"), entry)
    np.testing.assert_array_equal(unpack_bitplane(relT, 60), dense.T)
    assert entry.relT is None                         # served transiently
    assert ci._bytes <= ci.memory_budget_bytes        # invariant holds
    # with room, the plane IS retained and accounted
    ci2 = ComposedIndex(idx, backend="bitplane",
                        memory_budget_bytes=1 << 20)
    e2 = _Entry("bitplane", rel.copy(), 60, 300, int(dense.sum()))
    ci2._insert(("a", "b"), e2)
    ci2._entry_relT(("a", "b"), e2)
    assert e2.relT is not None
    assert ci2._bytes == e2.nbytes()


def test_co_query_pricing_covers_both_legs():
    """co_dependency/co_contributory compose TWO relations on the hopcache
    path; the planner must price both, not half the real cost."""
    idx, sink = _chain_index(n=400, n_ops=6)
    mid = idx.ops[2].output_id
    sess = QuerySession(idx, ComposedIndex(idx))
    p = prov(idx).source(mid).rows([0]).co_dependency("src", sink).plan()
    assert sess._plan_pairs(p) == [("src", mid), ("src", sink)]
    c = sess.explain(p)["cost"]
    assert c["legs"] is not None and len(c["legs"]) == 2
    assert c["walk_ns"] == pytest.approx(
        sum(leg["walk_ns"] for leg in c["legs"]))
    p10 = prov(idx).source("src").rows([0]).co_contributory(mid, via=sink).plan()
    assert sess._plan_pairs(p10) == [("src", sink), (mid, sink)]


def test_choose_no_path_walks():
    idx, sink = _chain_index()
    model = CostModel(idx)
    assert model.choose(sink, "src", 64, 4.0)["strategy"] == "walk"


def test_explain_does_not_mutate_demand():
    idx, sink = _chain_index()
    sess = QuerySession(idx, ComposedIndex(idx))
    p = prov(idx).source("src").rows([0]).forward().to(sink).plan()
    before = dict(sess.costmodel._demand)
    out = sess.explain(p)
    assert out["strategy"] in ("walk", "hopcache")
    assert "cost" in out and out["cost"]["walk_ns"] > 0
    assert sess.costmodel._demand == before


# ===========================================================================
# QuerySession routing counters (small-batch/cached vs large-batch/cold)
# ===========================================================================
def test_session_cost_model_routing_counters():
    idx, sink = _chain_index(n=1000)
    sess = QuerySession(idx, ComposedIndex(idx))
    # cold single-probe plans walk at first, then flip once demand amortizes
    for i in range(40):
        sess.run(prov(idx).source("src").rows([i % 10]).forward().to(sink).plan())
    assert sess.counters["walk"] >= 1
    assert sess.counters["hopcache"] >= 1
    walked = sess.counters["walk"]
    # once the relation is cached, even B=1 plans probe it (contains() path)
    sess.run(prov(idx).source("src").rows([0]).forward().to(sink).plan())
    assert sess.counters["walk"] == walked

    # a LARGE cold batch routes straight to the hop-cache on a fresh session
    fresh = QuerySession(idx, ComposedIndex(idx))
    probes = [[i % 10] for i in range(64)]
    fresh.run(prov(idx).source(sink).rows_batch(probes).backward().to("src").plan())
    assert fresh.counters == {**fresh.counters, "hopcache": 1, "walk": 0}


def test_every_routing_policy_answers_alike():
    """The cost model, the legacy min-batch heuristic, a forced hop-cache
    and a forced walk route the same workloads differently — a stream of
    single probes, two large batches, and a mix — but answer them alike,
    cold and once demand has settled the routes."""
    idx, sink = _chain_index(n=400)
    rng = np.random.default_rng(11)
    n_src, n_sink = idx.datasets["src"].n_rows, idx.datasets[sink].n_rows
    singles = [[int(r)] for r in rng.integers(0, n_src, 24)]
    batch_f = [rng.integers(0, n_src, 4).tolist() for _ in range(64)]
    batch_b = [rng.integers(0, n_sink, 4).tolist() for _ in range(64)]

    def fwd(rows):
        return prov(idx).source("src").rows(rows).forward().to(sink).plan()

    workloads = {
        "small_batch_stream": [fwd(r) for r in singles],
        "large_batch": [
            prov(idx).source("src").rows_batch(batch_f).forward().to(sink).plan(),
            prov(idx).source(sink).rows_batch(batch_b).backward().to("src").plan(),
        ],
        "mixed": [fwd(r) for r in singles[::4]] + [
            prov(idx).source(sink).rows(batch_b[0]).backward().to("src").plan(),
            prov(idx).source("src").rows_batch(batch_f).forward().to(sink).plan(),
        ],
    }
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        policies = {
            "auto": QuerySession(idx, ComposedIndex(idx)),
            "heuristic_minbatch8": QuerySession(idx, ComposedIndex(idx),
                                                hopcache_min_batch=8),
            "forced_hopcache": QuerySession(idx, ComposedIndex(idx),
                                            hopcache_min_batch=1),
        }
    walk = QuerySession(idx, ComposedIndex(idx), use_hopcache=False)
    for name, plans in workloads.items():
        want = [walk.run(p) for p in plans]
        for sess in policies.values():
            for _ in range(2):          # cold, then with demand settled
                for got, w in zip([sess.run(p) for p in plans], want):
                    if isinstance(w, list):
                        assert len(got) == len(w)
                        for g, x in zip(got, w):
                            np.testing.assert_array_equal(g, x)
                    else:
                        np.testing.assert_array_equal(got, w)
    assert policies["forced_hopcache"].counters["walk"] == 0
    assert policies["auto"].counters["hopcache"] > 0


def test_hopcache_min_batch_deprecated_but_honored():
    idx, sink = _chain_index()
    with pytest.warns(DeprecationWarning, match="hopcache_min_batch"):
        legacy = QuerySession(idx, ComposedIndex(idx), hopcache_min_batch=8)
    # the legacy heuristic never composes for sub-threshold probes, no matter
    # how much demand accumulates — the mis-routing the cost model fixes
    for i in range(40):
        legacy.run(prov(idx).source("src").rows([i % 10]).forward().to(sink).plan())
    assert legacy.counters["walk"] == 40 and legacy.counters["hopcache"] == 0
    # ... and still routes >= min_batch probes to the hop-cache
    legacy.run(prov(idx).source("src")
               .rows_batch([[i] for i in range(8)]).forward().to(sink).plan())
    assert legacy.counters["hopcache"] == 1
    # default sessions carry no heuristic and emit no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        QuerySession(idx, ComposedIndex(idx))


# ===========================================================================
# ComposedIndex: byte accounting + auto-backend mixing
# ===========================================================================
def test_insert_overwrite_releases_old_bytes():
    """Regression: re-inserting an existing key must subtract the old
    entry's size — _bytes used to inflate and force spurious evictions."""
    idx, sink = _chain_index(n=40, n_ops=2)
    ci = ComposedIndex(idx, backend="bitplane")
    from repro.core.hopcache import _Entry

    rel = np.ones((8, 4), dtype=np.uint32)
    entry = _Entry("bitplane", rel, 8, 128, 1024)
    ci._insert(("a", "b"), entry)
    once = ci._bytes
    assert once == entry.nbytes()
    for _ in range(5):
        ci._insert(("a", "b"), _Entry("bitplane", rel.copy(), 8, 128, 1024))
    assert ci._bytes == once                       # no double counting
    assert ci.evictions == 0                       # no spurious evictions


def test_insert_overwrite_under_tight_budget_no_spurious_evictions():
    idx, sink = _chain_index(n=40, n_ops=2)
    from repro.core.hopcache import _Entry

    rel = np.ones((64, 8), dtype=np.uint32)        # 2 KiB
    other = np.ones((32, 8), dtype=np.uint32)      # 1 KiB
    ci = ComposedIndex(idx, backend="bitplane", memory_budget_bytes=4096)
    ci._insert(("x", "y"), _Entry("bitplane", other, 32, 256, 10))
    for _ in range(10):                            # would blow 4 KiB if leaked
        ci._insert(("a", "b"), _Entry("bitplane", rel.copy(), 64, 256, 10))
    assert ("x", "y") in ci._cache and ("a", "b") in ci._cache
    assert ci.evictions == 0
    assert ci._bytes == sum(e.nbytes() for e in ci._cache.values())


def _dense_join_pipeline():
    """Two stacked diamonds re-joined on a 3-valued key: each diamond UNIONS
    two branch contributions and multiplies fan-out, so the accumulated
    src→sink relation densifies past the cost model's threshold mid-chain —
    the sparse prefix must stay CSR while the blow-up converts to packed
    bitplanes, in ONE cache."""
    rng = np.random.default_rng(7)
    n = 24
    idx = ProvenanceIndex("densejoin")
    t = Table.from_columns({
        "k": rng.integers(0, 3, n).astype(np.float32),   # 3 join keys
        "x": rng.normal(size=n).astype(np.float32),
    })
    s = track(t, idx, "src")
    a = s.filter_rows(np.ones(n, dtype=bool))
    b = s.value_transform("x", "scale", factor=2.0)
    j = a.join(b, on="k", how="inner")                   # diamond 1
    col = [c for c in j.table.columns if c != "k"][0]
    a2 = j.filter_rows(np.ones(j.table.n_rows, dtype=bool))
    b2 = j.value_transform(col, "scale", factor=3.0)
    j2 = a2.join(b2, on="k", how="inner")                # diamond 2
    j2.mark_sink()
    return idx, j2.dataset_id


def test_auto_mixes_backends_in_one_cache_with_parity():
    """Pinned to forced-COO capture: with structured tensors the sparse
    prefixes stay implicit gathers and never touch CSR — this test exercises
    the explicit csr↔bitplane conversion machinery (densification mid-chain),
    which must keep working for unstructured relations.  The structured
    three-way mix is covered in tests/test_structured.py."""
    pytest.importorskip("scipy")
    from repro.core.capture import force_coo_capture
    with force_coo_capture():
        idx, sink = _dense_join_pipeline()
    auto = ComposedIndex(idx, backend="auto")
    want = tqp.ref_q1(idx, "src", [0, 5], sink)
    np.testing.assert_array_equal(auto.q1_forward("src", [0, 5], sink), want)
    st = auto.stats()
    assert st["entries_csr"] > 0 and st["entries_bitplane"] > 0
    assert auto.conversions >= 1     # a CSR accumulation densified mid-chain
    # the src->sink relation itself crossed the density threshold
    assert auto.relation_backend("src", sink) == "bitplane"
    assert auto._relation_entry("src", sink).density >= cm.DENSITY_THRESHOLD
    # parity against both forced backends on forward AND backward probes
    for be in ("csr", "bitplane"):
        forced = ComposedIndex(idx, backend=be)
        np.testing.assert_array_equal(
            forced.q1_forward("src", [0, 5], sink), want)
        for a_, f_ in zip(auto.q2_backward(sink, [[0], [1, 2]], "src"),
                          forced.q2_backward(sink, [[0], [1, 2]], "src")):
            np.testing.assert_array_equal(a_, f_)


def test_bitplane_backward_probe_matches_reference_loop():
    """The vectorized transposed-plane backward probe == the old per-probe
    row-scan loop, bit for bit."""
    idx, sink = _chain_index(n=150, n_ops=6)
    ci = ComposedIndex(idx, backend="bitplane")
    entry = ci._relation_entry("src", sink)
    rng = np.random.default_rng(0)
    n_sink = idx.datasets[sink].n_rows
    masks = rng.random((17, n_sink)) < 0.05
    masks[3] = False                                # an empty probe too
    got = ci.probe_backward(masks, sink, "src")
    from repro.core.provtensor import pack_bitplane
    words = pack_bitplane(masks)
    want = np.stack([(entry.rel & w[None, :]).any(axis=1) for w in words], axis=0)
    np.testing.assert_array_equal(got, want)
    # the transposed plane was cached on the entry and accounted
    assert entry.relT is not None
    assert ci._bytes >= entry.relT.nbytes
