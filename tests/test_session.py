"""The unified lazy query API: builder -> QueryPlan -> QuerySession.

Pins (a) builder compilation and its explicit single-vs-batch entry points,
(b) exact agreement between the legacy q1-q11 shims and the QuerySession
planner under BOTH physical strategies (forced walk / forced hop-cache) on
randomized pipelines, (c) the multi-path diamond DAG the old unique-chain
hop-cache could not compose, (d) run_many fusion (results + counters), and
(e) the cache-routing stats surfaced through ``QuerySession.stats()``.
"""
import warnings

import numpy as np
import pytest

import test_query_parity as tqp
from repro.core import query as Q
from repro.core.hopcache import ComposedIndex
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import AmbiguousProbeWarning, QueryPlan, QuerySession, prov


def walk_session(idx) -> QuerySession:
    return QuerySession(idx, ComposedIndex(idx), use_hopcache=False)


def forced_hopcache_session(idx, composed=None, **kw) -> QuerySession:
    """Session pinned to the hop-cache strategy via the legacy min-batch
    knob — deliberately deprecated usage, so silence the warning here
    instead of spamming every suite run."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return QuerySession(
            idx, composed if composed is not None else ComposedIndex(idx, **kw),
            hopcache_min_batch=1)


def cache_session(idx, **kw) -> QuerySession:
    return forced_hopcache_session(idx, **kw)


# ===========================================================================
# Builder -> plan compilation
# ===========================================================================
def _tiny_index():
    idx = ProvenanceIndex("tiny")
    t = track(Table.from_columns({"k": np.arange(6, dtype=np.float32),
                                  "x": np.ones(6, dtype=np.float32)}), idx, "src")
    t = t.filter_rows(np.array([1, 0, 1, 1, 0, 1], bool))
    t.mark_sink()
    return idx, t.dataset_id


def test_builder_compiles_each_kind():
    idx, sink = _tiny_index()
    p = prov(idx).source("src").rows([0, 2]).forward().to(sink).plan()
    assert (p.kind, p.direction, p.batched, p.how) == ("record", "fwd", False, False)
    assert p.rows.shape == (1, 6) and p.rows.sum() == 2

    p = prov(idx).source(sink).rows([0]).attrs([1]).backward().to("src").how().plan()
    assert (p.kind, p.direction, p.how) == ("cells", "bwd", True)
    assert p.attrs.shape[1] == idx.datasets[sink].n_cols

    p = prov(idx).source(sink).transformations().plan()
    assert p.kind == "transformations" and p.rows is None

    p = prov(idx).source("src").rows([1]).co_contributory(sink, via=sink).plan()
    assert (p.kind, p.target, p.via) == ("co_contributory", sink, sink)

    p = prov(idx).source(sink).rows([0]).co_dependency("src", sink).plan()
    assert (p.kind, p.anchor, p.target) == ("co_dependency", "src", sink)

    # batch entry points are explicit; attr set broadcasts over the row batch
    p = (prov(idx).source("src").rows_batch([[0], [1, 2]]).attrs([0])
         .forward().to(sink).plan())
    assert p.batched and p.rows.shape == (2, 6) and p.attrs.shape[0] == 2


def test_builder_validation_errors():
    idx, sink = _tiny_index()
    with pytest.raises(ValueError, match="source"):
        prov(idx).rows([0]).forward().plan()
    with pytest.raises(ValueError, match="rows"):
        prov(idx).source("src").forward().to(sink).plan()
    with pytest.raises(ValueError, match="forward"):
        prov(idx).source("src").rows([0]).to(sink).plan()
    with pytest.raises(ValueError, match=r"\.to"):
        prov(idx).source("src").rows([0]).forward().plan()
    with pytest.raises(KeyError):
        prov(idx).source("nope")
    with pytest.raises(ValueError, match="rows_batch"):
        prov(idx).source("src").rows([0]).attrs_batch([[0]]).forward().to(sink).plan()
    # a 2-D stack is never a single probe, and vice versa
    with pytest.raises(ValueError, match="ONE probe"):
        prov(idx).source("src").rows(np.zeros((2, 6), bool)).forward().to(sink).plan()
    with pytest.raises(ValueError, match="batch"):
        prov(idx).source("src").rows_batch(np.zeros(6, bool)).forward().to(sink).plan()


def test_plan_ir_is_validated():
    with pytest.raises(ValueError, match="kind"):
        QueryPlan(kind="nope", source="a")
    with pytest.raises(ValueError, match="row probe"):
        QueryPlan(kind="record", source="a", target="b")
    with pytest.raises(ValueError, match="how"):
        QueryPlan(kind="co_dependency", source="a", target="b", anchor="c",
                  rows=np.ones((1, 2), bool), how=True)


# ===========================================================================
# Legacy shims == session planner, under BOTH strategies
# ===========================================================================
@pytest.mark.parametrize("seed", range(6))
def test_session_strategies_agree_with_shims(seed):
    idx, sink, rng = tqp._random_pipeline(seed)
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    walk, cache = walk_session(idx), cache_session(idx)
    for rows in tqp._row_probes(rng, n_src):
        pf = prov(idx).source("src").rows(rows).forward().to(sink).plan()
        want = tqp.ref_q1(idx, "src", rows, sink)
        np.testing.assert_array_equal(walk.run(pf), want)
        np.testing.assert_array_equal(cache.run(pf), want)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            np.testing.assert_array_equal(Q.q1_forward(idx, "src", rows, sink), want)
    for rows in tqp._row_probes(rng, n_sink):
        pb = prov(idx).source(sink).rows(rows).backward().to("src").plan()
        want = tqp.ref_q2(idx, sink, rows, "src")
        np.testing.assert_array_equal(walk.run(pb), want)
        np.testing.assert_array_equal(cache.run(pb), want)
    assert walk.counters["hopcache"] == 0
    assert cache.counters["hopcache"] > 0 and cache.counters["walk"] == 0


@pytest.mark.parametrize("seed", range(4))
def test_session_co_queries_agree_with_refs(seed):
    idx, sink, rng = tqp._random_pipeline(seed)
    n_src = idx.datasets["src"].n_rows
    walk, cache = walk_session(idx), cache_session(idx)
    others = [d for d in idx.datasets if d not in ("src", sink)]
    for d2 in others[:2]:
        want = tqp.ref_q10(idx, "src", [0], d2)
        p = prov(idx).source("src").rows([0]).co_contributory(d2).plan()
        np.testing.assert_array_equal(walk.run(p), want)
        np.testing.assert_array_equal(cache.run(p), want)   # via=None -> walk
        want = tqp.ref_q10(idx, "src", [0], d2, via=sink)
        p = prov(idx).source("src").rows([0]).co_contributory(d2, via=sink).plan()
        np.testing.assert_array_equal(walk.run(p), want)
        np.testing.assert_array_equal(cache.run(p), want)
    mid = idx.ops[0].output_id
    n_mid = idx.datasets[mid].n_rows
    rows = [int(rng.integers(0, n_mid))]
    want = tqp.ref_q11(idx, mid, rows, "src", sink)
    p = prov(idx).source(mid).rows(rows).co_dependency("src", sink).plan()
    np.testing.assert_array_equal(walk.run(p), want)
    np.testing.assert_array_equal(cache.run(p), want)


# ===========================================================================
# Multi-path diamond DAG (the case the old unique-chain hop-cache missed)
# ===========================================================================
def _diamond(seed=0):
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex("diamond")
    t = Table.from_columns({
        "k": np.arange(10, dtype=np.float32),
        "x": rng.normal(size=10).astype(np.float32),
    })
    s = track(t, idx, "src")
    a = s.filter_rows(rng.random(10) < 0.8)                 # branch A
    b = s.value_transform("x", "scale", factor=2.0)          # branch B
    j = a.join(b, on="k", how="inner")                       # re-join: 2 paths
    keep = np.ones(j.table.n_rows, dtype=bool)
    keep[:: 3] = rng.random() < 0.5
    if not keep.any():
        keep[0] = True
    j = j.filter_rows(keep).mark_sink()
    return idx, j.dataset_id


@pytest.mark.parametrize("backend", ["csr", "bitplane"])
def test_multipath_diamond_hopcache_matches_walk(backend):
    if backend == "csr":
        pytest.importorskip("scipy")
    idx, sink = _diamond()
    ci = ComposedIndex(idx, backend=backend)
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    for rows in ([], [0], [3, 7], list(range(n_src))):
        np.testing.assert_array_equal(
            ci.q1_forward("src", rows, sink), tqp.ref_q1(idx, "src", rows, sink))
    for rows in ([], [0], list(range(n_sink))):
        np.testing.assert_array_equal(
            ci.q2_backward(sink, rows, "src"), tqp.ref_q2(idx, sink, rows, "src"))
    # the relation really is the sum over BOTH branch paths: each branch
    # alone under-counts the sink rows reached from a full-source probe
    sess = forced_hopcache_session(idx, composed=ci)
    full = sess.run(prov(idx).source("src").rows(list(range(n_src)))
                    .forward().to(sink).plan())
    assert sess.counters["hopcache"] > 0
    np.testing.assert_array_equal(full, tqp.ref_q1(idx, "src", list(range(n_src)), sink))


def test_multipath_diamond_session_strategies_agree():
    idx, sink = _diamond(seed=3)
    walk, cache = walk_session(idx), cache_session(idx)
    n_src = idx.datasets["src"].n_rows
    probes = [[i] for i in range(n_src)]
    pw = prov(idx).source("src").rows_batch(probes).forward().to(sink).plan()
    got_w, got_c = walk.run(pw), cache.run(pw)
    for b, (w, c) in enumerate(zip(got_w, got_c)):
        np.testing.assert_array_equal(w, tqp.ref_q1(idx, "src", [b], sink))
        np.testing.assert_array_equal(c, w)


# ===========================================================================
# Batched how-provenance (Q5-Q8 traces, one pass per batch)
# ===========================================================================
@pytest.mark.parametrize("seed", range(4))
def test_batched_how_matches_singles(seed):
    idx, sink, rng = tqp._random_pipeline(seed)
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    sess = walk_session(idx)

    probes = [[0], [], sorted(set(rng.integers(0, n_src, 3).tolist()))]
    batch = sess.run(prov(idx).source("src").rows_batch(probes)
                     .forward().to(sink).how().plan())
    assert len(batch) == len(probes)
    for p, (recs, hops) in zip(probes, batch):
        srecs, shops = sess.run(prov(idx).source("src").rows(p)
                                .forward().to(sink).how().plan())
        np.testing.assert_array_equal(recs, srecs)
        assert [(h.op_id, h.src_dataset, h.dst_dataset, h.n_records) for h in hops] \
            == [(h.op_id, h.src_dataset, h.dst_dataset, h.n_records) for h in shops]
        # and the single-probe trace equals the seed reference
        _, ref_hops = tqp.ref_forward_record_masks(idx, "src", p, collect_hops=True)
        assert [(h.op_id, h.src_dataset, h.dst_dataset, h.n_records) for h in shops] \
            == ref_hops

    probes = [[0], [n_sink - 1]]
    batch = sess.run(prov(idx).source(sink).rows_batch(probes)
                     .backward().to("src").how().plan())
    for p, (recs, hops) in zip(probes, batch):
        _, ref_hops = tqp.ref_backward_record_masks(idx, sink, p, collect_hops=True)
        assert [(h.op_id, h.src_dataset, h.dst_dataset, h.n_records) for h in hops] \
            == ref_hops
        np.testing.assert_array_equal(recs, tqp.ref_q2(idx, sink, p, "src"))


@pytest.mark.parametrize("seed", range(4))
def test_batched_attr_how_matches_single_q7_q8(seed):
    idx, sink, rng = tqp._random_pipeline(seed)
    n_src, c_src = idx.datasets["src"].n_rows, idx.datasets["src"].n_cols
    n_sink, c_sink = idx.datasets[sink].n_rows, idx.datasets[sink].n_cols
    sess = walk_session(idx)
    rprobes = [[0], sorted(set(rng.integers(0, n_src, 2).tolist())), []]
    batch = sess.run(prov(idx).source("src").rows_batch(rprobes).attrs([0])
                     .forward().to(sink).how().plan())
    for p, (cells, hops) in zip(rprobes, batch):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            scells, shops = Q.q7_forward_attr_how(idx, "src", p, [0], sink)
        np.testing.assert_array_equal(cells, scells)
        assert [(h.op_id, h.src_dataset, h.dst_dataset, h.n_records) for h in hops] \
            == [(h.op_id, h.src_dataset, h.dst_dataset, h.n_records) for h in shops]
    rprobes = [[0], [n_sink - 1]]
    aprobes = [[0], list(range(min(2, c_sink)))]
    batch = sess.run(prov(idx).source(sink).rows_batch(rprobes).attrs_batch(aprobes)
                     .backward().to("src").how().plan())
    for (p, a), (cells, hops) in zip(zip(rprobes, aprobes), batch):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            scells, shops = Q.q8_backward_attr_how(idx, sink, p, a, "src")
        np.testing.assert_array_equal(cells, scells)
        assert [(h.op_id, h.n_records) for h in hops] \
            == [(h.op_id, h.n_records) for h in shops]


# ===========================================================================
# run_many fusion
# ===========================================================================
@pytest.mark.parametrize("seed", range(4))
def test_run_many_fuses_and_matches_singles(seed):
    idx, sink, rng = tqp._random_pipeline(seed)
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    mid = idx.ops[0].output_id

    def plans():
        return [
            prov(idx).source("src").rows([0]).forward().to(sink).plan(),
            prov(idx).source(sink).rows([0]).backward().to("src").plan(),
            prov(idx).source("src").rows([1 % n_src, 2 % n_src])
                .forward().to(sink).plan(),
            prov(idx).source("src").rows_batch([[0], [3 % n_src]])
                .forward().to(sink).plan(),
            prov(idx).source(sink).rows([n_sink - 1]).attrs([0])
                .backward().to("src").plan(),
            prov(idx).source(sink).rows([0]).attrs([0]).backward().to("src").plan(),
            prov(idx).source(sink).transformations().plan(),
            prov(idx).source(mid).rows([0]).co_dependency("src", sink).plan(),
        ]
    sess = walk_session(idx)
    singles = [sess.run(p) for p in plans()]
    fsess = walk_session(idx)
    fused = fsess.run_many(plans())
    assert fsess.counters["fused_groups"] >= 2   # Q1 group + Q4 group
    assert fsess.counters["fused_plans"] >= 5
    assert len(fused) == len(singles)
    for s, f in zip(singles, fused):
        if isinstance(s, list) and not isinstance(s, np.ndarray) \
                and s and isinstance(s[0], dict):
            assert s == f                        # transformations
        elif isinstance(s, list):
            for a, b in zip(s, f):
                np.testing.assert_array_equal(a, b)
        else:
            np.testing.assert_array_equal(s, f)


def test_run_many_accepts_builders_and_routes_hopcache():
    idx, sink, rng = tqp._random_pipeline(1)
    n_src = idx.datasets["src"].n_rows
    sess = cache_session(idx)
    builders = [prov(idx).source("src").rows([i % n_src]).forward().to(sink)
                for i in range(10)]
    out = sess.run_many(builders)
    assert len(out) == 10
    for i, r in enumerate(out):
        np.testing.assert_array_equal(r, tqp.ref_q1(idx, "src", [i % n_src], sink))
    st = sess.stats()
    assert st["planner"]["fused_groups"] == 1
    assert st["planner"]["hopcache"] == 1        # ONE fused probe, not 10
    assert st["hopcache"]["misses"] >= 1         # composed the relation once


# ===========================================================================
# Stats plumbing: hop-cache counters surface through the session
# ===========================================================================
def test_session_stats_expose_hopcache_counters():
    idx, sink, rng = tqp._random_pipeline(2)
    n_src = idx.datasets["src"].n_rows
    sess = cache_session(idx, memory_budget_bytes=32 << 20)
    probes = [[i % n_src] for i in range(6)]
    p = prov(idx).source("src").rows_batch(probes).forward().to(sink).plan()
    assert sess.explain(p)["strategy"] == "hopcache"
    sess.run(p)
    st1 = sess.stats()
    assert st1["hopcache"]["misses"] >= 1 and st1["hopcache"]["entries"] >= 1
    assert st1["planner"]["hopcache"] == 1
    sess.run(p)                                   # relation now cached
    st2 = sess.stats()
    assert st2["hopcache"]["hits"] > st1["hopcache"]["hits"]
    assert st2["hopcache"]["misses"] == st1["hopcache"]["misses"]
    # a walk-only session never touches the cache — routing regressions
    # show up as misses moving where hits were expected
    w = walk_session(idx)
    w.run(p)
    assert w.stats()["hopcache"]["misses"] == 0
    assert w.stats()["planner"]["walk"] == 1


def test_shared_session_on_index():
    idx, sink, _ = tqp._random_pipeline(3)
    s1 = idx.session()
    assert idx.session() is s1
    assert s1.composed is idx.composed()
    with pytest.raises(ValueError):
        idx.session(hopcache_min_batch=3)


# ===========================================================================
# Legacy-shim ambiguity warnings (the is_probe_batch fix)
# ===========================================================================
def test_shims_warn_on_ambiguous_probes():
    idx, sink = _tiny_index()
    with pytest.warns(AmbiguousProbeWarning, match="empty probe"):
        res = Q.q1_forward(idx, "src", [], sink)
    assert res.size == 0                          # still the single-probe path
    with pytest.warns(AmbiguousProbeWarning, match="1-D integer"):
        res = Q.q2_backward(idx, sink, np.array([0, 1]), "src")
    assert isinstance(res, np.ndarray) and res.ndim == 1
    # unambiguous spellings stay silent
    with warnings.catch_warnings():
        warnings.simplefilter("error", AmbiguousProbeWarning)
        Q.q1_forward(idx, "src", [0, 1], sink)               # index list
        Q.q1_forward(idx, "src", [[0], [1]], sink)           # batch of sets
        Q.q1_forward(idx, "src", np.ones(6, dtype=bool), sink)  # bool mask
    # ... and the builder never guesses at all
    with warnings.catch_warnings():
        warnings.simplefilter("error", AmbiguousProbeWarning)
        prov(idx).source("src").rows([]).forward().to(sink).run()
        prov(idx).source("src").rows_batch([]).forward().to(sink).plan()


def test_serve_engines_sharing_one_index_never_collide():
    """Two engines over ONE prov index (the documented pattern) must not
    overwrite each other's requests@N/responses@N datasets."""
    from repro.serve.engine import ServeEngine

    idx = ProvenanceIndex("shared-serve")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        e1 = ServeEngine(prov_index=idx)
        e2 = ServeEngine(prov_index=idx)
    r1 = e1.record(np.zeros((3, 2), np.int32), prompt_len=2,
                   request_ids=np.arange(3))
    r2 = e2.record(np.zeros((4, 2), np.int32), prompt_len=2,
                   request_ids=np.arange(4))
    assert r1.response_dataset != r2.response_dataset
    assert r1.request_dataset != r2.request_dataset
    np.testing.assert_array_equal(
        prov(idx).source(r2.response_dataset).rows([1])
        .backward().to(r2.request_dataset).run(), [1])
    # and the index itself rejects a duplicate producer
    with pytest.raises(ValueError, match="already exists"):
        idx.record([r1.request_dataset], r1.response_dataset,
                   Table.from_columns({"x": np.zeros(3, np.float32)}),
                   idx.ops[0].info)


def test_shims_emit_deprecation_once():
    idx, sink = _tiny_index()
    Q._DEPRECATION_WARNED.discard("q1_forward")
    with pytest.warns(DeprecationWarning, match="q1_forward"):
        Q.q1_forward(idx, "src", [0], sink)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        Q.q1_forward(idx, "src", [0], sink)       # second call is silent
