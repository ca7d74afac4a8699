"""Federation: catalog + boundary handles + FederatedSession.

Four pillars, mirroring the acceptance criteria of the redesign:

* **Parity** — randomized pipelines are replayed TWICE from one spec list:
  once into a single merged index (the baseline the paper assumes), once
  split at a random cut into a ``prep`` index and a ``serve`` index glued by
  a catalog link.  Every federated answer must be byte-identical to the
  seed reference on the merged index — forward, backward, batched,
  co-queries, empty masks, ``-1`` sentinels (outer joins / appends), and
  diamonds whose branches cross the boundary over TWO links.
* **Capability isolation** — a :class:`BoundaryHandle` cannot mutate the
  exporting index or resolve non-ancestor datasets (typed
  :class:`CapabilityError`), and a :class:`ServeEngine` attached via
  ``upstream=`` holds no reference to the prep index object.
* **Explain / stats** — ``FederatedSession.explain`` surfaces per-segment
  strategy/cost (never just a stitched total) and ``stats`` aggregates
  per-index counters under the registered index name.
* **Back-compat** — ``ServeEngine(prov_index=...)`` warns once per process
  and answers identical lineage.
"""
import warnings

import numpy as np
import pytest

import pipegen
import test_query_parity as tqp
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import (
    BoundaryHandle,
    CapabilityError,
    FederatedSession,
    FederationError,
    ProvCatalog,
    QueryPlan,
    prov,
)
from repro.provenance.catalog import Link, qualify, split_ref
from repro.serve import engine as serve_engine
from repro.serve.engine import ServeEngine


# ===========================================================================
# Spec-replay pipelines — shared generators in tests/pipegen.py
# ===========================================================================
_random_specs = pipegen.random_specs
_apply = pipegen.apply_spec
_build_merged = pipegen.build_merged
_build_federated = pipegen.build_federated

SEEDS = list(range(8))


@pytest.mark.parametrize("seed", SEEDS)
def test_federated_record_parity_vs_merged(seed):
    base, specs = _random_specs(seed)
    merged, ids = _build_merged(base, specs)
    rng = np.random.default_rng(seed + 1000)
    cut = int(rng.integers(1, len(specs)))
    catalog, refs, sink_ref = _build_federated(base, specs, cut)
    src_ref = refs[0]
    n_src = merged.datasets["src"].n_rows
    n_sink = merged.datasets[ids[-1]].n_rows

    # forward src -> every dataset (both sides of the boundary)
    for rows in tqp._row_probes(rng, n_src):
        for j, ref in enumerate(refs):
            want = tqp.ref_q1(merged, "src", rows, ids[j])
            got = prov(catalog).source(src_ref).rows(rows).forward().to(ref).run()
            np.testing.assert_array_equal(got, want)
    # backward sink -> every dataset
    for rows in tqp._row_probes(rng, n_sink):
        for j, ref in enumerate(refs):
            want = tqp.ref_q2(merged, ids[-1], rows, ids[j])
            got = (prov(catalog).source(sink_ref).rows(rows)
                   .backward().to(ref).run())
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_federated_batch_and_co_queries_parity(seed):
    base, specs = _random_specs(seed)
    merged, ids = _build_merged(base, specs)
    rng = np.random.default_rng(seed + 2000)
    cut = int(rng.integers(1, len(specs)))
    catalog, refs, sink_ref = _build_federated(base, specs, cut)
    src_ref = refs[0]
    n_src = merged.datasets["src"].n_rows
    n_sink = merged.datasets[ids[-1]].n_rows

    # batched backward with empty probes interleaved
    probes = [[], [0], sorted(set(rng.integers(0, n_sink, 4).tolist())), []]
    got = (prov(catalog).source(sink_ref).rows_batch(probes)
           .backward().to(src_ref).run())
    for p, g in zip(probes, got):
        np.testing.assert_array_equal(g, tqp.ref_q2(merged, ids[-1], p, "src"))

    # co_dependency across the boundary: probe a serve-side dataset, anchor
    # at prep/src, answer at the sink
    mid_j = max(cut, 1)
    mid_ref, mid_id = refs[mid_j], ids[mid_j]
    n_mid = merged.datasets[mid_id].n_rows
    rows = [int(rng.integers(0, n_mid))]
    want = tqp.ref_q11(merged, mid_id, rows, "src", ids[-1])
    got = (prov(catalog).source(mid_ref).rows(rows)
           .co_dependency(src_ref, sink_ref).run())
    np.testing.assert_array_equal(got, want)

    # co_contributory with explicit via at the sink
    d2_j = 1
    want = tqp.ref_q10(merged, "src", [0], ids[d2_j], via=ids[-1])
    got = (prov(catalog).source(src_ref).rows([0])
           .co_contributory(refs[d2_j], via=sink_ref).run())
    np.testing.assert_array_equal(got, want)


def test_empty_batch_and_no_path():
    base, specs = _random_specs(3)
    catalog, refs, sink_ref = _build_federated(base, specs, 1)
    got = (prov(catalog).source(sink_ref).rows_batch([])
           .backward().to(refs[0]).run())
    assert got == []
    # no dataflow path (src -> src never crosses back): answers empty, not
    # an error — matching the walking engine
    got = (prov(catalog).source(sink_ref).rows([0]).forward()
           .to(refs[0]).run())
    assert got.size == 0


# ===========================================================================
# Diamond ACROSS the boundary: two links carry two branches of one source
# ===========================================================================
_cross_boundary_diamond = pipegen.cross_boundary_diamond


@pytest.mark.parametrize("seed", range(4))
def test_cross_boundary_diamond_parity(seed):
    """BOTH links must contribute: either branch alone under-counts, exactly
    like the single-index diamond the multi-path hop-cache composes."""
    merged, sink_id, catalog, sink_ref = _cross_boundary_diamond(seed)
    n_src = merged.datasets["src"].n_rows
    n_sink = merged.datasets[sink_id].n_rows
    for rows in ([], [0], [2, 7], list(range(n_src))):
        want = tqp.ref_q1(merged, "src", rows, sink_id)
        got = (prov(catalog).source("prep/src").rows(rows)
               .forward().to(sink_ref).run())
        np.testing.assert_array_equal(got, want)
    probes = [[i] for i in range(n_sink)]
    got = (prov(catalog).source(sink_ref).rows_batch(probes)
           .backward().to("prep/src").run())
    for b, g in enumerate(got):
        np.testing.assert_array_equal(g, tqp.ref_q2(merged, sink_id, [b], "src"))
    sess = catalog.session()
    assert sess.counters["links_crossed"] >= 2


# ===========================================================================
# Alignment stitching (the ServeEngine request_ids path, in isolation)
# ===========================================================================
def test_alignment_stitch_duplicates_and_unlinked():
    prep = ProvenanceIndex("prep")
    t = track(Table.from_columns({"k": np.arange(6, dtype=np.float32),
                                  "x": np.ones(6, np.float32)}), prep, "raw")
    t.mark_sink()
    serve = ProvenanceIndex("serve")
    s = track(Table.from_columns({"k": np.zeros(4, np.float32),
                                  "x": np.ones(4, np.float32)}), serve, "req")
    out = s.value_transform("x", "scale", factor=3.0).mark_sink()
    catalog = ProvCatalog("aligned")
    catalog.register("prep", prep).register("serve", serve)
    # req row j came from raw row align[j]; row 3 has no upstream origin
    catalog.link("prep/raw", "serve/req", alignment=[5, 2, 2, -1])

    ref = qualify("serve", out.dataset_id)
    # forward: raw row 2 feeds req rows {1, 2}
    got = prov(catalog).source("prep/raw").rows([2]).forward().to(ref).run()
    np.testing.assert_array_equal(got, [1, 2])
    # backward: duplicates OR-accumulate, unlinked rows vanish
    got = (prov(catalog).source(ref).rows_batch([[0], [1], [2], [3], [1, 2]])
           .backward().to("prep/raw").run())
    assert [g.tolist() for g in got] == [[5], [2], [2], [], [2]]


def test_link_validation_errors():
    prep = ProvenanceIndex("prep")
    t = track(Table.from_columns({"x": np.ones(4, np.float32)}), prep, "raw")
    derived = t.value_transform("x", "scale", factor=2.0)
    serve = ProvenanceIndex("serve")
    track(Table.from_columns({"x": np.ones(3, np.float32)}), serve, "req")
    catalog = ProvCatalog()
    catalog.register("prep", prep).register("serve", serve)
    with pytest.raises(FederationError, match="different members"):
        catalog.link("prep/raw", f"prep/{derived.dataset_id}")
    with pytest.raises(FederationError, match="equal row counts"):
        catalog.link("prep/raw", "serve/req")          # 4 vs 3, no alignment
    with pytest.raises(FederationError, match="shape"):
        catalog.link("prep/raw", "serve/req", alignment=[0, 1])
    with pytest.raises(FederationError, match=r"\[-1"):
        catalog.link("prep/raw", "serve/req", alignment=[0, 1, 9])
    with pytest.raises(FederationError, match="producer"):
        # can't land boundary rows on a dataset an op already produces
        serve2 = ProvenanceIndex("serve2")
        s2 = track(Table.from_columns({"x": np.ones(4, np.float32)}),
                   serve2, "req2")
        d2 = s2.value_transform("x", "scale", factor=2.0)
        catalog.register("serve2", serve2)
        catalog.link("prep/raw", f"serve2/{d2.dataset_id}")
    with pytest.raises(FederationError, match="qualified"):
        catalog.link("raw", "serve/req")
    with pytest.raises(FederationError, match="unknown index"):
        catalog.link("nope/raw", "serve/req")
    with pytest.raises(FederationError, match="already registered"):
        catalog.register("prep", prep)
    with pytest.raises(FederationError, match="member name"):
        catalog.register("a/b", prep)


def test_cyclic_link_graph_raises():
    a, b = ProvenanceIndex("a"), ProvenanceIndex("b")
    ta = track(Table.from_columns({"x": np.ones(3, np.float32)}), a, "sa")
    tb = track(Table.from_columns({"x": np.ones(3, np.float32)}), b, "sb")
    a2 = ta.value_transform("x", "scale", factor=2.0)
    b2 = tb.value_transform("x", "scale", factor=2.0)
    catalog = ProvCatalog()
    catalog.register("a", a).register("b", b)
    catalog.link(f"a/{a2.dataset_id}", "b/sb")
    catalog.link(f"b/{b2.dataset_id}", "a/sa")
    with pytest.raises(FederationError, match="cycle"):
        prov(catalog).source("a/sa").rows([0]).forward().to(f"b/{b2.dataset_id}").run()


# ===========================================================================
# Cross-index cells / how parity (the PR 9 lift), via-less Q10 stays loud
# ===========================================================================
@pytest.mark.parametrize("seed", SEEDS)
def test_federated_cells_parity_vs_merged(seed):
    """Cross-boundary attribute lineage: byte-identical to the merged
    single-index walk — forward and backward, every dataset, empty probes."""
    base, specs = _random_specs(seed)
    merged, ids = _build_merged(base, specs)
    rng = np.random.default_rng(seed + 3000)
    cut = int(rng.integers(1, len(specs)))
    catalog, refs, sink_ref = _build_federated(base, specs, cut)
    src_ref = refs[0]
    n_src = merged.datasets["src"].n_rows
    c_src = merged.datasets["src"].n_cols
    n_sink = merged.datasets[ids[-1]].n_rows
    c_sink = merged.datasets[ids[-1]].n_cols

    for rows in tqp._row_probes(rng, n_src):
        attrs = sorted(set(rng.integers(0, c_src, 2).tolist()))
        want = tqp.ref_q3(merged, "src", rows, attrs, ids[-1])
        got = (prov(catalog).source(src_ref).rows(rows).attrs(attrs)
               .forward().to(sink_ref).run())
        np.testing.assert_array_equal(got, want)
    rows = [int(rng.integers(0, n_sink))]
    attrs = list(range(c_sink))
    for j, ref in enumerate(refs):
        want = tqp.ref_q4(merged, ids[-1], rows, attrs, ids[j])
        got = (prov(catalog).source(sink_ref).rows(rows).attrs(attrs)
               .backward().to(ref).run())
        np.testing.assert_array_equal(got, want)


def _strip_links(hops):
    return [(h.op_name, h.category, h.n_records) for h in hops
            if h.category != "link"]


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_federated_how_parity_vs_merged(seed):
    """Record+how and cells+how across the boundary: answers byte-identical
    to merged; hop traces match the merged walk op-for-op (op name,
    category, contribution count) once the synthetic boundary-crossing
    ``category="link"`` hop is dropped."""
    base, specs = _random_specs(seed)
    merged, ids = _build_merged(base, specs)
    rng = np.random.default_rng(seed + 4000)
    cut = int(rng.integers(1, len(specs)))
    catalog, refs, sink_ref = _build_federated(base, specs, cut)
    n_src = merged.datasets["src"].n_rows
    n_sink = merged.datasets[ids[-1]].n_rows

    rows = sorted(set(rng.integers(0, n_sink, 3).tolist()))
    want_recs, want_hops = (prov(merged).source(ids[-1]).rows(rows)
                            .backward().to("src").how().run())
    got_recs, got_hops = (prov(catalog).source(sink_ref).rows(rows)
                          .backward().to(refs[0]).how().run())
    np.testing.assert_array_equal(got_recs, want_recs)
    assert _strip_links(got_hops) == _strip_links(want_hops)
    assert sum(1 for h in got_hops if h.category == "link") == 1

    rows = [int(rng.integers(0, n_src))]
    want_recs, want_hops = (prov(merged).source("src").rows(rows)
                            .forward().to(ids[-1]).how().run())
    got_recs, got_hops = (prov(catalog).source(refs[0]).rows(rows)
                          .forward().to(sink_ref).how().run())
    np.testing.assert_array_equal(got_recs, want_recs)
    assert _strip_links(got_hops) == _strip_links(want_hops)

    # cells + how: batched, empty probes interleaved
    c_src = merged.datasets["src"].n_cols
    probes = [[], [0], sorted(set(rng.integers(0, n_src, 3).tolist()))]
    want = (prov(merged).source("src").rows_batch(probes)
            .attrs(list(range(c_src))).forward().to(ids[-1]).how().run())
    got = (prov(catalog).source(refs[0]).rows_batch(probes)
           .attrs(list(range(c_src))).forward().to(sink_ref).how().run())
    for (wc, wh), (gc, gh) in zip(want, got):
        np.testing.assert_array_equal(gc, wc)
        assert _strip_links(gh) == _strip_links(wh)


def test_federated_cells_diamond_both_links_contribute():
    merged, sink_id, catalog, sink_ref = _cross_boundary_diamond(1)
    c_src = merged.datasets["src"].n_cols
    for rows in ([0], [2, 5]):
        want = tqp.ref_q3(merged, "src", rows, [0, 1], sink_id)
        got = (prov(catalog).source("prep/src").rows(rows).attrs([0, 1])
               .forward().to(sink_ref).run())
        np.testing.assert_array_equal(got, want)
    assert c_src == 2


def test_cross_index_co_contributory_needs_via():
    base, specs = _random_specs(5)
    catalog, refs, sink_ref = _build_federated(base, specs, 1)
    with pytest.raises(FederationError, match="via"):
        (prov(catalog).source(refs[0]).rows([0])
         .co_contributory(sink_ref).run())


def test_single_member_plans_delegate_with_full_kind_support():
    base, specs = _random_specs(6)
    merged, ids = _build_merged(base, specs)
    catalog, refs, sink_ref = _build_federated(base, specs, len(specs))
    # the whole chain lives in prep: every kind works through the catalog
    sink_prep = refs[-2] if refs[-1].startswith("serve") else refs[-1]
    # build the same spelling against the merged baseline
    j = refs.index(sink_prep)
    want = tqp.ref_q3(merged, "src", [0], [1], ids[j])
    got = (prov(catalog).source(refs[0]).rows([0]).attrs([1])
           .forward().to(sink_prep).run())
    np.testing.assert_array_equal(got, want)
    recs, hops = (prov(catalog).source(refs[0]).rows([0])
                  .forward().to(sink_prep).how().run())
    np.testing.assert_array_equal(recs, tqp.ref_q1(merged, "src", [0], ids[j]))
    assert all(h.op_id >= 0 for h in hops)
    meta = prov(catalog).source(sink_prep).transformations().run()
    assert len(meta) == len(merged.upstream_ops(ids[j]))
    sess = catalog.session()
    assert sess.counters["single_index"] >= 3
    assert sess.counters["federated"] == 0


# ===========================================================================
# run_many fusion across the boundary
# ===========================================================================
def test_run_many_fuses_federated_plans():
    base, specs = _random_specs(7)
    merged, ids = _build_merged(base, specs)
    catalog, refs, sink_ref = _build_federated(base, specs, 2)
    n_sink = merged.datasets[ids[-1]].n_rows
    sess = FederatedSession(catalog)
    plans = [prov(catalog).source(sink_ref).rows([i % n_sink])
             .backward().to(refs[0]) for i in range(12)]
    out = sess.run_many(plans)
    assert len(out) == 12
    for i, g in enumerate(out):
        np.testing.assert_array_equal(
            g, tqp.ref_q2(merged, ids[-1], [i % n_sink], "src"))
    # ONE fused propagation: a single federated execution, the 12 plans
    # packed into one (B=12) pass per member segment
    assert sess.counters["fused_groups"] == 1
    assert sess.counters["fused_plans"] == 12
    assert sess.counters["federated"] == 1


# ===========================================================================
# Cross-boundary composed relations (the federation's own stitched cache)
# ===========================================================================
@pytest.mark.parametrize("seed", SEEDS[:4])
def test_cross_relation_cache_parity(seed):
    """With the demand threshold at 0 every cross route composes its
    stitched relation immediately — answers must stay byte-identical to
    the merged reference, and repeated probes hit the cache."""
    pytest.importorskip("scipy")
    base, specs = _random_specs(seed)
    merged, ids = _build_merged(base, specs)
    rng = np.random.default_rng(seed + 3000)
    cut = int(rng.integers(1, len(specs)))
    catalog, refs, sink_ref = _build_federated(base, specs, cut)
    sess = FederatedSession(catalog, cross_min_demand=0)
    n_src = merged.datasets["src"].n_rows
    n_sink = merged.datasets[ids[-1]].n_rows

    probes = [[], [0], sorted(set(rng.integers(0, n_sink, 4).tolist()))]
    got = sess.run(prov(catalog).source(sink_ref).rows_batch(probes)
                   .backward().to(refs[0]).plan())
    for p, g in zip(probes, got):
        np.testing.assert_array_equal(g, tqp.ref_q2(merged, ids[-1], p, "src"))
    assert sess.counters["cross_composes"] == 1
    assert sess.counters["cross_probes"] == 1
    # forward route composes its own relation; the backward one is reused
    fprobes = [[i] for i in range(min(6, n_src))]
    got = sess.run(prov(catalog).source(refs[0]).rows_batch(fprobes)
                   .forward().to(sink_ref).plan())
    for p, g in zip(fprobes, got):
        np.testing.assert_array_equal(g, tqp.ref_q1(merged, "src", p, ids[-1]))
    sess.run(prov(catalog).source(sink_ref).rows([0]).backward()
             .to(refs[0]).plan())
    assert sess.counters["cross_composes"] == 2      # one per route
    assert sess.counters["cross_probes"] == 3
    assert sess.counters["segments"] == 0            # never fell back


def test_cross_relation_cache_diamond_and_alignment():
    pytest.importorskip("scipy")
    merged, sink_id, catalog, sink_ref = _cross_boundary_diamond(1)
    sess = FederatedSession(catalog, cross_min_demand=0)
    n_src = merged.datasets["src"].n_rows
    got = sess.run(prov(catalog).source("prep/src")
                   .rows_batch([[i] for i in range(n_src)])
                   .forward().to(sink_ref).plan())
    for b, g in enumerate(got):
        np.testing.assert_array_equal(g, tqp.ref_q1(merged, "src", [b], sink_id))
    assert sess.counters["cross_composes"] == 1      # BOTH links in one relation

    # alignment matrix parity (duplicates + unlinked rows), both directions
    prep = ProvenanceIndex("prep")
    track(Table.from_columns({"x": np.ones(6, np.float32)}), prep, "raw")
    serve = ProvenanceIndex("serve")
    s = track(Table.from_columns({"x": np.ones(4, np.float32)}), serve, "req")
    out = s.value_transform("x", "scale", factor=3.0).mark_sink()
    cat = ProvCatalog()
    cat.register("prep", prep).register("serve", serve)
    cat.link("prep/raw", "serve/req", alignment=[5, 2, 2, -1])
    fsess = FederatedSession(cat, cross_min_demand=0)
    ref = qualify("serve", out.dataset_id)
    got = fsess.run(prov(cat).source(ref).rows_batch([[0], [1], [2], [3], [1, 2]])
                    .backward().to("prep/raw").plan())
    assert [g.tolist() for g in got] == [[5], [2], [2], [], [2]]
    got = fsess.run(prov(cat).source("prep/raw").rows([2]).forward()
                    .to(ref).plan())
    np.testing.assert_array_equal(got, [1, 2])
    assert fsess.counters["cross_composes"] == 2


def test_unroutable_cross_compose_memoized_as_failed():
    """A route with a member-level link path but NO dataset-level dataflow
    path must not re-pay the compose attempt on every probe."""
    pytest.importorskip("scipy")
    prep = ProvenanceIndex("prep")
    t = track(Table.from_columns({"x": np.ones(5, np.float32)}), prep, "raw")
    a = t.value_transform("x", "scale", factor=2.0)
    track(Table.from_columns({"x": np.ones(3, np.float32)}), prep, "orphan")
    serve = ProvenanceIndex("serve")
    s = track(a.table, serve, "ingest")
    out = s.value_transform("x", "scale", factor=3.0).mark_sink()
    catalog = ProvCatalog()
    catalog.register("prep", prep).register("serve", serve)
    catalog.link(qualify("prep", a.dataset_id), "serve/ingest")
    sess = FederatedSession(catalog, cross_min_demand=0)
    plan = (prov(catalog).source("prep/orphan").rows([0]).forward()
            .to(qualify("serve", out.dataset_id)).plan())
    got = sess.run(plan)
    assert got.size == 0
    assert sess.counters["cross_composes"] == 0      # nothing to stitch
    assert len(sess._cross_failed) == 1
    segments_after_first = sess.counters["segments"]
    got = sess.run(plan)                             # memoized: no re-attempt
    assert got.size == 0
    assert sess.counters["cross_composes"] == 0
    assert sess.counters["segments"] == segments_after_first
    # a routable query on the same catalog still composes + caches
    ok = sess.run(prov(catalog).source(qualify("serve", out.dataset_id))
                  .rows([0]).backward().to("prep/raw").plan())
    np.testing.assert_array_equal(ok, [0])
    assert sess.counters["cross_composes"] == 1


def test_cross_relation_survives_unrelated_links():
    """The serving pattern: one new link per recorded generation, landing
    on a brand-new requests@N dataset.  No cached route can reach it, so
    hot stitched relations must SURVIVE — wholesale invalidation would
    defeat the fast path in exactly the scenario it exists for."""
    pytest.importorskip("scipy")
    prep, exported, _, _ = _capability_fixture()
    engine = ServeEngine("serve:cachetest",
                         upstream=prep.export(exported.dataset_id))
    sess = engine.federation
    sess.cross_min_demand = 0
    r1 = engine.record(np.zeros((3, 2), np.int32), prompt_len=2,
                       request_ids=np.array([0, 1, 2]))
    got1 = engine.response_lineage(r1, rows=[1], upstream="raw")
    assert sess.counters["cross_composes"] == 1
    # a second generation appends a new link; the cached route keeps
    engine.record(np.zeros((2, 2), np.int32), prompt_len=2,
                  request_ids=np.array([3, 0]))
    again = engine.response_lineage(r1, rows=[1], upstream="raw")
    np.testing.assert_array_equal(again, got1)
    assert sess.counters["cross_composes"] == 1      # NOT recomposed
    assert sess.counters["cross_probes"] >= 2


def test_upstream_engine_requires_explicit_request_ids():
    """With an upstream attach the boundary link is a lineage assertion:
    the arange() default must never silently fabricate it, and a bad batch
    must fail BEFORE mutating the serving index."""
    prep, exported, _, _ = _capability_fixture()
    engine = ServeEngine("serve:reqids",
                         upstream=prep.export(exported.dataset_id))
    with pytest.raises(ValueError, match="explicit request_ids"):
        engine.record(np.zeros((3, 2), np.int32), prompt_len=2)
    # out-of-range rows fail before add_source: no orphan requests@N
    with pytest.raises(ValueError, match="boundary dataset"):
        engine.record(np.zeros((3, 2), np.int32), prompt_len=2,
                      request_ids=np.array([0, 1, 99]))
    assert not any(d.startswith("requests@") for d in engine.prov.datasets)
    assert not engine.catalog.links
    # -1 = request with no upstream origin: records fine, traces to nothing
    ok = engine.record(np.zeros((2, 2), np.int32), prompt_len=2,
                       request_ids=np.array([2, -1]))
    got = engine.response_lineage_batch(ok, [[0], [1]], upstream="raw")
    assert [g.tolist() for g in got] == [[3], []]


def test_upstream_tuple_attach_validates_dataset():
    prep, exported, _, _ = _capability_fixture()
    catalog = ProvCatalog()
    catalog.register("prep", prep)
    with pytest.raises(KeyError):
        ServeEngine("serve:typo",
                    upstream=(catalog, "prep/definitely-missing"))
    engine = ServeEngine(
        "serve:ok", upstream=(catalog, qualify("prep", exported.dataset_id)))
    assert engine.catalog is catalog and "serve" in catalog.members


def test_cross_relation_invalidates_on_new_link():
    """A stitched relation must not survive a link-set change: adding the
    second branch link changes the answer, exactly to the merged one."""
    pytest.importorskip("scipy")
    merged, sink_id, catalog, sink_ref = _cross_boundary_diamond(2)
    # rebuild the same split world but register only branch_a's link first
    prep_member = catalog.members["prep"]
    serve_member = catalog.members["serve"]
    link_a, link_b = catalog.links
    partial = ProvCatalog("partial")
    partial.register("prep", prep_member._index)
    partial.register("serve", serve_member._index)
    partial.link(link_a.up, link_a.down)
    sess = FederatedSession(partial, cross_min_demand=0)
    n_src = merged.datasets["src"].n_rows
    all_rows = list(range(n_src))
    plan = (prov(partial).source("prep/src").rows(all_rows)
            .forward().to(sink_ref).plan())
    one_branch = sess.run(plan)
    both = tqp.ref_q1(merged, "src", all_rows, sink_id)
    assert sess.counters["cross_composes"] == 1
    # now declare the second boundary: the cached relation is stale
    partial.link(link_b.up, link_b.down)
    got = sess.run(plan)
    np.testing.assert_array_equal(got, both)
    assert sess.counters["cross_composes"] == 2      # recomposed after the link
    assert len(one_branch) <= len(both)


# ===========================================================================
# Capability isolation
# ===========================================================================
def _capability_fixture():
    prep = ProvenanceIndex("prep")
    s = track(Table.from_columns({"k": np.arange(8, dtype=np.float32),
                                  "x": np.ones(8, np.float32)}), prep, "raw")
    exported = s.filter_rows(np.array([1, 1, 0, 1, 1, 0, 1, 1], bool))
    sibling = s.value_transform("x", "scale", factor=2.0)  # NOT an ancestor
    downstream = exported.value_transform("x", "scale", factor=3.0)
    return prep, exported, sibling, downstream


def test_boundary_handle_denies_mutation_and_non_ancestors():
    prep, exported, sibling, downstream = _capability_fixture()
    handle = prep.export(exported.dataset_id)
    assert isinstance(handle, BoundaryHandle)
    # mutation verbs raise the typed error
    with pytest.raises(CapabilityError, match="read-only"):
        handle.record([], "x", None, None)
    with pytest.raises(CapabilityError, match="read-only"):
        handle.add_source("y", None)
    # ancestors resolve; the sibling branch and the downstream consumer don't
    assert exported.dataset_id in handle.datasets
    assert "raw" in handle.datasets
    assert sibling.dataset_id not in handle.datasets
    with pytest.raises(CapabilityError, match="not an ancestor"):
        handle.datasets[sibling.dataset_id]
    with pytest.raises(CapabilityError, match="not an ancestor"):
        handle.datasets[downstream.dataset_id]
    with pytest.raises(KeyError):
        handle.datasets["never-existed"]
    assert set(handle.datasets) == {"raw", exported.dataset_id}
    # plans touching non-ancestors are rejected before execution
    plan = QueryPlan(kind="record", source="raw",
                     target=sibling.dataset_id, direction="fwd",
                     rows=np.ones((1, 8), bool))
    with pytest.raises(CapabilityError, match="not an ancestor"):
        handle.run(plan)
    with pytest.raises(CapabilityError):
        handle.path_exists("raw", downstream.dataset_id)
    # ancestor-only plans answer through the exporting index's session
    ok = QueryPlan(kind="record", source="raw", target=exported.dataset_id,
                   direction="fwd", rows=np.ones((1, 8), bool))
    res = handle.run(ok)
    np.testing.assert_array_equal(
        res, tqp.ref_q1(prep, "raw", list(range(8)), exported.dataset_id))
    # attenuation: re-export narrows, never widens
    narrower = handle.export("raw")
    assert set(narrower.datasets) == {"raw"}
    with pytest.raises(CapabilityError):
        handle.export(sibling.dataset_id)


def test_catalog_resolution_respects_capabilities():
    prep, exported, sibling, _ = _capability_fixture()
    handle = prep.export(exported.dataset_id)
    catalog = ProvCatalog()
    catalog.register("up", handle)
    assert qualify("up", "raw") in catalog.datasets
    assert qualify("up", sibling.dataset_id) not in catalog.datasets
    with pytest.raises(CapabilityError):
        catalog.datasets[qualify("up", sibling.dataset_id)]
    # the builder refuses the ref before a plan even compiles
    with pytest.raises(KeyError):
        prov(catalog).source(qualify("up", sibling.dataset_id))


def test_serve_engine_upstream_holds_no_prep_index():
    prep, exported, sibling, _ = _capability_fixture()
    handle = prep.export(exported.dataset_id)
    engine = ServeEngine("serve:captest", upstream=handle)
    assert all(v is not prep for v in vars(engine).values())
    assert engine.catalog.member_of(prep) is None
    # the registered upstream member is the read-only capability
    up = engine.catalog.members["prep"]
    assert up is handle
    with pytest.raises(CapabilityError):
        up.record([], "x", None, None)
    # lineage still reaches prep/raw through the federation
    r = engine.record(np.zeros((3, 2), np.int32), prompt_len=2,
                      request_ids=np.array([0, 2, 2]))
    got = engine.response_lineage(r, rows=[1], upstream="raw")
    # request row 1 aligned to exported row 2, which is raw row 3
    np.testing.assert_array_equal(got, [3])
    got = engine.response_lineage_batch(r, [[0], [1], [2]], upstream="prep/raw")
    assert [g.tolist() for g in got] == [[0], [3], [3]]


def test_serve_engine_prov_index_shim_warns_once_and_matches():
    prep = ProvenanceIndex("prep-shim")
    s = track(Table.from_columns({"k": np.arange(6, dtype=np.float32),
                                  "x": np.ones(6, np.float32)}), prep, "raw")
    clean = s.filter_rows(np.array([1, 0, 1, 1, 0, 1], bool))
    clean.mark_sink()
    serve_engine._DEPRECATION_WARNED.discard("prov_index")
    with pytest.warns(DeprecationWarning, match="prov_index"):
        e = ServeEngine("serve:shim", prov_index=prep)
    # single-entry catalog wrap: the engine records INTO the passed index
    assert e.prov is prep
    assert list(e.catalog.members) == ["serve"]
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        ServeEngine("serve:shim2", prov_index=prep)   # silent now
    # identical lineage to the legacy merged-index behavior
    r = e.record(np.zeros((3, 2), np.int32), prompt_len=2,
                 request_ids=np.array([0, 2, 2]),
                 request_source=clean.dataset_id)
    got = e.response_lineage(r, rows=[1], upstream="raw")
    np.testing.assert_array_equal(got, tqp.ref_q2(prep, r.response_dataset,
                                                  [1], "raw"))
    with pytest.raises(ValueError, match="not both"):
        ServeEngine("serve:both", upstream=prep.export("raw"),
                    prov_index=prep)


# ===========================================================================
# explain / stats: per-segment visibility, per-index aggregation
# ===========================================================================
def test_explain_surfaces_per_segment_strategy_and_cost():
    base, specs = _random_specs(9)
    catalog, refs, sink_ref = _build_federated(base, specs, 2)
    sess = catalog.session()
    plan = (prov(catalog).source(sink_ref).rows([0])
            .backward().to(refs[0]).plan())
    ex = sess.explain(plan)
    assert ex["federated"] is True
    assert ex["strategy"] == "federated"
    assert len(ex["segments"]) >= 2
    seen_indexes = set()
    for seg in ex["segments"]:
        assert seg["strategy"] in ("walk", "hopcache")
        assert "segment" in seg and "->" in seg["segment"]
        seen_indexes.add(seg["index"])
    assert seen_indexes == {"prep", "serve"}        # one+ segment PER side
    assert len(ex["links"]) == 1
    # single-member plans surface the inner planner verdict + owning index
    ex1 = sess.explain(prov(catalog).source(refs[0]).rows([0]).forward()
                       .to(refs[1]).plan())
    assert ex1["federated"] is False
    assert ex1["index"] == split_ref(refs[1])[0]
    assert ex1["strategy"] in ("walk", "hopcache")


def test_stats_aggregate_per_index_under_registered_name():
    base, specs = _random_specs(10)
    catalog, refs, sink_ref = _build_federated(base, specs, 2)
    sess = catalog.session()
    (prov(catalog).source(sink_ref).rows([0]).backward().to(refs[0])
     .run(sess))
    st = sess.stats()
    assert set(st) == {"federation", "indexes"}
    assert set(st["indexes"]) == {"prep", "serve"}
    for name in ("prep", "serve"):
        inner = st["indexes"][name]
        assert inner["index"] == name                # registered == owning
        assert inner["planner"]["plans"] >= 1        # each side executed
        assert "hits" in inner["hopcache"]
    fed = st["federation"]
    assert fed["plans"] == 1 and fed["federated"] == 1
    assert fed["segments"] >= 2 and fed["links_crossed"] == 1
    # catalog.stats() is the same aggregation
    assert ProvCatalog.stats(catalog)["federation"]["plans"] == 1


def test_shared_session_on_catalog():
    base, specs = _random_specs(11)
    catalog, refs, sink_ref = _build_federated(base, specs, 1)
    s1 = catalog.session()
    assert catalog.session() is s1
    with pytest.raises(ValueError):
        catalog.session(nope=1)


# ===========================================================================
# IR plumbing
# ===========================================================================
def test_plan_refs_enumerate_footprint():
    p = QueryPlan(kind="record", source="a/x", target="b/y", direction="fwd",
                  rows=np.ones((1, 3), bool))
    assert p.refs() == ("a/x", "b/y")
    p = QueryPlan(kind="co_dependency", source="m", target="d3", anchor="d1",
                  rows=np.ones((1, 3), bool))
    assert set(p.refs()) == {"m", "d3", "d1"}


def test_split_ref_and_link_repr():
    assert split_ref("prep/a#1") == ("prep", "a#1")
    assert split_ref("prep/a/b") == ("prep", "a/b")
    with pytest.raises(FederationError):
        split_ref("unqualified")
    with pytest.raises(FederationError):
        split_ref("/ds")
    link = Link(up="a/x", down="b/y", alignment=None)
    up = np.zeros((2, 4), bool)
    up[0, 1] = True
    np.testing.assert_array_equal(link.stitch_down(up, 4), up)
    np.testing.assert_array_equal(link.stitch_up(up, 4), up)


# ===========================================================================
# Catalog-owned cross-relation store + the cost-model gate
# ===========================================================================
def test_cross_store_shared_across_sessions():
    """The stitched-relation store lives on the CATALOG: a second session
    over the same catalog reuses hot relations the first one composed —
    the serving-tier pattern, where short-lived sessions front one
    long-lived catalog."""
    pytest.importorskip("scipy")
    base, specs = _random_specs(21)
    catalog, refs, sink_ref = _build_federated(base, specs, 2)
    a = FederatedSession(catalog, cross_min_demand=0)
    plan = (prov(catalog).source(sink_ref).rows([0])
            .backward().to(refs[0]).plan())
    got = a.run(plan)
    assert a.counters["cross_composes"] == 1
    b = FederatedSession(catalog, cross_min_demand=0)
    assert b._store is a._store is catalog._cross_store
    assert len(b._cross) == 1           # visible before b ever ran a plan
    np.testing.assert_array_equal(np.asarray(b.run(plan)), np.asarray(got))
    assert b.counters["cross_composes"] == 0         # reused, not recomposed
    assert b.counters["cross_probes"] == 1


def test_cost_gate_budget_zero_never_stitches():
    """Default gate (``cross_min_demand=None``): a stitched relation that
    cannot be retained under the byte budget never amortizes, so the gate
    keeps segment execution forever — and the segment answers must equal
    the stitched ones."""
    pytest.importorskip("scipy")
    base, specs = _random_specs(22)
    catalog, refs, sink_ref = _build_federated(base, specs, 2)
    sess = FederatedSession(catalog, cross_budget_bytes=0)
    plan = (prov(catalog).source(sink_ref).rows([0])
            .backward().to(refs[0]).plan())
    got = sess.run(plan)
    for _ in range(40):                  # demand far past any fixed floor
        np.testing.assert_array_equal(np.asarray(sess.run(plan)),
                                      np.asarray(got))
    assert sess.counters["cross_composes"] == 0
    assert sess.counters["segments"] > 0
    # demand is still tracked (the gate re-evaluates as budget/stats move)
    assert any(v > 40 for v in sess._route_demand.values())
    # identical world, permissive budget: the stitched answer matches
    base2, specs2 = _random_specs(22)
    catalog2, _, sink_ref2 = _build_federated(base2, specs2, 2)
    stitch = FederatedSession(catalog2, cross_min_demand=0)
    plan2 = (prov(catalog2).source(sink_ref2).rows([0])
             .backward().to(refs[0]).plan())
    np.testing.assert_array_equal(np.asarray(stitch.run(plan2)),
                                  np.asarray(got))
    assert stitch.counters["cross_composes"] == 1


def test_cross_route_choose_stats_fallback_demand_floor():
    """A route with any unpriceable hop falls back to the legacy demand
    floor instead of a cost estimate."""
    from repro.core.costmodel import (
        CROSS_FALLBACK_MIN_DEMAND,
        cross_route_choose,
    )

    v = cross_route_choose([None], 0.0, 1, CROSS_FALLBACK_MIN_DEMAND - 1)
    assert (v["strategy"], v["estimated"]) == ("segments", False)
    v = cross_route_choose([None], 0.0, 1, CROSS_FALLBACK_MIN_DEMAND)
    assert (v["strategy"], v["estimated"]) == ("stitched", False)


def test_member_relation_stats_price_the_route():
    """The gate's inputs: every registered member (index or handle) prices
    a composed relation for the cost model without materializing it."""
    base, specs = _random_specs(23)
    catalog, refs, sink_ref = _build_federated(base, specs, 2)
    for name, member in catalog.members.items():
        local = [split_ref(r)[1] for r in (list(refs) + [sink_ref])
                 if split_ref(r)[0] == name]
        if len(local) < 2:
            continue
        rel, ns = member.relation_stats(local[0], local[-1])
        assert ns >= 0.0
        if rel is not None:
            assert rel.rows > 0 and rel.cols > 0 and rel.nnz >= 0
