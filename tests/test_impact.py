"""Impact-analysis subsystem: erasure closure, RecomputePlan ordering,
hop-cache/cross-relation invalidation, what-if replay exactness, federated
erasure, and the serving-tier entry point."""
import numpy as np
import pytest

import pipegen
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import (
    FederationError,
    apply_invalidations,
    erasure_plan,
    prov,
    whatif_replay,
)
from repro.provenance.catalog import qualify

SEEDS = list(range(6))


# ---------------------------------------------------------------------------
# Erasure closure: batched plan == per-row production queries
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", SEEDS)
def test_erasure_closure_matches_per_row_queries(seed):
    idx, sink, rng = pipegen.random_pipeline(seed)
    n = idx.datasets["src"].n_rows
    rows = sorted(set(rng.integers(0, n, size=4).tolist()))
    plan = erasure_plan(idx, "src", rows)

    got = {i.ref: i.rows for i in plan.impacts}
    # naive reference: one forward record query per (erased row, dataset)
    for ds in idx.datasets:
        expected = np.zeros(idx.datasets[ds].n_rows, dtype=bool)
        for r in rows:
            hit = prov(idx).source("src").rows([r]).forward().to(ds).run()
            expected[np.asarray(hit, dtype=np.int64)] = True
        if expected.any():
            assert ds in got, ds
            np.testing.assert_array_equal(got[ds], np.flatnonzero(expected))
        else:
            assert ds not in got, ds
    # minimal: every listed impact is non-empty, sources lead the plan
    assert all(i.n_affected > 0 for i in plan.impacts)
    assert plan.impacts[0].ref == "src"
    np.testing.assert_array_equal(plan.impacts[0].rows, rows)


@pytest.mark.parametrize("seed", SEEDS)
def test_plan_topologically_ordered_and_classified(seed):
    idx, sink, rng = pipegen.random_pipeline(seed)
    plan = erasure_plan(idx, "src", [0, 1])
    order = [ds for ds in idx.datasets if ds in set(plan.affected)]
    assert list(plan.affected) == order  # registration order IS topological
    for i in plan.impacts:
        rec = idx.datasets[i.ref]
        assert i.materialized == rec.materialized
        assert i.is_sink == rec.is_sink
        assert i.n_rows == rec.n_rows
    assert "src" not in plan.rebuild
    assert all(idx.datasets[r].materialized for r in plan.rebuild)
    # rebuild targets carry a cost estimate when the cost model has a path
    if plan.rebuild:
        assert plan.est_total_ns >= 0.0


def test_erasure_rejects_bad_rows_and_unknown_source():
    idx, sink, _ = pipegen.random_pipeline(0)
    with pytest.raises(KeyError):
        erasure_plan(idx, "nope", [0])
    with pytest.raises(IndexError):
        erasure_plan(idx, "src", [idx.datasets["src"].n_rows + 5])


# ---------------------------------------------------------------------------
# Cache invalidation
# ---------------------------------------------------------------------------
def test_invalidation_drops_stale_entries_and_is_idempotent():
    idx, sink, rng = pipegen.random_pipeline(3)
    comp = idx.composed()
    comp.relation("src", sink)  # composes + caches every (src, mid) prefix
    assert comp.stats()["entries"] > 0
    plan = erasure_plan(idx, "src", [0])
    assert plan.invalidations
    assert {i.kind for i in plan.invalidations} == {"composed"}
    assert apply_invalidations(idx, plan) == len(plan.invalidations)
    assert comp.stats()["entries"] == 0
    # idempotent: a fresh plan over the emptied cache lists nothing
    plan2 = erasure_plan(idx, "src", [0])
    assert not plan2.invalidations
    assert apply_invalidations(idx, plan2) == 0
    # the cache still answers (recomposes from the intact tensors)
    hit = comp.q1_forward("src", [0], sink)
    ref = prov(idx).source("src").rows([0]).forward().to(sink).run()
    np.testing.assert_array_equal(hit, ref)


def test_invalidation_deletes_spilled_payloads(tmp_path):
    idx, sink, rng = pipegen.random_pipeline(3)
    # bitplane entries carry real bytes, so a tiny budget forces spills
    comp = idx.composed(memory_budget_bytes=512, spill=str(tmp_path),
                        backend="bitplane")
    comp.relation("src", sink)
    stats = comp.stats()
    assert stats["spilled_entries"] > 0  # tiny budget forces the spill tier
    n_payloads = comp._spill_store.stats()["entries"]
    plan = erasure_plan(idx, "src", [0])
    residencies = {i.residency for i in plan.invalidations}
    assert "spilled" in residencies
    apply_invalidations(idx, plan)
    assert comp.stats()["entries"] == 0
    assert comp.stats()["spilled_entries"] == 0
    assert comp._spill_store.stats()["entries"] < n_payloads


def test_invalidation_spares_unrelated_entries():
    idx = ProvenanceIndex("inv-spare")
    rng = np.random.default_rng(0)
    a = track(Table.from_columns({
        "k": np.arange(10, dtype=np.float32),
        "x": rng.normal(size=10).astype(np.float32)}), idx, "a")
    b = track(Table.from_columns({
        "k": np.arange(10, dtype=np.float32),
        "z": rng.normal(size=10).astype(np.float32)}), idx, "b")
    b2 = b.value_transform("z", "scale", factor=3.0)
    j = a.join(b2, on="k", how="inner").mark_sink()
    comp = idx.composed()
    comp.relation("a", j.dataset_id)
    comp.relation("b", b2.dataset_id)     # region {b, b2}: off the closure
    plan = erasure_plan(idx, "a", [0, 1])
    stale = {(i.src, i.dst) for i in plan.invalidations}
    assert ("b", b2.dataset_id) not in stale
    apply_invalidations(idx, plan)
    assert comp.residency("b", b2.dataset_id) == "ram"  # survived
    assert comp.residency("a", j.dataset_id) is None    # dropped


# ---------------------------------------------------------------------------
# What-if replay: exactness against a full pipeline re-run
# ---------------------------------------------------------------------------
def _whatif_pipeline(base: Table, keep: np.ndarray, ref1: Table, ref2: Table,
                     name: str):
    """A frozen-choice pipeline over every recomputable category: the same
    selections/params applied to the original and the perturbed base give
    the full-re-run ground truth what-if replay must match exactly."""
    idx = ProvenanceIndex(name)
    cur = track(base.copy(), idx, "src")
    cur = cur.value_transform("x", "scale", factor=2.0)
    cur = cur.filter_rows(keep)
    cur = cur.join(track(ref1.copy(), idx), on="k", how="outer")
    cur = cur.oversample(frac=0.4, seed=5, noise=0.1)
    cur = cur.append(track(ref2.copy(), idx))
    cur.mark_sink()
    return idx, cur.dataset_id, cur.table


def _assert_rows_equal(a: Table, b: Table, rows_a, rows_b):
    np.testing.assert_array_equal(a.null[rows_a], b.null[rows_b])
    da, db = a.data[rows_a], b.data[rows_b]
    ok = ~a.null[rows_a]
    np.testing.assert_allclose(da[ok], db[ok], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("seed", range(4))
def test_whatif_replay_matches_full_rerun(seed):
    rng = np.random.default_rng(seed)
    n, K = 30, 8
    base = Table.from_columns({
        "k": rng.integers(0, K, n).astype(np.float32),
        "x": rng.normal(size=n).astype(np.float32),
    })
    keep = rng.random(n) < 0.7
    if not keep.any():
        keep[0] = True
    ref1 = Table.from_columns({
        "k": np.arange(K, dtype=np.float32),
        "z": rng.normal(size=K).astype(np.float32)})
    ref2 = Table.from_columns({
        "x": rng.normal(size=4).astype(np.float32),
        "z": rng.normal(size=4).astype(np.float32)})
    idx, sink, orig_sink = _whatif_pipeline(base, keep, ref1, ref2,
                                            f"wi{seed}")

    rows = sorted(set(rng.integers(0, n, size=3).tolist()))
    vals = rng.normal(size=len(rows)).astype(np.float32) * 10
    res = whatif_replay(idx, "src", rows, {"x": vals}, sink)

    # ground truth: the SAME frozen pipeline over the perturbed base
    patched = base.copy()
    patched.data[np.asarray(rows), patched.cid("x")] = vals
    _, _, full_sink = _whatif_pipeline(patched, keep, ref1, ref2,
                                       f"wi{seed}-rerun")

    # before == recorded run; after == full re-run, on exactly the
    # provenance-related sink rows
    _assert_rows_equal(res.before, orig_sink, np.arange(len(res.sink_rows)),
                       res.sink_rows)
    _assert_rows_equal(res.after, full_sink, np.arange(len(res.sink_rows)),
                       res.sink_rows)
    # completeness: every sink row OUTSIDE the closure is untouched by the
    # full re-run — the closure missed nothing
    outside = np.setdiff1d(np.arange(orig_sink.n_rows), res.sink_rows)
    _assert_rows_equal(orig_sink, full_sink, outside, outside)
    # and the replay recomputed ONLY provenance-related rows
    assert len(res.sink_rows) < orig_sink.n_rows
    # deltas line up with the changed mask
    deltas = res.row_deltas()
    assert len(deltas) == len(res.sink_rows)
    for i, d in enumerate(deltas):
        assert bool(d) == bool(res.changed[i])


def test_whatif_restores_recorded_state():
    rng = np.random.default_rng(1)
    idx, sink, _ = pipegen.random_pipeline(5)
    src_rec = idx.datasets["src"]
    before_tables = {ds: r.table for ds, r in idx.datasets.items()}
    before_x = src_rec.table.data.copy()
    whatif_replay(idx, "src", [0], {"x": [99.0]}, sink)
    for ds, r in idx.datasets.items():
        assert r.table is before_tables[ds]   # same objects, policy intact
    np.testing.assert_array_equal(src_rec.table.data, before_x)


def test_whatif_over_catalog_delegates_within_member():
    base, specs = pipegen.random_specs(2)
    catalog, refs, sink_ref = pipegen.build_federated(base, specs, 1)
    ingest = catalog.datasets["serve/ingest"]
    res = whatif_replay(catalog, "serve/ingest", [0], {"x": [50.0]},
                        sink_ref)
    assert res.source == "serve/ingest" and res.sink == sink_ref
    # value recomputation never crosses members
    with pytest.raises(FederationError, match="never leave"):
        whatif_replay(catalog, "prep/src", [0], {"x": [1.0]}, sink_ref)


# ---------------------------------------------------------------------------
# Federated erasure: closure across links == merged single-index closure
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("cut", [1, 2])
def test_federated_erasure_matches_merged(seed, cut):
    base, specs = pipegen.random_specs(seed)
    merged, ids = pipegen.build_merged(base, specs)
    catalog, refs, sink_ref = pipegen.build_federated(base, specs, cut)
    ref_of = dict(zip(ids, refs))

    rows = [0, min(3, len(base["k"]) - 1)]
    mplan = erasure_plan(merged, "src", rows)
    fplan = erasure_plan(catalog, "prep/src", rows)
    f_by_ref = {i.ref: i.rows for i in fplan.impacts}

    boundary_ref = refs[cut]
    for i in mplan.impacts:
        if i.ref not in ref_of:
            continue  # a join/append side table: not represented federated
        np.testing.assert_array_equal(f_by_ref[ref_of[i.ref]], i.rows,
                                      err_msg=ref_of[i.ref])
    # the boundary dataset appears on BOTH sides of the identity link
    if boundary_ref in f_by_ref:
        np.testing.assert_array_equal(f_by_ref["serve/ingest"],
                                      f_by_ref[boundary_ref])
    # member-topological order: every prep impact precedes every serve one
    members = [i.ref.split("/")[0] for i in fplan.impacts]
    assert members == sorted(members, key=["prep", "serve"].index)


def test_federated_erasure_lists_cross_relation_invalidations():
    sp = pytest.importorskip("scipy.sparse")
    base, specs = pipegen.random_specs(0)
    catalog, refs, sink_ref = pipegen.build_federated(base, specs, 2)
    sess = catalog.session()
    link = catalog.links[0]
    # a stitched cross-relation over the route the erasure poisons
    store = catalog._cross_store
    store.put(("prep/src", sink_ref, "fwd"),
              sp.identity(4, dtype=np.float32, format="csr"),
              frozenset({(link.up, link.down)}))
    # and per-member composed entries
    prep_idx = catalog.members["prep"]._index
    prep_idx.composed().relation("src", refs[2].split("/")[1])
    plan = erasure_plan(catalog, "prep/src", [0])
    kinds = {i.kind for i in plan.invalidations}
    assert "cross" in kinds and "composed" in kinds
    cross = [i for i in plan.invalidations if i.kind == "cross"]
    assert cross[0].src == "prep/src" and cross[0].dst == sink_ref
    dropped = apply_invalidations(catalog, plan)
    assert dropped == len(plan.invalidations)
    assert ("prep/src", sink_ref, "fwd") not in store.entries
    assert prep_idx.composed().stats()["entries"] == 0


def test_federated_erasure_through_boundary_handle():
    """An upstream member registered as a read-only capability still
    closes downstream — and the plan carries no invalidations for caches
    the capability cannot touch."""
    from repro.provenance import ProvCatalog

    rng = np.random.default_rng(0)
    prep = ProvenanceIndex("prep-cap")
    s = track(Table.from_columns({
        "k": np.arange(12, dtype=np.float32),
        "x": rng.normal(size=12).astype(np.float32)}), prep, "raw")
    clean = s.value_transform("x", "scale", factor=2.0)
    clean.mark_sink()
    serve = ProvenanceIndex("serve-cap")
    ing = track(clean.table, serve, "ingest")
    out = ing.filter_rows(rng.random(12) < 0.8)
    out.mark_sink()
    catalog = ProvCatalog("cap")
    catalog.register("prep", prep.export(clean.dataset_id))
    catalog.register("serve", serve)
    catalog.link(qualify("prep", clean.dataset_id), "serve/ingest")

    plan = erasure_plan(catalog, "prep/raw", [0, 1])
    refs = set(plan.affected)
    assert qualify("prep", clean.dataset_id) in refs
    assert "serve/ingest" in refs
    assert all(i.scope != "prep" for i in plan.invalidations)


# ---------------------------------------------------------------------------
# Serving-tier entry point
# ---------------------------------------------------------------------------
def test_serve_engine_erasure_impact():
    from repro.serve.engine import ServeEngine

    rng = np.random.default_rng(0)
    prep = ProvenanceIndex("prep-serve")
    s = track(Table.from_columns({
        "k": np.arange(16, dtype=np.float32),
        "x": rng.normal(size=16).astype(np.float32)}), prep, "raw")
    clean = s.value_transform("x", "scale", factor=2.0)
    clean.mark_sink()

    engine = ServeEngine("serve:test", upstream=prep.export(clean.dataset_id))
    # simulate one recorded request batch linked to upstream rows
    req = Table.from_columns({
        "x": rng.normal(size=4).astype(np.float32)})
    track(req, engine.prov, "requests@0").mark_sink()
    up_name, boundary = engine._upstream
    engine.catalog.link(qualify(up_name, boundary),
                        qualify(engine._serve_name, "requests@0"),
                        alignment=np.array([2, 5, 7, 2]))

    plan = engine.erasure_impact([2])   # defaults to the upstream boundary
    by_ref = {i.ref: i.rows for i in plan.impacts}
    assert qualify(up_name, boundary) in by_ref
    # upstream row 2 backs requests 0 and 3
    np.testing.assert_array_equal(
        by_ref[qualify(engine._serve_name, "requests@0")], [0, 3])
    with pytest.raises(ValueError, match="source="):
        ServeEngine("serve:bare").erasure_impact([0])
