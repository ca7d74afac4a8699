"""Differential parity: mesh-sharded provenance index vs the merged engine.

The sharded index is a pure re-partitioning — every answer must be
BYTE-IDENTICAL to the single-host engine, which these suites pin three ways:

* **seeded differential sweep** (always runs) — pipegen pipelines at
  1/2/4/8 shards plus shard counts that do not divide ``n`` evenly and the
  ``n_shards == n`` single-row/empty-shard extreme, across every plan kind
  the session plans (forward/backward record, batched, co-queries, how
  traces, cells), empty probes, and ``-1`` sentinels (outer joins/appends);
* **Hypothesis properties** (runs where hypothesis is installed) — free
  choice of seed x shard count x probe set, minimized on failure;
* **federation seam** — ``as_catalog`` registers each shard as a
  ``ProvCatalog`` member glued by range-alignment links; probes across the
  seam must match the merged engine on BOTH the cold per-segment path and
  the hot stitched-cross-relation path.

Both execution engines are covered: the sequential ``numpy`` join loop
everywhere, and the ``shard_map`` collective engine wherever the host
exposes enough devices (CI's multi-device lane forces 8 via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import pipegen
import test_query_parity as tqp
from repro.core.provtensor import ProvTensor, SlotGather, shard_ranges
from repro.provenance import ShardedProvenanceIndex, prov

SHARD_COUNTS = [1, 2, 4, 8]
SEEDS = list(range(8))


def _mask_stacks_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a, bool), np.asarray(b, bool))


def _per_probe_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def _sharded_sessions(idx, n_shards):
    """Both engines when available; the numpy fallback always."""
    views = [ShardedProvenanceIndex(idx, n_shards, engine="numpy")]
    auto = ShardedProvenanceIndex(idx, n_shards)
    if auto.engine_name == "collective":
        views.append(auto)
    return views


# ===========================================================================
# Seeded differential sweep (always runs)
# ===========================================================================
@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("seed", SEEDS)
def test_record_mask_stack_parity(seed, n_shards):
    """Raw (B, n) mask stacks — forward and backward, hopcache and walk —
    byte-identical to the merged engine."""
    idx, sink, rng = pipegen.random_pipeline(seed, name="shardp")
    merged = idx.session()
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    fwd = pipegen.row_probes(rng, n_src) + [[]]
    bwd = pipegen.row_probes(rng, n_sink)
    pf = prov(idx).source("src").rows_batch(fwd).forward().to(sink).plan()
    pb = prov(idx).source(sink).rows_batch(bwd).backward().to("src").plan()
    want_f = merged.run_masks(pf)
    want_b = merged.run_masks(pb)
    for sv in _sharded_sessions(idx, n_shards):
        for use_hopcache in (True, False):
            ss = tqp.QuerySession(sv, use_hopcache=use_hopcache)
            _mask_stacks_equal(ss.run_masks(
                prov(sv).source("src").rows_batch(fwd)
                .forward().to(sink).plan()), want_f)
            _mask_stacks_equal(ss.run_masks(
                prov(sv).source(sink).rows_batch(bwd)
                .backward().to("src").plan()), want_b)


@pytest.mark.parametrize("n_shards", [3, 5, 7])
@pytest.mark.parametrize("seed", SEEDS[:4])
def test_uneven_shard_counts(seed, n_shards):
    """Shard counts that do NOT divide n evenly: the remainder rows spread
    one-per-shard and every range boundary still concatenates exactly."""
    idx, sink, rng = pipegen.random_pipeline(seed, name="uneven")
    merged = idx.session()
    n_src = idx.datasets["src"].n_rows
    assert n_src % n_shards != 0 or True  # layout correctness either way
    for dst in idx.datasets:
        rows = pipegen.row_probes(rng, n_src)
        plan = prov(idx).source("src").rows_batch(rows).forward().to(dst).plan()
        want = merged.run_masks(plan)
        sv = ShardedProvenanceIndex(idx, n_shards, engine="numpy")
        got = sv.session().run_masks(
            prov(sv).source("src").rows_batch(rows).forward().to(dst).plan())
        _mask_stacks_equal(got, want)


@pytest.mark.parametrize("seed", SEEDS[:4])
def test_single_row_shards(seed):
    """n_shards == n_rows of the sink: every shard holds at most one row
    (and PADS to one row when n < n_shards leaves empty tails)."""
    idx, sink, rng = pipegen.random_pipeline(seed, name="singlerow")
    merged = idx.session()
    n_sink = idx.datasets[sink].n_rows
    n_src = idx.datasets["src"].n_rows
    sv = ShardedProvenanceIndex(idx, n_sink, engine="numpy")
    rows = pipegen.row_probes(rng, n_src)
    want = merged.run_masks(
        prov(idx).source("src").rows_batch(rows).forward().to(sink).plan())
    got = sv.session().run_masks(
        prov(sv).source("src").rows_batch(rows).forward().to(sink).plan())
    _mask_stacks_equal(got, want)


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("seed", SEEDS[:4])
def test_co_queries_and_how_parity(seed, n_shards):
    """Co-contributory / co-dependency / how traces through the sharded
    session — the walkers and hop-cache routing must agree with merged."""
    idx, sink, rng = pipegen.random_pipeline(seed, name="shardco")
    merged = idx.session()
    n_src = idx.datasets["src"].n_rows
    mids = [d for d in idx.datasets
            if d not in ("src", sink) and idx.path_exists("src", d)
            and idx.path_exists(d, sink)]
    for sv in _sharded_sessions(idx, n_shards):
        ss = sv.session()
        rows = [int(rng.integers(0, n_src))]
        # co_contributory with explicit via at the sink
        for d2 in mids[:2]:
            a = merged.run(prov(idx).source("src").rows(rows)
                           .co_contributory(d2, via=sink).plan())
            b = ss.run(prov(sv).source("src").rows(rows)
                       .co_contributory(d2, via=sink).plan())
            np.testing.assert_array_equal(a, b)
        # co_dependency anchored at src, answered at sink
        for mid in mids[:2]:
            n_mid = idx.datasets[mid].n_rows
            mrows = [int(rng.integers(0, n_mid))]
            a = merged.run(prov(idx).source(mid).rows(mrows)
                           .co_dependency("src", sink).plan())
            b = ss.run(prov(sv).source(mid).rows(mrows)
                       .co_dependency("src", sink).plan())
            np.testing.assert_array_equal(a, b)
        # how traces: records + hop list must match exactly
        a_recs, a_hops = merged.run(prov(idx).source(sink).rows([0])
                                    .backward().to("src").how().plan())
        b_recs, b_hops = ss.run(prov(sv).source(sink).rows([0])
                                .backward().to("src").how().plan())
        np.testing.assert_array_equal(a_recs, b_recs)
        assert [(h.op_id, h.op_name) for h in a_hops] == \
            [(h.op_id, h.op_name) for h in b_hops]


@pytest.mark.parametrize("n_shards", [2, 4])
@pytest.mark.parametrize("seed", SEEDS[:4])
def test_cells_parity(seed, n_shards):
    """Cell-level lineage through the sharded view (attr maps are shared
    with the base index, so this pins the op-wrapping plumbing)."""
    idx, sink, rng = pipegen.random_pipeline(seed, name="shardcell")
    merged = idx.session()
    n_sink = idx.datasets[sink].n_rows
    rows = [int(rng.integers(0, n_sink))]
    for sv in _sharded_sessions(idx, n_shards):
        ss = sv.session()
        a = merged.run(prov(idx).source(sink).rows(rows).attrs([0])
                       .backward().to("src").plan())
        b = ss.run(prov(sv).source(sink).rows(rows).attrs([0])
                   .backward().to("src").plan())
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("n_shards", SHARD_COUNTS)
@pytest.mark.parametrize("seed", SEEDS[:5])
def test_cross_shard_diamond(seed, n_shards):
    """The multi-producer diamond: per-shard composed blocks must OR both
    paths exactly like the merged multi-path hop-cache."""
    idx, sink = pipegen.diamond_pipeline(seed, name="sharddia")
    merged = idx.session()
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    for sv in _sharded_sessions(idx, n_shards):
        ss = sv.session()
        for rows in ([], [0], [n_src - 1], list(range(n_src))):
            want = tqp.ref_q1(idx, "src", rows, sink)
            got = ss.run(prov(sv).source("src").rows(rows)
                         .forward().to(sink).plan())
            np.testing.assert_array_equal(got, want)
        for rows in ([], [0], list(range(n_sink))):
            want = tqp.ref_q2(idx, sink, rows, "src")
            got = ss.run(prov(sv).source(sink).rows(rows)
                         .backward().to("src").plan())
            np.testing.assert_array_equal(got, want)


def test_empty_probes_and_no_path():
    idx, sink, rng = pipegen.random_pipeline(0, name="shardempty")
    sv = ShardedProvenanceIndex(idx, 4, engine="numpy")
    ss = sv.session()
    got = ss.run(prov(sv).source(sink).rows_batch([]).backward()
                 .to("src").plan())
    assert got == []
    # no dataflow path: all-empty, never an error (the walkers' convention)
    got = ss.run(prov(sv).source(sink).rows([0]).forward().to("src").plan())
    assert got.size == 0


def test_sentinel_slices():
    """-1 sentinels (outer join null side) must survive row slicing: the
    slice keeps the sentinel inside the window and drops rows outside."""
    src = np.array([0, -1, 2, -1, 1], dtype=np.int32)
    t = ProvTensor(n_out=5, n_in=(3,), slots=[SlotGather(src)])
    for lo, hi in shard_ranges(5, 3):
        sl = t.slice_rows(lo, hi)
        np.testing.assert_array_equal(
            sl.slot_gather(0), src[lo:hi])
    # COO form: sentinel rows vanish from pairs but row count is preserved
    coo = np.array([[0, 0], [2, 2], [4, 1]], dtype=np.int32)
    tc = ProvTensor(n_out=5, n_in=(3,), coo=coo)
    for n_shards in (2, 3, 5):
        masks = np.eye(3, dtype=bool)
        sv = [tc.slice_rows(lo, hi) for lo, hi in shard_ranges(5, n_shards)]
        got = np.concatenate(
            [s.forward_mask_batch(0, masks) for s in sv], axis=1)
        np.testing.assert_array_equal(got, tc.forward_mask_batch(0, masks))


def test_shard_ranges_layout():
    assert shard_ranges(10, 4) == [(0, 3), (3, 6), (6, 8), (8, 10)]
    assert shard_ranges(3, 8)[-1] == (3, 3)          # empty tail shards
    assert shard_ranges(0, 2) == [(0, 0), (0, 0)]
    with pytest.raises(ValueError):
        shard_ranges(5, 0)


# ===========================================================================
# The federation seam: shards as catalog members
# ===========================================================================
@pytest.mark.parametrize("n_shards", [2, 4, 8])
@pytest.mark.parametrize("seed", SEEDS[:4])
def test_catalog_seam_parity(seed, n_shards):
    """Cross-shard probes through the PR 4 federation machinery: identity
    fan-out links, per-shard relation ops, range-alignment gather links —
    cold segment path AND hot stitched-cross-relation path."""
    idx, sink, rng = pipegen.random_pipeline(seed, name="shardcat")
    merged = idx.session()
    src = "src"
    n_src = idx.datasets[src].n_rows
    n_sink = idx.datasets[sink].n_rows
    sv = ShardedProvenanceIndex(idx, n_shards, engine="numpy")
    catalog = sv.as_catalog(src, sink)
    fs = catalog.session()
    fwd = pipegen.row_probes(rng, n_src) + [[]]
    bwd = pipegen.row_probes(rng, n_sink)
    want_f = merged.run(
        prov(idx).source(src).rows_batch(fwd).forward().to(sink).plan())
    want_b = merged.run(
        prov(idx).source(sink).rows_batch(bwd).backward().to(src).plan())
    fplan = (prov(catalog).source(f"root/{src}").rows_batch(fwd)
             .forward().to(f"gather/{sink}").plan())
    bplan = (prov(catalog).source(f"gather/{sink}").rows_batch(bwd)
             .backward().to(f"root/{src}").plan())
    _per_probe_equal(fs.run(fplan), want_f)
    _per_probe_equal(fs.run(bplan), want_b)
    # drive cumulative demand past cross_min_demand=32: the stitched
    # cross-relation hot path must answer identically to the cold walk
    for _ in range(12):
        hot_f = fs.run(fplan)
        hot_b = fs.run(bplan)
    _per_probe_equal(hot_f, want_f)
    _per_probe_equal(hot_b, want_b)


def test_catalog_seam_diamond():
    """Cross-shard diamond THROUGH the seam: multi-producer relation blocks
    distributed over 4 shard members still OR both paths."""
    idx, sink = pipegen.diamond_pipeline(1, name="shardcatdia")
    merged = idx.session()
    sv = ShardedProvenanceIndex(idx, 4, engine="numpy")
    catalog = sv.as_catalog("src", sink)
    fs = catalog.session()
    n_src = idx.datasets["src"].n_rows
    probes = [[], [0], list(range(n_src))]
    want = merged.run(
        prov(idx).source("src").rows_batch(probes).forward().to(sink).plan())
    got = fs.run(prov(catalog).source("root/src").rows_batch(probes)
                 .forward().to(f"gather/{sink}").plan())
    _per_probe_equal(got, want)


# ===========================================================================
# The collective engine (requires a multi-device host)
# ===========================================================================
def _devices():
    import jax

    return len(jax.devices())


@pytest.mark.parametrize("n_shards", [2, 4, 8])
@pytest.mark.parametrize("seed", SEEDS[:4])
def test_collective_engine_parity(seed, n_shards):
    """shard_map all_gather/psum walkers vs merged — CI's multi-device lane
    exercises this at 8 devices; single-device hosts skip."""
    if _devices() < n_shards:
        pytest.skip(f"needs {n_shards} devices, have {_devices()}")
    idx, sink, rng = pipegen.random_pipeline(seed, name="shardcoll")
    merged = idx.session()
    n_src = idx.datasets["src"].n_rows
    n_sink = idx.datasets[sink].n_rows
    sv = ShardedProvenanceIndex(idx, n_shards, engine="collective")
    assert sv.engine_name == "collective"
    ss = sv.session(use_hopcache=False)   # force the collective walkers
    fwd = pipegen.row_probes(rng, n_src) + [[]]
    bwd = pipegen.row_probes(rng, n_sink)
    _mask_stacks_equal(
        ss.run_masks(prov(sv).source("src").rows_batch(fwd)
                     .forward().to(sink).plan()),
        merged.run_masks(prov(idx).source("src").rows_batch(fwd)
                         .forward().to(sink).plan()))
    _mask_stacks_equal(
        ss.run_masks(prov(sv).source(sink).rows_batch(bwd)
                     .backward().to("src").plan()),
        merged.run_masks(prov(idx).source(sink).rows_batch(bwd)
                         .backward().to("src").plan()))


# ===========================================================================
# Hypothesis properties (free seed x shards x probes, minimized on failure).
# ===========================================================================
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n_shards=st.integers(1, 12),
       data=st.data())
def test_prop_record_parity(seed, n_shards, data):
    idx, sink, _ = pipegen.random_pipeline(seed, name="hyp")
    merged = idx.session()
    n_src = idx.datasets["src"].n_rows
    probes = data.draw(st.lists(
        st.lists(st.integers(0, n_src - 1), max_size=6), max_size=4))
    sv = ShardedProvenanceIndex(idx, n_shards, engine="numpy")
    ss = sv.session()
    plan_m = (prov(idx).source("src").rows_batch(probes)
              .forward().to(sink).plan())
    plan_s = (prov(sv).source("src").rows_batch(probes)
              .forward().to(sink).plan())
    _mask_stacks_equal(ss.run_masks(plan_s), merged.run_masks(plan_m))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), n_shards=st.integers(2, 10))
def test_prop_diamond_backward_parity(seed, n_shards):
    idx, sink = pipegen.diamond_pipeline(seed % 50, name="hypdia")
    merged = idx.session()
    n_sink = idx.datasets[sink].n_rows
    probes = [[], [0], list(range(n_sink))]
    sv = ShardedProvenanceIndex(idx, n_shards, engine="numpy")
    plan_m = (prov(idx).source(sink).rows_batch(probes)
              .backward().to("src").plan())
    plan_s = (prov(sv).source(sink).rows_batch(probes)
              .backward().to("src").plan())
    _mask_stacks_equal(sv.session().run_masks(plan_s),
                       merged.run_masks(plan_m))
