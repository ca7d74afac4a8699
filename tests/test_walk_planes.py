"""The fused walk's plane operand kept on the device between calls.

Pins (a) that prepared and walked apart, the kernel answers exactly as in
one go; (b) that walks through a :class:`~repro.core.query.WalkPlaneStore`
answer byte for byte as walks without one, over randomized pipelines, both
directions, on the oracle and the interpret-mode Pallas path; (c) the
store's counters and the bytes a hit puts (the mask alone); (d) LRU
eviction and an operand over the budget, under a patched budget; (e) that
the linearity audit still runs before any lookup; (f) that a chain whose
tensors were spilled and faulted back still hits.  Every chain here has a
hop captured as explicit COO, so the fused walk routes it to the dense
planes (a chain of structured hops walks its row map instead:
``test_gather_walk.py``).
"""
from collections import defaultdict

import jax.numpy as jnp
import numpy as np
import pytest

import pipegen
from repro.core import query as Q
from repro.core.capture import force_coo_capture
from repro.core.pipeline import ProvenanceIndex
from repro.core.provtensor import pack_bitplane
from repro.core.spill import SpillPolicy, _TensorFault
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.kernels import ops as K
from repro.kernels import ref as R
from repro.provenance import QuerySession, prov

PLANE_COUNTERS = ("walk_plane_hits", "walk_plane_misses",
                  "walk_plane_evictions", "walk_plane_bytes")


def _chain(n=60, seed=0, index=None):
    """raw -> kept -> scaled: a linear chain, so record plans fuse; the
    filter is captured as COO, so they take the dense planes."""
    rng = np.random.default_rng(seed)
    idx = index if index is not None else ProvenanceIndex(f"planes-{seed}")
    t = track(Table.from_columns({
        "k": np.arange(n, dtype=np.float32),
        "x": rng.normal(size=n).astype(np.float32),
    }), idx, "raw")
    with force_coo_capture():
        kept = t.filter_rows(rng.random(n) > 0.3)
    sink = kept.value_transform("x", "scale", factor=2.0)
    sink.mark_sink()
    return idx, kept, sink


def _plan(idx, src, dst, direction, rng, batch=4):
    n = idx.datasets[src].n_rows
    rows = rng.random((batch, n)) < 0.3
    q = prov(idx).source(src).rows_batch(rows)
    q = q.forward() if direction == "fwd" else q.backward()
    return q.to(dst).plan()


def _host(idx, plan):
    return QuerySession(idx, use_hopcache=False, fused_walk=False).run(plan)


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("hops", [1, 3, 6])
@pytest.mark.parametrize("use_pallas", [None, True])
def test_prepared_walk_matches_the_one_shot_walk(hops, use_pallas):
    rng = np.random.default_rng(hops)
    dims = [int(rng.integers(5, 90)) for _ in range(hops + 1)]
    planes = [R.pack_bits(jnp.asarray(rng.random((dims[j], dims[j + 1]))
                                      < 0.1)) for j in range(hops)]
    prepared = K.prepare_walk_planes(planes, use_pallas=use_pallas)
    for b in (1, 7):
        mask = R.pack_bits(jnp.asarray(rng.random((b, dims[0])) < 0.3))
        got = K.batched_walk_prepared(mask, prepared, block_b=4, block_k=64)
        want = K.batched_walk(mask, planes, use_pallas=use_pallas, block_b=4,
                              block_k=64)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    bad = R.pack_bits(jnp.asarray(rng.random((2, dims[0] + 40)) < 0.3))
    with pytest.raises(ValueError):
        K.batched_walk_prepared(bad, prepared)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("use_pallas", [None, True])
def test_stored_planes_answer_as_fresh_ones(seed, use_pallas):
    with force_coo_capture():  # no structured hop: the dense route
        idx, sink, rng = pipegen.random_pipeline(seed)
    counters = defaultdict(int)
    store = Q.WalkPlaneStore(counters)
    for direction, (src, dst) in (("fwd", ("src", sink)),
                                  ("bwd", (sink, "src"))):
        for _ in range(2):  # a miss, then a hit
            rows = rng.random((3, idx.datasets[src].n_rows)) < 0.3
            fresh = Q.fused_walk_record_masks_batch(
                idx, src, dst, rows, direction, use_pallas=use_pallas)
            kept = Q.fused_walk_record_masks_batch(
                idx, src, dst, rows, direction, use_pallas=use_pallas,
                counters=counters, planes=store)
            if fresh is None:  # the audit refused the chain: no lookup
                assert kept is None
                continue
            np.testing.assert_array_equal(kept, fresh)
    assert counters["walk_plane_hits"] == counters["walk_plane_misses"] > 0
    assert counters["gather_walk"] == counters["gather_walk_declined"] == 0


def test_a_hit_puts_only_the_mask():
    idx, _, sink = _chain()
    sess = QuerySession(idx, use_hopcache=False, fused_walk=True)
    assert all(sess.counters[k] == 0 for k in PLANE_COUNTERS)
    rng = np.random.default_rng(1)
    first = _plan(idx, "raw", sink.dataset_id, "fwd", rng)
    _same(sess.run(first), _host(idx, first))
    c = dict(sess.counters)
    assert (c["walk_plane_misses"], c["walk_plane_hits"]) == (1, 0)
    assert c["walk_plane_bytes"] > 0 and c["walk_plane_evictions"] == 0
    second = _plan(idx, "raw", sink.dataset_id, "fwd", rng)
    _same(sess.run(second), _host(idx, second))
    assert sess.counters["walk_plane_misses"] == 1
    assert sess.counters["walk_plane_hits"] == 1
    mask = pack_bitplane(np.asarray(second.rows, dtype=bool))
    assert sess.counters["walk_put_bytes"] - c["walk_put_bytes"] == mask.nbytes
    assert sess.counters["walk_plane_bytes"] == c["walk_plane_bytes"]


def _both_ways(sess, idx, sink, rng):
    """One walk each way; returns the store's resident bytes after each."""
    seen = []
    for d, (s, t) in (("fwd", ("raw", sink)), ("bwd", (sink, "raw"))):
        plan = _plan(idx, s, t, d, rng)
        before = sess.counters["walk_plane_bytes"]
        _same(sess.run(plan), _host(idx, plan))
        seen.append(sess.counters["walk_plane_bytes"] - before)
    return seen


def test_least_recently_used_planes_are_evicted_past_the_budget(monkeypatch):
    idx, _, sink = _chain()
    sink = sink.dataset_id
    rng = np.random.default_rng(2)
    fwd_bytes, bwd_bytes = _both_ways(
        QuerySession(idx, use_hopcache=False, fused_walk=True), idx, sink, rng)
    assert fwd_bytes > 0 and bwd_bytes > 0
    # room for one direction's operand, not for both
    monkeypatch.setattr(Q, "plane_store_budget",
                        lambda max_plane_bytes: max(fwd_bytes, bwd_bytes))
    sess = QuerySession(idx, use_hopcache=False, fused_walk=True)
    _both_ways(sess, idx, sink, rng)
    c = sess.counters
    assert c["walk_plane_evictions"] == 1
    assert c["walk_plane_bytes"] == bwd_bytes  # fwd, the older, went
    plan = _plan(idx, "raw", sink, "fwd", rng)
    _same(sess.run(plan), _host(idx, plan))
    assert (c["walk_plane_misses"], c["walk_plane_hits"]) == (3, 0)
    assert c["walk_plane_evictions"] == 2 and c["walk_plane_bytes"] == fwd_bytes


def test_planes_over_the_budget_are_used_and_not_kept(monkeypatch):
    monkeypatch.setattr(Q, "plane_store_budget", lambda max_plane_bytes: 1)
    idx, _, sink = _chain()
    sess = QuerySession(idx, use_hopcache=False, fused_walk=True)
    rng = np.random.default_rng(3)
    for _ in range(2):
        assert _both_ways(sess, idx, sink.dataset_id, rng) == [0, 0]
    c = sess.counters
    assert c["fused_walk"] == 4
    assert (c["walk_plane_misses"], c["walk_plane_hits"]) == (4, 0)
    assert c["walk_plane_evictions"] == 0 and c["walk_plane_bytes"] == 0


def test_budget_is_a_share_of_device_memory_or_of_the_cap():
    # the CPU reports no memory: four plane caps
    assert Q.plane_store_budget(256 << 20) == 4 * (256 << 20)


def test_a_side_entrance_still_sends_the_plan_to_the_host_walker():
    idx, kept, sink = _chain()
    sess = QuerySession(idx, use_hopcache=False, fused_walk=True)
    rng = np.random.default_rng(4)
    for _ in range(2):
        plan = _plan(idx, "raw", sink.dataset_id, "fwd", rng)
        _same(sess.run(plan), _host(idx, plan))
    # appended: a join of the chain's middle with its end, so mass from
    # raw enters the new sink by two slots
    joined = kept.join(sink, on="k", how="inner").mark_sink()
    before = dict(sess.counters)
    plan = _plan(idx, "raw", joined.dataset_id, "fwd", rng)
    _same(sess.run(plan), _host(idx, plan))
    for k in ("fused_walk",) + PLANE_COUNTERS:
        assert sess.counters[k] == before[k], k
    assert sess.counters["host_walk_ns"] > before["host_walk_ns"]
    plan = _plan(idx, "raw", sink.dataset_id, "fwd", rng)
    _same(sess.run(plan), _host(idx, plan))
    assert sess.counters["walk_plane_hits"] == before["walk_plane_hits"] + 1


def test_spilled_and_faulted_back_chain_still_hits():
    idx = ProvenanceIndex("planes-spill", spill=SpillPolicy(budget_bytes=256))
    idx, _, sink = _chain(n=200, index=idx)
    sink = sink.dataset_id
    sess = QuerySession(idx, use_hopcache=False, fused_walk=True)
    rng = np.random.default_rng(5)
    plan = _plan(idx, "raw", sink, "fwd", rng)
    _same(sess.run(plan), _host(idx, plan))
    assert sess.counters["walk_plane_misses"] == 1
    for op in idx.ops:  # fault each back in turn: the others spill again
        op.tensor.resident()
    assert any(isinstance(op.tensor, _TensorFault) for op in idx.ops)
    faults = idx.stats()["spill"]["rehydrations"]
    plan = _plan(idx, "raw", sink, "fwd", rng)
    got = sess.run(plan)
    # the hit needs no tensor: nothing was faulted back to answer it
    assert idx.stats()["spill"]["rehydrations"] == faults
    _same(got, _host(idx, plan))  # the host walker faults them back
    plan = _plan(idx, "raw", sink, "fwd", rng)
    _same(sess.run(plan), _host(idx, plan))
    assert sess.counters["walk_plane_misses"] == 1
    assert sess.counters["walk_plane_hits"] == 2
