"""Spans and timing counters at the layer boundaries of the record path.

Pins (a) the session's timing counters after fused walks (each phase inside
its parent, every one above 0) and the exact bytes the walk puts on the
device, (b) that answers do not change while a profiler trace records,
(c) the nesting of ``session.execute`` > ``walk.fused`` > {``walk.put``,
``walk.wait``} on one host thread of a recorded trace, and ``tier.pass``
around them on the tier's executor thread with its pass number, (d) the
tier's pass and queue-wait counters over a counting backend, and (e) that
the federation's ``run_many``, which shares ``run_many_fused`` with the
session, still answers and writes no new counter.
"""
import contextlib
import glob
import os
import threading

import jax
import numpy as np
import pytest

import pipegen
import test_query_parity as tqp
from repro.core.capture import force_coo_capture
from repro.core.compose import path_tensors
from repro.core.pipeline import ProvenanceIndex
from repro.core.provtensor import pack_bitplane
from repro.core.spans import span
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import FederatedSession, QuerySession, prov
from repro.serve import ServingTier

TIMEOUT_S = 60
WALK_SPANS = ("session.execute", "walk.fused", "walk.put", "walk.wait")


def _chain(n=70, seed=0, coo_filter=False):
    """raw -> scaled -> sink: a linear chain, so record plans take the
    fused walk; with ``coo_filter`` the filter is captured as COO, so they
    take its dense planes."""
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex(f"spans-{seed}")
    t = track(Table.from_columns({
        "k": np.arange(n, dtype=np.float32),
        "x": rng.normal(size=n).astype(np.float32),
    }), idx, "raw")
    scaled = t.value_transform("x", "scale", factor=2.0)
    with force_coo_capture() if coo_filter else contextlib.nullcontext():
        sink = scaled.filter_rows(rng.random(n) > 0.3)
    sink.mark_sink()
    return idx, sink.dataset_id


def _session(idx):
    return QuerySession(idx, use_hopcache=False, fused_walk=True)


def _plans(idx, sink, n, batch=3):
    n_raw, n_sink = idx.datasets["raw"].n_rows, idx.datasets[sink].n_rows
    out = []
    for i in range(n):
        if i % 2 == 0:
            rows = [[(i + j) % n_raw, (3 * i + j) % n_raw] for j in range(batch)]
            out.append(prov(idx).source("raw").rows_batch(rows)
                       .forward().to(sink).plan())
        else:
            rows = [[(i + j) % n_sink] for j in range(batch)]
            out.append(prov(idx).source(sink).rows_batch(rows)
                       .backward().to("raw").plan())
    return out


def _recorded(tmp_path, fn):
    """Run ``fn`` while a profiler trace records into ``tmp_path``; return
    its result and the trace's host events as (name, line, start, end,
    stats) tuples."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for li, line in enumerate(plane.lines):
            events += [(e.name, (plane.name, li), int(e.start_ns),
                        int(e.end_ns), dict(e.stats)) for e in line.events]
    return out, events


def _inside(child, parents):
    """The parent that holds ``child``'s interval on ``child``'s thread."""
    _, line, s, e, _ = child
    hits = [p for p in parents if p[1] == line and p[2] <= s and e <= p[3]]
    assert len(hits) == 1, (child, hits)
    return hits[0]


def test_span_adds_its_duration_and_counts_a_raised_exit():
    c = {"t_ns": 0}
    with span("test.outer", c, "t_ns", meta=1):
        pass
    first = c["t_ns"]
    assert first > 0
    with pytest.raises(RuntimeError):
        with span("test.outer", c, "t_ns"):
            raise RuntimeError("boom")
    assert c["t_ns"] > first
    with span("test.bare"):      # no counter dict: the annotation alone
        pass
    assert set(c) == {"t_ns"}


def test_fused_walk_phases_nest_in_the_session_counters():
    idx, sink = _chain()
    sess = _session(idx)
    plans = _plans(idx, sink, 6)
    for p in plans:
        sess.run(p)
    c = sess.counters
    assert c["fused_walk"] == len(plans) and c["walk"] == len(plans)
    for key in ("execute_ns", "fused_walk_ns", "walk_put_ns", "walk_wait_ns",
                "walk_put_bytes"):
        assert c[key] > 0, key
    assert c["walk_put_ns"] + c["walk_wait_ns"] <= c["fused_walk_ns"]
    assert c["fused_walk_ns"] <= c["execute_ns"]
    # no cell of this session reaches the host walker or the hop-cache
    assert c["host_walk_ns"] == 0 and c["hopcache_probe_ns"] == 0


def test_walk_put_bytes_are_the_planes_and_masks_put():
    """Every walk puts its packed mask; the planes go only with the first
    walk of each direction, after which the session's plane store holds
    them on the device."""
    idx, sink = _chain(coo_filter=True)
    sess = _session(idx)
    plans = _plans(idx, sink, 4, batch=5)
    expected = 0
    for p in plans:
        up, down = ((p.source, p.target) if p.direction == "fwd"
                    else (p.target, p.source))
        chain = path_tensors(idx, up, down)
        if p.direction == "fwd":
            planes = [op.tensor.bitplane_fwd(s) for op, s in chain]
        else:
            planes = [op.tensor.bitplane_bwd(s) for op, s in reversed(chain)]
        mask = pack_bitplane(np.asarray(p.rows, dtype=bool))
        misses = sess.counters["walk_plane_misses"]
        sess.run(p)
        expected += mask.nbytes
        if sess.counters["walk_plane_misses"] > misses:
            expected += sum(pl.nbytes for pl in planes)
        assert sess.counters["walk_put_bytes"] == expected
    assert sess.counters["walk_plane_misses"] == 2
    assert sess.counters["walk_plane_hits"] == len(plans) - 2


def test_host_walk_and_hopcache_probe_spans_count_their_branch():
    idx, sink = _chain()
    host = QuerySession(idx, use_hopcache=False, fused_walk=False)
    for p in _plans(idx, sink, 2):
        host.run(p)
    assert host.counters["host_walk_ns"] > 0
    assert host.counters["fused_walk_ns"] == 0
    assert host.counters["execute_ns"] >= host.counters["host_walk_ns"]
    cached = QuerySession(idx, use_hopcache=True, fused_walk=True)
    for p in _plans(idx, sink, 2):
        cached.composed.relation(*cached._plan_pairs(p)[0])
        cached.run(p)
    assert cached.counters["hopcache"] == 2
    assert cached.counters["hopcache_probe_ns"] > 0
    assert cached.counters["execute_ns"] >= cached.counters["hopcache_probe_ns"]


def test_answers_are_identical_while_a_profiler_records(tmp_path):
    idx, sink = _chain()
    sess = _session(idx)
    plans = _plans(idx, sink, 4)
    plain = [sess.run(p) for p in plans]
    traced, _ = _recorded(tmp_path, lambda: [sess.run(p) for p in plans])
    assert len(plain) == len(traced)
    for a, b in zip(plain, traced):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.dtype == y.dtype
            assert x.tobytes() == y.tobytes()


def test_walk_spans_nest_on_one_host_thread(tmp_path):
    idx, sink = _chain()
    sess = _session(idx)
    plans = _plans(idx, sink, 3)
    sess.run(plans[0])                  # compile outside the trace
    _, events = _recorded(tmp_path, lambda: [sess.run(p) for p in plans])
    by = {n: [e for e in events if e[0] == n] for n in WALK_SPANS}
    assert [len(by[n]) for n in WALK_SPANS] == [len(plans)] * 4
    for fused in by["walk.fused"]:
        _inside(fused, by["session.execute"])
    for name in ("walk.put", "walk.wait"):
        for ev in by[name]:
            _inside(ev, by["walk.fused"])
    for put, wait in zip(sorted(by["walk.put"], key=lambda e: e[2]),
                         sorted(by["walk.wait"], key=lambda e: e[2])):
        assert put[3] <= wait[2]        # the copy, then the wait


def test_tier_pass_span_carries_its_number_around_the_session(tmp_path):
    idx, sink = _chain()
    sess = _session(idx)
    plans = _plans(idx, sink, 2)
    for p in plans:
        sess.run(p)

    def serve():
        with ServingTier(sess, max_batch=8, max_wait_ms=1.0) as tier:
            for p in plans:
                tier.submit_sync("t", p, timeout=TIMEOUT_S)
            return dict(tier.counters)

    counters, events = _recorded(tmp_path, serve)
    passes = [e for e in events if e[0] == "tier.pass"]
    assert len(passes) == counters["passes"] == len(plans)
    assert sorted(e[4]["pass"] for e in passes) == [1, 2]
    assert all(e[4]["plans"] == 1 for e in passes)
    executes = [e for e in events if e[0] == "session.execute"]
    assert len(executes) == len(plans)
    for ex in executes:
        _inside(ex, passes)


class _CountingBackend:
    def __init__(self):
        self.calls = 0
        self.plans = 0
        self.lock = threading.Lock()

    def run_many(self, plans):
        with self.lock:
            self.calls += 1
            self.plans += len(plans)
        return [None] * len(plans)


def test_tier_pass_counters_match_the_backend_calls():
    idx, sink = _chain()
    backend = _CountingBackend()
    plans = _plans(idx, sink, 20, batch=1)
    with ServingTier(backend, max_batch=4, max_wait_ms=2.0) as tier:
        futs = tier.submit_many_nowait("burst", plans)
        for f in futs:
            f.result(timeout=TIMEOUT_S)
        c = dict(tier.counters)
    assert c["passes"] == backend.calls >= 1
    assert c["pass_plans"] == backend.plans == len(plans)
    assert c["pass_ns"] > 0 and c["queue_wait_ns"] > 0


def test_timer_flushed_plan_waits_in_the_queue():
    idx, sink = _chain()
    backend = _CountingBackend()
    plan = _plans(idx, sink, 1, batch=1)[0]
    with ServingTier(backend, max_batch=64, max_wait_ms=50.0) as tier:
        tier.submit_sync("lone", plan, timeout=TIMEOUT_S)
        c = dict(tier.counters)
    assert c["flush_timer"] == 1 and c["passes"] == 1
    assert c["queue_wait_ns"] >= 0.8 * 50e6


def test_federated_run_many_still_answers_through_the_shared_fusion():
    base, specs = pipegen.random_specs(7)
    merged, ids = pipegen.build_merged(base, specs)
    catalog, refs, sink_ref = pipegen.build_federated(base, specs, 2)
    n_sink = merged.datasets[ids[-1]].n_rows
    sess = FederatedSession(catalog)
    keys = set(sess.counters)
    plans = [prov(catalog).source(sink_ref).rows([i % n_sink])
             .backward().to(refs[0]) for i in range(6)]
    out = sess.run_many(plans)
    assert len(out) == len(plans)
    for i, got in enumerate(out):
        np.testing.assert_array_equal(
            got, tqp.ref_q2(merged, ids[-1], [i % n_sink], "src"))
    assert sess.counters["fused_plans"] == len(plans)
    assert set(sess.counters) == keys
