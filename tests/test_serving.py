"""Serving tier: micro-batch fusion, admission control, tenant scoping.

Four pillars:

* **Fusion parity** — answers through the tier (fused ``run_many`` passes,
  any surface: threaded burst, per-request, async) are byte-identical to a
  plain sequential ``session.run`` loop, and the tier counters prove the
  requests actually rode fused batches.
* **Admission** — the bounded front door sheds load with TYPED errors
  (:class:`QueueFullError`, :class:`TenantOverloadError`,
  :class:`TierClosedError`), backpressures with ``wait=True``, caps
  per-tenant in-flight, and scopes tenants to capability ref sets
  (:class:`CapabilityError` at admission, before a bucket ever sees the
  plan).
* **Lifecycle** — ``shutdown(drain=True)`` answers everything already
  admitted; ``drain=False`` rejects it; a stopped tier refuses new work.
* **Engine recording** — ``ServeEngine`` provenance invariants the tier
  rests on: gid collision looping, lineage-vs-hand-built-plan parity,
  bare-ref qualification through ``as_backend()``, the ``prov_index=``
  deprecation warning attributing to the CALLER's file, and ``record``
  over a seeded token batch.

pytest-timeout guards these in CI; locally (where the plugin may be
absent) an autouse SIGALRM fixture aborts a wedged async test instead of
hanging the whole suite.
"""
import asyncio
import signal
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import CapabilityError, prov
from repro.serve import (
    QueueFullError,
    ServingTier,
    TenantOverloadError,
    TenantScope,
    TierClosedError,
)
from repro.serve import engine as serve_engine
from repro.serve.engine import GenerationResult, ServeEngine

DEADLINE_S = 120

# engages pytest-timeout where installed (CI); elsewhere the marker is
# inert and the SIGALRM fixture below is the guard
pytestmark = pytest.mark.timeout(DEADLINE_S)


@pytest.fixture(autouse=True)
def _deadline():
    """Abort (don't hang) a wedged serving test when pytest-timeout is not
    installed.  SIGALRM only works on the main thread of a POSIX process;
    anywhere else this is a no-op and the CI plugin is the only guard."""
    if (not hasattr(signal, "SIGALRM")
            or threading.current_thread() is not threading.main_thread()):
        yield
        return

    def _boom(signum, frame):
        raise TimeoutError(f"serving test exceeded {DEADLINE_S}s deadline")

    old = signal.signal(signal.SIGALRM, _boom)
    signal.alarm(DEADLINE_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


# ---------------------------------------------------------------------------
# Fixtures
# ---------------------------------------------------------------------------
def _chain_index(seed=0, n=48):
    """raw -> scaled -> sink chain plus a SIBLING branch off raw (not an
    ancestor of sink — the out-of-scope ref for capability tests)."""
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex(f"serving-test-{seed}")
    s = track(Table.from_columns({
        "k": np.arange(n, dtype=np.float32),
        "x": rng.normal(size=n).astype(np.float32),
    }), idx, "raw")
    scaled = s.value_transform("x", "scale", factor=2.0)
    sibling = s.value_transform("x", "scale", factor=-1.0)
    sink = scaled.filter_rows(rng.random(n) > 0.25)
    sink.mark_sink()
    return idx, sink.dataset_id, sibling.dataset_id


def _mixed_plans(idx, sink, n_plans, seed=1):
    """Round-robin Q1/Q2/Q4 single-probe plans — three fuse keys."""
    rng = np.random.default_rng(seed)
    n_raw = idx.datasets["raw"].n_rows
    n_sink = idx.datasets[sink].n_rows
    plans = []
    for i in range(n_plans):
        if i % 3 == 0:
            plans.append(prov(idx).source("raw")
                         .rows([int(rng.integers(n_raw))])
                         .forward().to(sink).plan())
        elif i % 3 == 1:
            plans.append(prov(idx).source(sink)
                         .rows([int(rng.integers(n_sink))])
                         .backward().to("raw").plan())
        else:
            plans.append(prov(idx).source(sink)
                         .rows([int(rng.integers(n_sink))]).attrs([0])
                         .backward().to("raw").plan())
    return plans


def _assert_parity(ref, got):
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        la = a if isinstance(a, list) else [a]
        lb = b if isinstance(b, list) else [b]
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _poll(pred, timeout=30.0, what="condition"):
    t0 = time.perf_counter()
    while not pred():
        if time.perf_counter() - t0 > timeout:
            raise TimeoutError(f"{what} not reached within {timeout}s")
        time.sleep(0.005)


class _GatedBackend:
    """``run_many`` blocks until the gate opens — keeps admitted requests
    in flight so the admission bounds become observable from a test."""

    def __init__(self, session):
        self.session = session
        self.gate = threading.Event()
        self.calls = 0

    def run_many(self, plans):
        self.gate.wait(DEADLINE_S)
        self.calls += 1
        return self.session.run_many(plans)


# ===========================================================================
# Fusion parity + batching
# ===========================================================================
def test_tier_burst_parity_and_fused_batches():
    idx, sink, _ = _chain_index()
    sess = idx.session()
    plans = _mixed_plans(idx, sink, 48)
    ref = [sess.run(p) for p in plans]
    with ServingTier(sess, max_batch=16, max_wait_ms=5.0) as tier:
        futs = tier.submit_many_nowait("burst", plans)
        got = [f.result(timeout=60) for f in futs]
        st = tier.stats()["tier"]
    _assert_parity(ref, got)
    # the requests actually fused: 48 plans over 3 fuse keys, 16-wide caps
    assert st["submitted"] == st["completed"] == 48
    assert st["batched_plans"] == 48
    assert st["batches"] < 48
    assert st["max_batch_seen"] == 16
    assert st["flush_full"] >= 3


def test_tier_single_probe_timer_flush():
    idx, sink, _ = _chain_index()
    sess = idx.session()
    plan = _mixed_plans(idx, sink, 1)[0]
    with ServingTier(sess, max_batch=64, max_wait_ms=1.0) as tier:
        got = tier.submit_sync("lone", plan, timeout=30)
        st = tier.stats()["tier"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(sess.run(plan)))
    assert st["flush_timer"] == 1 and st["flush_full"] == 0


def test_tier_async_surface_parity():
    idx, sink, _ = _chain_index(seed=2)
    sess = idx.session()
    plans = _mixed_plans(idx, sink, 12, seed=3)
    ref = [sess.run(p) for p in plans]

    async def main():
        tier = ServingTier(sess, max_batch=4, max_wait_ms=1.0)
        got = await asyncio.gather(
            *[tier.submit(f"t{i % 2}", p) for i, p in enumerate(plans)])
        await tier.aclose()
        return got

    _assert_parity(ref, asyncio.run(main()))


# ===========================================================================
# Admission: bounds, backpressure, typed rejection
# ===========================================================================
def test_queue_full_sheds_typed_then_recovers():
    idx, sink, _ = _chain_index(seed=4)
    sess = idx.session()
    plans = _mixed_plans(idx, sink, 6, seed=5)
    backend = _GatedBackend(sess)
    tier = ServingTier(backend, max_batch=1, max_wait_ms=0.1,
                       max_queue=4).start()
    try:
        futs = [tier.submit_nowait("t", p) for p in plans[:4]]
        _poll(lambda: tier.admission.pending == 4, what="queue fill")
        with pytest.raises(QueueFullError):
            tier.submit_nowait("t", plans[4]).result(timeout=30)
        assert tier.admission.counters["rejected_queue_full"] == 1
        backend.gate.set()      # drain the gate: admitted work completes
        _assert_parity([sess.run(p) for p in plans[:4]],
                       [f.result(timeout=60) for f in futs])
        # capacity freed: the same submission is admitted now
        np.testing.assert_array_equal(
            np.asarray(tier.submit_sync("t", plans[4], timeout=30)),
            np.asarray(sess.run(plans[4])))
    finally:
        backend.gate.set()
        tier.shutdown()


def test_wait_turns_rejection_into_backpressure():
    idx, sink, _ = _chain_index(seed=6)
    sess = idx.session()
    plans = _mixed_plans(idx, sink, 3, seed=7)
    backend = _GatedBackend(sess)
    tier = ServingTier(backend, max_batch=1, max_wait_ms=0.1,
                       max_queue=2).start()
    try:
        futs = [tier.submit_nowait("t", p) for p in plans[:2]]
        _poll(lambda: tier.admission.pending == 2, what="queue fill")
        waiting = tier.submit_nowait("t", plans[2], wait=True)
        time.sleep(0.05)
        assert not waiting.done()       # parked, NOT rejected
        backend.gate.set()
        np.testing.assert_array_equal(np.asarray(waiting.result(timeout=60)),
                                      np.asarray(sess.run(plans[2])))
        for f in futs:
            f.result(timeout=60)
        assert tier.admission.counters["rejected_queue_full"] == 0
    finally:
        backend.gate.set()
        tier.shutdown()


def test_tenant_inflight_cap_isolates_tenants():
    idx, sink, _ = _chain_index(seed=8)
    sess = idx.session()
    plans = _mixed_plans(idx, sink, 4, seed=9)
    backend = _GatedBackend(sess)
    tier = ServingTier(backend, max_batch=1, max_wait_ms=0.1,
                       max_queue=16).start()
    tier.register_tenant("capped", max_inflight=2)
    try:
        futs = [tier.submit_nowait("capped", p) for p in plans[:2]]
        _poll(lambda: tier.admission.pending == 2, what="cap fill")
        with pytest.raises(TenantOverloadError):
            tier.submit_nowait("capped", plans[2]).result(timeout=30)
        # the shed request never touched GLOBAL capacity: another tenant
        # with plenty of queue headroom is admitted immediately
        other = tier.submit_nowait("other", plans[3])
        _poll(lambda: tier.admission.pending == 3, what="other admitted")
        backend.gate.set()
        for f in futs + [other]:
            f.result(timeout=60)
        st = tier.admission.stats()
        assert st["rejected_tenant_cap"] == 1
        assert st["tenants"]["capped"]["rejected"] == 1
        assert st["tenants"]["other"]["rejected"] == 0
    finally:
        backend.gate.set()
        tier.shutdown()


# ===========================================================================
# Capability scoping
# ===========================================================================
def test_tenant_scope_denies_out_of_scope_refs_at_admission():
    idx, sink, sibling = _chain_index(seed=10)
    sess = idx.session()
    # the tenant's capability: the sink's export — its ancestor closure
    # (raw, scaled, sink), which excludes the sibling branch
    handle = idx.export(sink)
    tier = ServingTier(sess, max_batch=4, max_wait_ms=1.0,
                       allow_unregistered=False).start()
    tier.register_tenant("scoped", handle)
    tier.register_tenant("operator")        # unrestricted
    try:
        in_scope = (prov(idx).source(sink).rows([0])
                    .backward().to("raw").plan())
        out_scope = (prov(idx).source(sibling).rows([0])
                     .backward().to("raw").plan())
        np.testing.assert_array_equal(
            np.asarray(tier.submit_sync("scoped", in_scope, timeout=30)),
            np.asarray(sess.run(in_scope)))
        with pytest.raises(CapabilityError):
            tier.submit_sync("scoped", out_scope, timeout=30)
        # same plan, unrestricted tenant: served
        np.testing.assert_array_equal(
            np.asarray(tier.submit_sync("operator", out_scope, timeout=30)),
            np.asarray(sess.run(out_scope)))
        # unknown tenants are a capability failure on a closed-roster tier
        with pytest.raises(CapabilityError):
            tier.submit_sync("stranger", in_scope, timeout=30)
        st = tier.stats()["admission"]
        assert st["capability_denied"] == 1
        assert st["tenants"]["scoped"]["denied"] == 1
    finally:
        tier.shutdown()
    assert repr(TenantScope(["a", "b"])) == "TenantScope(2 refs)"


def test_submit_many_isolates_per_plan_rejection():
    idx, sink, sibling = _chain_index(seed=11)
    sess = idx.session()
    good = _mixed_plans(idx, sink, 4, seed=12)
    bad = prov(idx).source(sibling).rows([0]).backward().to("raw").plan()
    plans = good[:2] + [bad] + good[2:]
    with ServingTier(sess, max_batch=8, max_wait_ms=1.0) as tier:
        tier.register_tenant("scoped", idx.export(sink))
        futs = tier.submit_many_nowait("scoped", plans)
        with pytest.raises(CapabilityError):
            futs[2].result(timeout=30)
        _assert_parity([sess.run(p) for p in good],
                       [f.result(timeout=60)
                        for f in futs[:2] + futs[3:]])


# ===========================================================================
# Lifecycle: drain, reject, closed
# ===========================================================================
def test_shutdown_drain_answers_everything_admitted():
    idx, sink, _ = _chain_index(seed=13)
    sess = idx.session()
    plans = _mixed_plans(idx, sink, 9, seed=14)
    # huge max_wait + wide batches: everything is still sitting in buckets
    # when shutdown begins, so ONLY the drain path can answer it
    tier = ServingTier(sess, max_batch=64, max_wait_ms=60_000.0).start()
    futs = tier.submit_many_nowait("t", plans)
    _poll(lambda: tier.admission.pending == len(plans), what="bucketed")
    tier.shutdown(drain=True)
    _assert_parity([sess.run(p) for p in plans],
                   [f.result(timeout=1) for f in futs])
    st = tier.stats()
    assert st["tier"]["flush_drain"] >= 1
    assert st["tier"]["completed"] == len(plans)
    assert st["admission"]["pending"] == 0
    with pytest.raises(TierClosedError):
        tier.submit_nowait("t", plans[0])


def test_shutdown_without_drain_rejects_queued():
    idx, sink, _ = _chain_index(seed=15)
    sess = idx.session()
    plans = _mixed_plans(idx, sink, 6, seed=16)
    tier = ServingTier(sess, max_batch=64, max_wait_ms=60_000.0).start()
    futs = tier.submit_many_nowait("t", plans)
    _poll(lambda: tier.admission.pending == len(plans), what="bucketed")
    tier.shutdown(drain=False)
    for f in futs:
        with pytest.raises(TierClosedError):
            f.result(timeout=1)
    st = tier.stats()
    assert st["tier"]["failed"] == len(plans)
    assert st["admission"]["pending"] == 0  # releases balanced the admits


def test_backend_failure_fans_out_and_releases():
    class _Broken:
        def run_many(self, plans):
            raise RuntimeError("backend exploded")

    idx, sink, _ = _chain_index(seed=17)
    plans = _mixed_plans(idx, sink, 3, seed=18)
    with pytest.raises(RuntimeError):
        # __exit__ on the exception path shuts down WITHOUT draining
        with ServingTier(_Broken(), max_batch=1, max_wait_ms=0.1) as tier:
            futs = [tier.submit_nowait("t", p) for p in plans]
            for f in futs:
                with pytest.raises(RuntimeError, match="backend exploded"):
                    f.result(timeout=30)
            assert tier.stats()["tier"]["failed"] == len(plans)
            assert tier.admission.pending == 0
            raise RuntimeError("leave via the exception path")


# ===========================================================================
# ServeEngine recording invariants + tier integration
# ===========================================================================
def _recorded_engine(b=4):
    engine = ServeEngine("serve:tiertest")
    r = engine.record(np.zeros((b, 2), np.int32), prompt_len=2,
                      request_ids=np.arange(b))
    return engine, r


def test_record_gid_collision_loops_to_free_slot():
    engine = ServeEngine("serve:gid")
    # an earlier generation (or a sibling engine on a shared index) already
    # owns slot 0 — recording must skip it, not collide
    engine.prov.add_source("responses@0", Table.from_columns(
        {"z": np.zeros(2, np.float32)}))
    r = engine.record(np.zeros((3, 2), np.int32), prompt_len=1,
                      request_ids=np.arange(3))
    assert (r.request_dataset, r.response_dataset) == \
        ("requests@1", "responses@1")
    r2 = engine.record(np.zeros((2, 2), np.int32), prompt_len=1,
                       request_ids=np.arange(2))
    assert r2.response_dataset == "responses@2"
    # both recordings answer lineage independently
    np.testing.assert_array_equal(engine.response_lineage(r, rows=[2]), [2])
    np.testing.assert_array_equal(engine.response_lineage(r2, rows=[0]), [0])


def test_response_lineage_matches_hand_built_plans():
    engine, r = _recorded_engine()
    got = engine.response_lineage(r, rows=[0, 2])
    ref = (prov(engine.prov).source(r.response_dataset).rows([0, 2])
           .backward().to(r.request_dataset).run(engine.session))
    np.testing.assert_array_equal(got, ref)
    batch = engine.response_lineage_batch(r, [[0], [1], [2, 3]])
    refs = engine.session.run_many([
        prov(engine.prov).source(r.response_dataset).rows(rows)
        .backward().to(r.request_dataset).plan()
        for rows in [[0], [1], [2, 3]]])
    _assert_parity(refs, batch)


def test_engine_backend_qualifies_bare_refs_through_tier():
    engine, r = _recorded_engine()
    backend = engine.as_backend()
    bare = (prov(engine.prov).source(r.response_dataset).rows([1])
            .backward().to(r.request_dataset).plan())
    prepared = backend.prepare(bare)
    assert prepared.source == f"serve/{r.response_dataset}"
    assert prepared.target == f"serve/{r.request_dataset}"
    qualified = (prov(engine.catalog)
                 .source(f"serve/{r.response_dataset}").rows([1])
                 .backward().to(f"serve/{r.request_dataset}").plan())
    with ServingTier(backend, max_batch=4, max_wait_ms=1.0) as tier:
        got_bare = tier.submit_sync("a", bare, timeout=30)
        got_qual = tier.submit_sync("b", qualified, timeout=30)
        st = tier.stats()
    ref = engine.response_lineage(r, rows=[1])
    np.testing.assert_array_equal(np.asarray(got_bare), ref)
    np.testing.assert_array_equal(np.asarray(got_qual), ref)
    assert "backend" in st     # the engine backend exposes session stats


def test_prov_index_deprecation_attributes_callers_file():
    prep = ProvenanceIndex("prep-warnfile")
    track(Table.from_columns({"k": np.arange(3, dtype=np.float32)}),
          prep, "raw").mark_sink()
    serve_engine._DEPRECATION_WARNED.discard("prov_index")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        ServeEngine("serve:warnfile", prov_index=prep)
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)
           and "prov_index" in str(w.message)]
    assert len(dep) == 1
    # the computed stacklevel lands on THIS file (the deprecated call
    # site), not an engine-internal frame
    assert dep[0].filename == __file__


def test_record_seeded_tokens_registers_the_pair_and_lineage():
    """``record`` over a seeded (B, n_new) token batch registers exactly
    the requests@N / responses@N pair, one HAUGMENT op between them, and
    maps each response row to its request row."""
    rng = np.random.default_rng(7)
    tokens = rng.integers(0, 50_000, (5, 3)).astype(np.int32)
    engine = ServeEngine("serve:record")
    r = engine.record(tokens, prompt_len=4, request_ids=np.arange(5) + 10)
    assert isinstance(r, GenerationResult)
    np.testing.assert_array_equal(r.tokens, tokens)
    assert (r.request_dataset, r.response_dataset) == \
        ("requests@0", "responses@0")
    assert engine.prov.datasets[r.request_dataset].n_rows == 5
    assert engine.prov.datasets[r.response_dataset].n_rows == 5
    (op,) = engine.prov.ops
    assert (op.input_ids, op.output_id) == ([r.request_dataset],
                                            r.response_dataset)
    assert op.info.params == {"n_new": 3, "prompt_len": 4}
    got = engine.response_lineage_batch(r, [[i] for i in range(5)])
    assert [g.tolist() for g in got] == [[i] for i in range(5)]
    np.testing.assert_array_equal(engine.response_lineage(r, rows=[1, 3]),
                                  [1, 3])
    with pytest.raises(ValueError, match="n_new"):
        engine.record(tokens[0], prompt_len=4)
