"""Serving-tier benchmark: cross-request fusion under concurrent load.

The scenario the async tier exists for — MANY independent lineage requests
(mixed Q1/Q2/Q4 against the deep chain, the same workload shape as
``bench_query.run_fused_batch``) arriving one at a time from many tenants:

* **saturation** — every request already queued (a burst): the sync
  per-request loop answers them one ``session.run`` at a time; the tier
  coalesces same-fuse-key plans into ``max_batch``-wide fused passes.  The
  headline is fused throughput / sync throughput at saturation.
* **open loop** — Poisson arrivals at a rate the sync loop can just about
  sustain: per-request latency (p50/p99) for the sync loop server vs the
  micro-batching tier.  The tier trades its ``max_wait_ms`` batching delay
  for immunity to queueing collapse.

Answers are asserted BYTE-IDENTICAL between the sequential session and the
tier before anything is timed.

Run as a script this merges a ``serving`` section into ``BENCH_query.json``
at the repo root (the perf-trajectory artifact bench_query.py owns).
"""
from __future__ import annotations

import concurrent.futures
import json
import os
import queue
import threading
import time

import numpy as np

from repro.core.hopcache import ComposedIndex
from repro.dataprep.usecases import build_deep_chain
from repro.provenance import QuerySession, prov
from repro.serve import ServingTier


def make_plans(idx, sink, n_requests: int, seed: int = 11):
    """A mixed Q1/Q2/Q4 request stream (round-robin kinds, random probes)
    — three fuse keys, so the tier packs roughly ``n_requests / 3`` plans
    behind each.

    Each request probes ONE row (Q4: one row, one attr) — the serving
    shape: a request traces ITS response row, not a batch.  Single-probe
    calls are per-call-overhead-bound, which is exactly the regime the
    tier's fusion targets."""
    src = "chain_src"
    n_src = idx.datasets[src].n_rows
    n_sink = idx.datasets[sink].n_rows
    c_sink = idx.datasets[sink].n_cols
    rng = np.random.default_rng(seed)
    plans = []
    for i in range(n_requests):
        kind = i % 3
        if kind == 0:
            plans.append(prov(idx).source(src)
                         .rows([int(rng.integers(n_src))])
                         .forward().to(sink).plan())
        elif kind == 1:
            plans.append(prov(idx).source(sink)
                         .rows([int(rng.integers(n_sink))])
                         .backward().to(src).plan())
        else:
            plans.append(prov(idx).source(sink)
                         .rows([int(rng.integers(n_sink))])
                         .attrs([int(rng.integers(c_sink))])
                         .backward().to(src).plan())
    return plans


class SyncLoopServer:
    """The baseline serving loop: one worker thread, one ``session.run``
    per request, strictly in arrival order — what ``ServeEngine`` offered
    before the tier existed, wrapped in the same future-based submit
    surface so the open-loop driver treats both servers identically."""

    def __init__(self, session) -> None:
        self.session = session
        self._q: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(target=self._worker,
                                        name="sync-loop", daemon=True)
        self._thread.start()

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            plan, fut = item
            try:
                fut.set_result(self.session.run(plan))
            except Exception as exc:        # noqa: BLE001
                fut.set_exception(exc)

    def submit(self, tenant: str, plan) -> "concurrent.futures.Future":
        fut: "concurrent.futures.Future" = concurrent.futures.Future()
        self._q.put((plan, fut))
        return fut

    def close(self) -> None:
        self._q.put(None)
        self._thread.join(timeout=30)


def _assert_parity(seq_results, tier_results) -> None:
    assert len(seq_results) == len(tier_results)
    for a, b in zip(seq_results, tier_results):
        la = a if isinstance(a, list) else [a]
        lb = b if isinstance(b, list) else [b]
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _open_loop(submit, plans, rate_hz: float, seed: int):
    """Poisson arrivals at ``rate_hz``; per-request latency measured from
    submit to future completion (queueing + batching + execution)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_hz, size=len(plans)))
    lat, lock = [], threading.Lock()

    def _done(fut, t_sub):
        dt = (time.perf_counter() - t_sub) * 1e3
        with lock:
            lat.append(dt)

    futs = []
    t0 = time.perf_counter()
    for i, (arr, plan) in enumerate(zip(arrivals, plans)):
        lag = arr - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        t_sub = time.perf_counter()
        fut = submit(f"tenant-{i % 8}", plan)
        fut.add_done_callback(lambda f, t=t_sub: _done(f, t))
        futs.append(fut)
    for f in futs:
        f.result(timeout=120)
    wall = time.perf_counter() - t0
    lat = np.array(sorted(lat))
    return {
        "rate_hz": float(rate_hz),
        "achieved_hz": len(plans) / max(wall, 1e-9),
        "p50_ms": float(np.percentile(lat, 50)),
        "p99_ms": float(np.percentile(lat, 99)),
        "mean_ms": float(lat.mean()),
    }


def run(quick: bool = False):
    # serving-shaped workload: MANY small per-request probes against a
    # moderate chain — the regime where per-request overhead (plan routing,
    # per-call walk/probe setup) dominates and fusion pays.  Request count
    # scales with mode; the chain does not (a serving tier fronts one
    # pipeline, load is the variable).
    n, n_ops = 1000, 24
    n_requests = 192 if quick else 384     # 3 fuse keys x full max_batch
    max_batch = 64
    reps = 1 if quick else 3
    idx, sink = build_deep_chain(n=n, n_ops=n_ops)

    def fresh_session():
        return QuerySession(idx, ComposedIndex(idx,
                                               memory_budget_bytes=256 << 20))

    plans = make_plans(idx, sink, n_requests)

    # -- parity: the tier's fused answers == the sequential session's -------
    ref_sess = fresh_session()
    seq_results = [ref_sess.run(p) for p in plans]
    with ServingTier(fresh_session(), max_batch=max_batch,
                     max_wait_ms=2.0, max_queue=4 * n_requests) as tier:
        futs = [tier.submit_nowait(f"tenant-{i % 8}", p)
                for i, p in enumerate(plans)]
        _assert_parity(seq_results, [f.result(timeout=120) for f in futs])
    print(f"parity OK: {n_requests} mixed Q1/Q2/Q4 requests, tier == "
          f"sequential session, byte-identical")

    # -- saturation: burst throughput, sync loop vs fused tier --------------
    # fresh warmed sessions per contender; the warm pass composes whatever
    # each cost model chooses, so the timed reps measure probe cost.
    # Medians over paired reps keep the ratio robust to host-load drift.
    sync_sess = fresh_session()
    tier_sess = fresh_session()
    sync_raw, tier_raw = [], []
    with ServingTier(tier_sess, max_batch=max_batch, max_wait_ms=2.0,
                     max_queue=4 * n_requests) as tier:
        for p in plans:                                      # warm passes
            sync_sess.run(p)
        for f in tier.submit_many_nowait("burst", plans):
            f.result(timeout=120)
        for _ in range(reps * 3):
            t0 = time.perf_counter()
            for p in plans:
                sync_sess.run(p)
            sync_raw.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            for f in tier.submit_many_nowait("burst", plans):
                f.result(timeout=120)
            tier_raw.append(time.perf_counter() - t0)
        tier_stats = tier.stats()
    sync_hz = n_requests / max(float(np.median(sync_raw)), 1e-9)
    tier_hz = n_requests / max(float(np.median(tier_raw)), 1e-9)
    speedup = float(np.median(np.array(sync_raw) / np.array(tier_raw)))
    print(f"saturation: sync loop {sync_hz:8.0f} req/s | tier "
          f"{tier_hz:8.0f} req/s ({speedup:.1f}x, max fused width "
          f"{tier_stats['tier']['max_batch_seen']})")

    # -- open loop: Poisson arrivals swept across the sync loop's capacity --
    # (the saturation curve: below sync capacity both serve; above it the
    # sync loop's queue grows without bound while the tier keeps fusing)
    fractions = [0.7] if quick else [0.4, 0.7, 1.0, 1.3]
    curve = []
    for frac in fractions:
        rate = frac * sync_hz
        sync_server = SyncLoopServer(fresh_session())
        sync_server.submit("warm", plans[0]).result(timeout=120)
        open_sync = _open_loop(sync_server.submit, plans, rate, seed=3)
        sync_server.close()
        with ServingTier(fresh_session(), max_batch=max_batch,
                         max_wait_ms=2.0,
                         max_queue=4 * n_requests) as tier:
            tier.submit_sync("warm", plans[0], timeout=120)
            open_tier = _open_loop(tier.submit_nowait, plans, rate, seed=3)
        curve.append({"fraction_of_sync_saturation": frac,
                      "sync_loop": open_sync, "tier": open_tier})
        print(f"open loop @ {rate:6.0f}/s ({frac:.1f}x sync sat): "
              f"sync p50 {open_sync['p50_ms']:6.2f} p99 "
              f"{open_sync['p99_ms']:7.2f} ms | tier p50 "
              f"{open_tier['p50_ms']:6.2f} p99 {open_tier['p99_ms']:7.2f} ms")

    return {
        "n": n, "n_ops": n_ops, "n_requests": n_requests,
        "max_batch": max_batch,
        "parity": "byte-identical",
        "saturation": {
            "sync_loop_req_per_s": sync_hz,
            "tier_req_per_s": tier_hz,
            "speedup_fused": speedup,
            "max_fused_width": tier_stats["tier"]["max_batch_seen"],
            "batches": tier_stats["tier"]["batches"],
        },
        "open_loop_curve": curve,
        "tier_counters": tier_stats["tier"],
    }


def _merge_trajectory(section: dict) -> None:
    """``BENCH_query.json`` belongs to bench_query.py; this bench only
    extends it with the ``serving`` section (creating the file when the
    query bench has not run yet)."""
    path = os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "BENCH_query.json"))
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["serving"] = section
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)
    print(f"wrote {path} (serving section)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced configuration (CI smoke) — still merges "
                    "the serving section into BENCH_query.json")
    args = ap.parse_args()
    _merge_trajectory(run(quick=args.quick))
