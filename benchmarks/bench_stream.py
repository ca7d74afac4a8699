"""Streaming-capture benchmark: bounded RSS + incremental hop-cache extension.

The scenario the spill tier and the incremental extension exist for — a
pipeline that never stops appending ops (a long-running preparation service,
a feature-store backfill) while lineage probes keep arriving:

* **extend micro** — a warm composed relation over a deep structured chain;
  each trial appends ONE op and compares the warm probe (eager one-step
  ``extend_tail``) against a cold ``ComposedIndex`` rebuild of the whole
  chain (the seed's invalidate+recompose behavior).  Headline: the median
  recompose/extend ratio (acceptance: >= 5x).
* **stream** — a continuous append stream (identity / filter / shuffle /
  append block mix, row count self-stabilizing around ~270) against a
  spill-tiered index + hop-cache vs the unbounded seed path.  Per sample:
  process RSS (psutil, when available), payload-resident bytes (op tensors +
  composed relations), batched Q1/Q2 probe p50/p99 through the QuerySession,
  and the extend/recompose counters.  The spill arm asserts payload
  residency stays under the configured budgets the whole run; the baseline
  arm recomposes from scratch at every sample and is CAPPED (logged) —
  that's the point.

Answers are asserted byte-identical between the spilled and the unbounded
index before anything is timed.

Run as a script this merges a ``stream`` section into ``BENCH_query.json``
at the repo root (the perf-trajectory artifact bench_query.py owns).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

try:
    import psutil
except ImportError:                          # degrade to payload accounting
    psutil = None

from repro.core.hopcache import ComposedIndex
from repro.core.opcat import AttrMap, CaptureInfo, OpCategory
from repro.core.pipeline import ProvenanceIndex
from repro.core.spill import SpillPolicy
from repro.dataprep.table import Table
from repro.provenance import QuerySession, prov


# ===========================================================================
# Fast append-stream driver (hand-built CaptureInfo, minimal table cost)
# ===========================================================================
def _table(n, c=2):
    data = np.zeros((n, c), dtype=np.float32)
    return Table(columns=[f"c{j}" for j in range(c)], data=data,
                 null=np.zeros((n, c), dtype=bool),
                 index=np.arange(n, dtype=np.int64), vocab={})


def _identity_info(n):
    return CaptureInfo(op_name="transform:scale", category=OpCategory.TRANSFORM,
                       contextual=False, n_out=n, n_in=[n],
                       params={"col": "c0", "fn": "scale",
                               "fn_params": {"factor": 1.0}},
                       attr_maps=[AttrMap("identity")])


def _filter_info(kept, n_in):
    return CaptureInfo(op_name="filter_rows", category=OpCategory.HREDUCE,
                       contextual=False, n_out=len(kept), n_in=[n_in],
                       kept_rows=kept, attr_maps=[AttrMap("identity")])


def _gather_info(src_rows, n_in):
    return CaptureInfo(op_name="shuffle", category=OpCategory.HAUGMENT,
                       contextual=False, n_out=len(src_rows), n_in=[n_in],
                       src_rows=src_rows, attr_maps=[AttrMap("identity")])


def _append_info(n_l, n_r):
    return CaptureInfo(op_name="append_rows", category=OpCategory.APPEND,
                       contextual=False, n_out=n_l + n_r, n_in=[n_l, n_r],
                       attr_maps=[AttrMap("identity"), AttrMap("identity")])


class StreamDriver:
    """Deterministic op stream: i%4 -> identity / ~3%-drop filter / shuffle
    gather / +8-row append block.  Row count stabilizes near drop/growth
    equilibrium (~270 from n0=256), so per-op cost stays flat and the ONLY
    thing growing without bound on the seed path is provenance."""

    BLOCK = 8

    def __init__(self, idx: ProvenanceIndex, n0: int = 256, seed: int = 0):
        self.idx = idx
        self.rng = np.random.default_rng(seed)
        idx.add_source("d0", _table(n0))
        self.cur, self.n = "d0", n0
        self.i = 0
        self._blocks = 0

    def step(self):
        i, n = self.i, self.n
        out = f"d{i + 1}"
        kind = i % 4
        if kind == 0:
            self.idx.record([self.cur], out, _table(n), _identity_info(n))
        elif kind == 1:
            kept = np.flatnonzero(self.rng.random(n) > 0.03).astype(np.int32)
            if len(kept) == 0:
                kept = np.array([0], dtype=np.int32)
            self.idx.record([self.cur], out, _table(len(kept)),
                            _filter_info(kept, n))
            n = len(kept)
        elif kind == 2:
            perm = self.rng.permutation(n).astype(np.int32)
            self.idx.record([self.cur], out, _table(n), _gather_info(perm, n))
        else:
            blk = f"blk{self._blocks}"
            self._blocks += 1
            self.idx.add_source(blk, _table(self.BLOCK))
            self.idx.record([self.cur, blk], out, _table(n + self.BLOCK),
                            _append_info(n, self.BLOCK))
            n += self.BLOCK
        self.cur, self.n, self.i = out, n, i + 1


def _probe_latency(sess, idx, cur, reps=7, batch=16, seed=1):
    """Batched Q1 (src->cur forward) + Q2 (cur->src backward) wall times."""
    rng = np.random.default_rng(seed)
    n_src = idx.datasets["d0"].n_rows
    n_cur = idx.datasets[cur].n_rows
    times = []
    for _ in range(reps):
        fwd = [rng.integers(0, n_src, size=4).tolist() for _ in range(batch)]
        bwd = [rng.integers(0, n_cur, size=4).tolist() for _ in range(batch)]
        t0 = time.perf_counter()
        sess.run(prov(idx).source("d0").rows_batch(fwd).forward().to(cur).plan())
        sess.run(prov(idx).source(cur).rows_batch(bwd).backward().to("d0").plan())
        times.append(time.perf_counter() - t0)
    a = np.sort(np.asarray(times))
    return float(a[len(a) // 2]), float(a[min(len(a) - 1, int(len(a) * 0.99))])


def _rss_mb():
    if psutil is None:
        return None
    return psutil.Process().memory_info().rss / 1e6


# ===========================================================================
# (a) extend micro: warm one-step extension vs cold chain recompose
# ===========================================================================
def run_extend_micro(quick: bool = False):
    hops = 12 if quick else 24
    n = 1024 if quick else 4096
    trials = 5 if quick else 9
    rng = np.random.default_rng(3)
    idx = ProvenanceIndex("extmicro")
    idx.add_source("d0", _table(n))
    cur, cn = "d0", n
    for i in range(hops):
        kept = np.flatnonzero(rng.random(cn) > 0.02).astype(np.int32)
        out = f"d{i + 1}"
        idx.record([cur], out, _table(len(kept)), _filter_info(kept, cn))
        cur, cn = out, len(kept)

    ci = ComposedIndex(idx)
    ci.relation("d0", cur)                   # warm the composed chain
    ratios, ext_ns, rec_ns = [], [], []
    for t in range(trials):
        kept = np.flatnonzero(rng.random(cn) > 0.02).astype(np.int32)
        out = f"x{t}"
        idx.record([cur], out, _table(len(kept)), _filter_info(kept, cn))
        cur, cn = out, len(kept)
        t0 = time.perf_counter()
        ci.relation("d0", cur)               # eager sync + warm probe
        te = time.perf_counter() - t0
        t0 = time.perf_counter()
        ComposedIndex(idx).relation("d0", cur)   # invalidate+recompose
        tr = time.perf_counter() - t0
        ratios.append(tr / te)
        ext_ns.append(te)
        rec_ns.append(tr)
    med = float(np.median(ratios))
    print(f"\n== extend micro: {hops}-hop chain, n={n} ==")
    print(f"warm extend   p50 {np.median(ext_ns) * 1e3:8.3f} ms")
    print(f"cold recompose p50 {np.median(rec_ns) * 1e3:8.3f} ms")
    print(f"recompose/extend ratio (median of {trials}): {med:.1f}x")
    assert ci.stats()["extends"] >= trials, ci.stats()
    return {"hops": hops, "n": n, "trials": trials,
            "extend_ms_p50": float(np.median(ext_ns) * 1e3),
            "recompose_ms_p50": float(np.median(rec_ns) * 1e3),
            "ratio_median": med}


# ===========================================================================
# (b) the append stream: bounded residency vs unbounded growth
# ===========================================================================
def run_stream(quick: bool = False, ops: int = 0):
    ops = ops or (2000 if quick else 1_000_000)
    n_samples = 8 if quick else 20
    base_cap = 2000 if quick else 20_000     # cold-recompose arm cap
    # op-tensor / composed-relation residency budgets, sized so the spill
    # tier actually engages within the run length
    tensor_budget = (256 << 10) if quick else (1 << 20)
    cache_budget = (512 << 10) if quick else (4 << 20)
    sample_every = max(1, ops // n_samples)

    # -- spill arm: bounded residency, eager extension ----------------------
    idx = ProvenanceIndex("stream",
                          spill=SpillPolicy(budget_bytes=tensor_budget))
    # spilled relations are rebuildable, so THEIR store may drop oldest
    # segments under a disk budget (op-tensor stores must never drop)
    ci = idx.composed(memory_budget_bytes=cache_budget,
                      spill=SpillPolicy(disk_budget_bytes=256 << 20))
    sess = QuerySession(idx, composed=ci)
    drv = StreamDriver(idx)

    # -- parity spot-check BEFORE timing: spilled == unbounded --------------
    ref_idx = ProvenanceIndex("streamref")
    ref_drv = StreamDriver(ref_idx)
    warm = min(ops, 400)
    for _ in range(warm):
        drv.step()
        ref_drv.step()
    want = ComposedIndex(ref_idx).relation("d0", ref_drv.cur)
    got = ci.relation("d0", drv.cur)
    w = np.asarray(want.todense()) if hasattr(want, "todense") else np.asarray(want)
    g = np.asarray(got.todense()) if hasattr(got, "todense") else np.asarray(got)
    assert np.array_equal(w, g), "spilled arm diverged from unbounded reference"
    print(f"parity: spilled == unbounded at op {warm} (byte-identical)")
    del ref_idx, ref_drv, want, got, w, g

    samples = []
    t_start = time.perf_counter()
    while drv.i < ops:
        drv.step()
        if drv.i % sample_every == 0 or drv.i == ops:
            # the one-time incremental drain of the appended tail (one
            # closed-form extension per absorbed op), separated out so the
            # probe numbers show the steady state
            t0 = time.perf_counter()
            ci.contains("d0", drv.cur)
            sync_s = time.perf_counter() - t0
            p50, p99 = _probe_latency(sess, idx, drv.cur)
            sp = idx.stats()["spill"]
            cs = ci.stats()
            payload = sp["resident_bytes"] + cs["bytes"]
            assert sp["resident_bytes"] <= tensor_budget, sp
            assert cs["bytes"] <= cache_budget * ci._spill.high_watermark, cs
            samples.append({
                "op": drv.i, "rss_mb": _rss_mb(),
                "payload_resident_mb": payload / 1e6,
                "tensor_resident_mb": sp["resident_bytes"] / 1e6,
                "cache_resident_mb": cs["bytes"] / 1e6,
                "spilled_ops": sp["spilled_ops"],
                "sync_ms": sync_s * 1e3,
                "probe_p50_ms": p50 * 1e3, "probe_p99_ms": p99 * 1e3,
                "extends": cs["extends"], "recomposes": cs["recomposes"],
            })
    stream_s = time.perf_counter() - t_start
    spilled_disk_mb = idx.stats()["spill"]["store"]["disk_bytes"] / 1e6

    # -- baseline arm: no spill, cold recompose per sample (seed path) ------
    if base_cap < ops:
        print(f"baseline arm CAPPED at {base_cap} of {ops} ops "
              "(unbounded growth + per-sample recompose would dominate the run)")
    bidx = ProvenanceIndex("streambase")
    bdrv = StreamDriver(bidx)
    bsamples = []
    bevery = max(1, base_cap // n_samples)
    while bdrv.i < base_cap:
        bdrv.step()
        if bdrv.i % bevery == 0 or bdrv.i == base_cap:
            bci = ComposedIndex(bidx)        # invalidate: cold every sample
            bsess = QuerySession(bidx, composed=bci)
            t0 = time.perf_counter()
            bci.relation("d0", bdrv.cur)     # the from-scratch recompose
            rebuild_s = time.perf_counter() - t0
            p50, p99 = _probe_latency(bsess, bidx, bdrv.cur)
            bsamples.append({
                "op": bdrv.i, "rss_mb": _rss_mb(),
                "payload_resident_mb": bidx.prov_nbytes() / 1e6,
                "rebuild_ms": rebuild_s * 1e3,
                "probe_p50_ms": p50 * 1e3, "probe_p99_ms": p99 * 1e3,
            })

    print(f"\n== stream: {ops} ops, tensor budget {tensor_budget / 1e6:.2f} MB, "
          f"cache budget {cache_budget / 1e6:.2f} MB ==")
    print(f"{'op':>9s} {'resident MB':>12s} {'RSS MB':>9s} {'sync ms':>9s} "
          f"{'p50 ms':>8s} {'p99 ms':>8s} {'extends':>8s} {'recomp':>7s}")
    for s in samples:
        rss = f"{s['rss_mb']:9.1f}" if s["rss_mb"] is not None else "      n/a"
        print(f"{s['op']:9d} {s['payload_resident_mb']:12.2f} {rss} "
              f"{s['sync_ms']:9.1f} "
              f"{s['probe_p50_ms']:8.2f} {s['probe_p99_ms']:8.2f} "
              f"{s['extends']:8d} {s['recomposes']:7d}")
    last, blast = samples[-1], bsamples[-1]
    print(f"stream wall {stream_s:.1f}s; spilled {spilled_disk_mb:.1f} MB to disk; "
          f"payload-resident bounded at {last['payload_resident_mb']:.2f} MB")
    print(f"baseline at op {blast['op']}: resident "
          f"{blast['payload_resident_mb']:.2f} MB (unbounded), "
          f"rebuild {blast['rebuild_ms']:.1f} ms (cold recompose), "
          f"warm p50 {blast['probe_p50_ms']:.2f} ms")
    return {
        "ops": ops, "tensor_budget_mb": tensor_budget / 1e6,
        "cache_budget_mb": cache_budget / 1e6,
        "parity": "byte-identical",
        "stream_wall_s": stream_s, "spilled_disk_mb": spilled_disk_mb,
        "samples": samples,
        "baseline_cap": base_cap, "baseline_samples": bsamples,
    }


def run(quick: bool = False, ops: int = 0):
    return {"extend_micro": run_extend_micro(quick=quick),
            "stream": run_stream(quick=quick, ops=ops)}


def _merge_trajectory(section: dict) -> None:
    """``BENCH_query.json`` belongs to bench_query.py; this bench only
    extends it with the ``stream`` section (creating the file when the
    query bench has not run yet)."""
    path = os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "BENCH_query.json"))
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["stream"] = section
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)
    print(f"wrote {path} (stream section)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced configuration (CI smoke) — still merges "
                    "the stream section into BENCH_query.json")
    ap.add_argument("--ops", type=int, default=0,
                    help="override the append-stream length")
    args = ap.parse_args()
    out = run(quick=args.quick, ops=args.ops)
    _merge_trajectory(out)
