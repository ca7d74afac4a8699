"""Fig 4 + Fig 5: query latency Q1-Q11 on the Census pipeline, plus the
batched multi-hop comparison (per-hop walk vs batch walk vs composed
hop-cache) on a deep chain, plus the FUSED-BATCH scenario (N mixed
Q1/Q2/Q4 plans submitted to one ``QuerySession.run_many`` vs the legacy
per-query loop).

Fig 4: all queries against MATERIALIZED endpoints (the default policy keeps
source + sink).  Fig 5: the same queries when the answer must RETURN values
from a NON-materialized intermediate -> per-record recomputation (§III-E).

Census is extended with a join (as the paper does) so Q10/Q11 are defined.

Run as a script this also writes ``BENCH_query.json`` at the repo root —
the perf-trajectory artifact.
"""
from __future__ import annotations

import itertools
import json
import os
import time
import warnings

import numpy as np

from repro.core import query as Q
from repro.core.hopcache import ComposedIndex
from repro.core.pipeline import ProvenanceIndex
from repro.core.recompute import recompute_rows
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.dataprep.usecases import build_deep_chain, chain_step, make_census, make_chain_table
from repro.provenance import QuerySession, prov


def build_census_with_join(seed=0):
    idx = ProvenanceIndex("census+join")
    t = make_census(seed)
    d = track(t, idx, "census_src")
    # reference table joined on the a0 category (the paper modified Census
    # to include a join for Q10/Q11)
    ref = Table.from_columns({
        "a0": np.arange(9, dtype=np.float32),
        "region": np.arange(9, dtype=np.float32) % 4,
    })
    r = track(ref, idx, "region_ref")
    d = d.impute([f"a{j}" for j in range(9, 15)], strategy="mean")
    d = d.normalize([f"a{j}" for j in range(9, 15)], kind="zscore")
    d = d.join(r, on="a0", how="inner")
    d = d.onehot("a1", n_values=16)
    d = d.onehot("a2", n_values=64)
    d.mark_sink()
    return idx, d


def _time_ms(fn, reps=3):
    vals = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        vals.append((time.perf_counter() - t0) * 1e3)
    return float(np.mean(vals))


def _time_ms_r(fn, reps=3):
    """Like :func:`_time_ms` but also returns the last run's result, so
    sanity checks reuse the answers the timed reps already computed instead
    of re-running every contender untimed afterwards."""
    vals, res = [], None
    for _ in range(reps):
        t0 = time.perf_counter()
        res = fn()
        vals.append((time.perf_counter() - t0) * 1e3)
    return float(np.mean(vals)), res


def run(quick: bool = False):
    idx, sink = build_census_with_join()
    src, ref, out = "census_src", "region_ref", sink.dataset_id
    mid = idx.ops[2].output_id      # join output (non-materialized)
    rows = [5]
    attrs = [3]

    queries = {
        "Q1": lambda: Q.q1_forward(idx, src, rows, out),
        "Q2": lambda: Q.q2_backward(idx, out, rows, src),
        "Q3": lambda: Q.q3_forward_attr(idx, src, rows, attrs, out),
        "Q4": lambda: Q.q4_backward_attr(idx, out, rows, attrs, src),
        "Q5": lambda: Q.q5_forward_how(idx, src, rows, out),
        "Q6": lambda: Q.q6_backward_how(idx, out, rows, src),
        "Q7": lambda: Q.q7_forward_attr_how(idx, src, rows, attrs, out),
        "Q8": lambda: Q.q8_backward_attr_how(idx, out, rows, attrs, src),
        "Q9": lambda: Q.q9_all_transformations(idx, out),
        "Q10": lambda: Q.q10_co_contributory(idx, src, rows, ref),
        "Q11": lambda: Q.q11_co_dependency(idx, mid, rows, src, out),
    }
    reps = 1 if quick else 3
    fig4 = {name: _time_ms(fn, reps) for name, fn in queries.items()}

    # Fig 5: same lineage + VALUES from the non-materialized join output
    def recomputing(name, fn):
        def wrapped():
            res = fn()
            lineage = res[0] if isinstance(res, tuple) else res
            arr = np.asarray(lineage).reshape(-1)
            take = [int(x) for x in arr[:4] if np.issubdtype(arr.dtype, np.integer)]
            recompute_rows(idx, mid, take or [0])
        return wrapped

    fig5 = {}
    for name, fn in queries.items():
        if name == "Q9":
            fig5[name] = fig4[name]     # metadata-only: unaffected (paper)
            continue
        mid_q = {
            "Q1": lambda: Q.q1_forward(idx, src, rows, mid),
            "Q2": lambda: Q.q2_backward(idx, mid, rows, src),
            "Q3": lambda: Q.q3_forward_attr(idx, src, rows, attrs, mid),
            "Q4": lambda: Q.q4_backward_attr(idx, mid, rows, attrs, src),
            "Q5": lambda: Q.q5_forward_how(idx, src, rows, mid),
            "Q6": lambda: Q.q6_backward_how(idx, mid, rows, src),
            "Q7": lambda: Q.q7_forward_attr_how(idx, src, rows, attrs, mid),
            "Q8": lambda: Q.q8_backward_attr_how(idx, mid, rows, attrs, src),
            "Q10": lambda: Q.q10_co_contributory(idx, src, rows, ref),
            "Q11": lambda: Q.q11_co_dependency(idx, mid, rows, src, out),
        }[name]
        fig5[name] = _time_ms(recomputing(name, mid_q), reps)

    print("\n== Fig 4: query latency, materialized (ms) ==")
    print("  " + "  ".join(f"{k}={v:.2f}" for k, v in fig4.items()))
    print("== Fig 5: query latency with recomputation (ms) ==")
    print("  " + "  ".join(f"{k}={v:.2f}" for k, v in fig5.items()))
    batch = run_batch_vs_walk(quick=quick)
    fused = run_fused_batch(quick=quick)
    costmodel = run_costmodel(quick=quick)
    federation = run_federation(quick=quick)
    structured = run_structured(quick=quick)
    sharded = run_sharded(quick=quick)
    # capture/memory trajectory (Fig 3 / Table IX) rides the same artifact,
    # so the CI smoke step records the representation-layer numbers too
    try:
        from benchmarks import bench_capture, bench_memory
    except ImportError:                     # run as a script: sibling import
        import bench_capture, bench_memory
    capture_res = bench_capture.run(quick=quick)
    memory_res = bench_memory.run(quick=quick)
    return {"table": "Fig4/5", "fig4_ms": fig4, "fig5_ms": fig5, "batch": batch,
            "fused_batch": fused, "costmodel": costmodel,
            "federation": federation, "structured": structured,
            "sharded": sharded, "capture": capture_res, "memory": memory_res}


# ---------------------------------------------------------------------------
# Batched multi-hop Q1/Q2: per-hop walk vs batch walk vs composed hop-cache
# ---------------------------------------------------------------------------
def run_batch_vs_walk(quick: bool = False, n_probes: int = 64):
    idx, sink = build_deep_chain(n=1000 if quick else 4000,
                                 n_ops=10 if quick else 14)
    src = "chain_src"
    n_src = idx.datasets[src].n_rows
    n_sink = idx.datasets[sink].n_rows
    rng = np.random.default_rng(7)
    probes_f = [sorted(rng.choice(n_src, size=4, replace=False).tolist())
                for _ in range(8 if quick else n_probes)]
    probes_b = [sorted(rng.choice(n_sink, size=4, replace=False).tolist())
                for _ in range(8 if quick else n_probes)]
    reps = 1 if quick else 3

    # strategy-PINNED session so each contender measures its own engine (the
    # adaptive planner would otherwise route batches through the hop-cache)
    walk_sess = QuerySession(idx, ComposedIndex(idx), use_hopcache=False)

    def q1_walk(p, batched=False):
        qb = prov(idx).source(src)
        qb = qb.rows_batch(p) if batched else qb.rows(p)
        return walk_sess.run(qb.forward().to(sink).plan())

    def q2_walk(p, batched=False):
        qb = prov(idx).source(sink)
        qb = qb.rows_batch(p) if batched else qb.rows(p)
        return walk_sess.run(qb.backward().to(src).plan())

    # warm the CSR halves so every contender measures probe cost, not build
    q1_walk(probes_f[0])
    q2_walk(probes_b[0])

    walk_f, walk_res = _time_ms_r(lambda: [q1_walk(p) for p in probes_f], reps)
    batch_f, batch_res = _time_ms_r(lambda: q1_walk(probes_f, batched=True),
                                    reps)
    ci = ComposedIndex(idx, memory_budget_bytes=256 << 20)
    t0 = time.perf_counter()
    ci.q1_forward(src, probes_f[:1], sink)            # composes the relation
    compose_ms = (time.perf_counter() - t0) * 1e3
    cache_f, cache_res = _time_ms_r(lambda: ci.q1_forward(src, probes_f, sink),
                                    reps)

    walk_b = _time_ms(lambda: [q2_walk(p) for p in probes_b], reps)
    batch_b = _time_ms(lambda: q2_walk(probes_b, batched=True), reps)
    cache_b = _time_ms(lambda: ci.q2_backward(sink, probes_b, src), reps)

    # sanity: all three contenders answer identically (reusing the answers
    # the timed reps produced — no untimed re-run of every contender)
    for a, b, c in zip(walk_res, batch_res, cache_res):
        assert (a == b).all() and (a == c).all()

    out = {
        "n_ops": len(idx.ops), "n_probes": len(probes_f),
        "q1_perhop_walk_ms": walk_f, "q1_batch_walk_ms": batch_f,
        "q1_hopcache_ms": cache_f, "q1_compose_cold_ms": compose_ms,
        "q2_perhop_walk_ms": walk_b, "q2_batch_walk_ms": batch_b,
        "q2_hopcache_ms": cache_b,
        "q1_speedup_batch": walk_f / max(batch_f, 1e-9),
        "q1_speedup_hopcache": walk_f / max(cache_f, 1e-9),
        "q2_speedup_batch": walk_b / max(batch_b, 1e-9),
        "q2_speedup_hopcache": walk_b / max(cache_b, 1e-9),
        "hopcache_stats": ci.stats(),
    }
    print(f"\n== batched multi-hop Q1/Q2 ({len(idx.ops)}-op chain, "
          f"{len(probes_f)} probe sets) ==")
    print(f"  Q1  per-hop walk {walk_f:8.2f} ms | batch walk {batch_f:8.2f} ms "
          f"({out['q1_speedup_batch']:.1f}x) | hop-cache {cache_f:8.2f} ms "
          f"({out['q1_speedup_hopcache']:.1f}x; cold compose {compose_ms:.2f} ms)")
    print(f"  Q2  per-hop walk {walk_b:8.2f} ms | batch walk {batch_b:8.2f} ms "
          f"({out['q2_speedup_batch']:.1f}x) | hop-cache {cache_b:8.2f} ms "
          f"({out['q2_speedup_hopcache']:.1f}x)")
    return out


# ---------------------------------------------------------------------------
# Fused batch: N mixed Q1/Q2/Q4 plans, session.run_many vs legacy loop
# ---------------------------------------------------------------------------
def run_fused_batch(quick: bool = False, n_plans: int = 60):
    """The query-plan API's headline scenario: a mixed workload of Q1, Q2
    and Q4 plans over the same deep chain.  The legacy loop answers them one
    free-function call at a time; ``run_many`` fuses the plans sharing a
    (kind, src, dst) key into one packed pass each — Q1s become one
    composed-relation probe, Q2s another, Q4s one batched bitplane walk."""
    idx, sink = build_deep_chain(n=1000 if quick else 4000,
                                 n_ops=10 if quick else 14)
    src = "chain_src"
    n_src = idx.datasets[src].n_rows
    n_sink = idx.datasets[sink].n_rows
    c_sink = idx.datasets[sink].n_cols
    rng = np.random.default_rng(11)
    n_plans = 12 if quick else n_plans
    reps = 1 if quick else 3

    specs = []
    for i in range(n_plans):
        kind = i % 3
        if kind == 0:       # Q1 forward record
            p = sorted(rng.choice(n_src, size=4, replace=False).tolist())
            specs.append(("q1", p, None))
        elif kind == 1:     # Q2 backward record
            p = sorted(rng.choice(n_sink, size=4, replace=False).tolist())
            specs.append(("q2", p, None))
        else:               # Q4 backward attr (cells)
            p = sorted(rng.choice(n_sink, size=2, replace=False).tolist())
            a = sorted(rng.choice(c_sink, size=2, replace=False).tolist())
            specs.append(("q4", p, a))

    def make_plans():
        plans = []
        for kind, p, a in specs:
            if kind == "q1":
                plans.append(prov(idx).source(src).rows(p).forward().to(sink).plan())
            elif kind == "q2":
                plans.append(prov(idx).source(sink).rows(p).backward().to(src).plan())
            else:
                plans.append(prov(idx).source(sink).rows(p).attrs(a)
                             .backward().to(src).plan())
        return plans

    def legacy_loop():
        out = []
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            for kind, p, a in specs:
                if kind == "q1":
                    out.append(Q.q1_forward(idx, src, p, sink))
                elif kind == "q2":
                    out.append(Q.q2_backward(idx, sink, p, src))
                else:
                    out.append(Q.q4_backward_attr(idx, sink, p, a, src))
        return out

    # warm the CSR halves + tensors so both contenders measure query cost
    legacy_loop()
    legacy_ms = _time_ms(legacy_loop, reps)

    session = QuerySession(idx, ComposedIndex(idx, memory_budget_bytes=256 << 20))
    plans = make_plans()
    t0 = time.perf_counter()
    fused_first = session.run_many(plans)     # includes cold relation compose
    fused_cold_ms = (time.perf_counter() - t0) * 1e3
    fused_ms = _time_ms(lambda: session.run_many(make_plans()), reps)

    # sanity: fused results == the legacy loop's, element for element
    for a, b in zip(legacy_loop(), fused_first):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    out = {
        "n_plans": n_plans, "n_ops": len(idx.ops),
        "legacy_loop_ms": legacy_ms,
        "session_run_many_ms": fused_ms,
        "session_run_many_cold_ms": fused_cold_ms,
        "speedup_fused": legacy_ms / max(fused_ms, 1e-9),
        "speedup_fused_cold": legacy_ms / max(fused_cold_ms, 1e-9),
        "session_stats": session.stats(),
    }
    print(f"\n== fused batch: {n_plans} mixed Q1/Q2/Q4 plans "
          f"({len(idx.ops)}-op chain) ==")
    print(f"  legacy per-query loop {legacy_ms:8.2f} ms | session.run_many "
          f"{fused_ms:8.2f} ms ({out['speedup_fused']:.1f}x; cold "
          f"{fused_cold_ms:.2f} ms, {out['speedup_fused_cold']:.1f}x)")
    return out


# ---------------------------------------------------------------------------
# Cost-model routing: auto vs forced strategies vs the legacy min-batch
# heuristic, plus the vectorized bitplane backward-probe microbench
# ---------------------------------------------------------------------------
def _strategy_sessions(idx):
    """One session per routing policy, each over its OWN hop-cache."""
    auto = QuerySession(idx, ComposedIndex(idx, memory_budget_bytes=256 << 20))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        heuristic = QuerySession(
            idx, ComposedIndex(idx, memory_budget_bytes=256 << 20),
            hopcache_min_batch=8)
        forced_hc = QuerySession(
            idx, ComposedIndex(idx, memory_budget_bytes=256 << 20),
            hopcache_min_batch=1)
    forced_walk = QuerySession(idx, ComposedIndex(idx), use_hopcache=False)
    return {"auto": auto, "heuristic_minbatch8": heuristic,
            "forced_hopcache": forced_hc, "forced_walk": forced_walk}


def run_costmodel(quick: bool = False):
    """Three workloads × four routing policies, measured at steady state
    (one warm-up pass lets the cost model's demand amortization settle and
    lets every policy compose whatever it chooses to compose):

    * ``small_batch_stream`` — N single-probe Q1s to one far pair.  The
      ``hopcache_min_batch`` heuristic walks EVERY one (B=1 < 8, and the
      relation is never composed, so the cached-pair check never fires) —
      the mis-routing the cost model fixes by amortizing demand.
    * ``large_batch`` — one B=64 batched Q1 + one B=64 batched Q2.
    * ``mixed`` — interleaved singles and batches, fwd and bwd.
    """
    idx, sink = build_deep_chain(n=1000 if quick else 4000,
                                 n_ops=10 if quick else 14)
    src = "chain_src"
    n_src = idx.datasets[src].n_rows
    n_sink = idx.datasets[sink].n_rows
    rng = np.random.default_rng(23)
    n_stream = 16 if quick else 60
    reps = 1 if quick else 3

    singles_f = [sorted(rng.choice(n_src, size=4, replace=False).tolist())
                 for _ in range(n_stream)]
    singles_b = [sorted(rng.choice(n_sink, size=4, replace=False).tolist())
                 for _ in range(n_stream)]
    batch_f = singles_f[: (8 if quick else 64)]
    batch_b = singles_b[: (8 if quick else 64)]

    def wl_small(sess):
        return [sess.run(prov(idx).source(src).rows(p).forward().to(sink).plan())
                for p in singles_f]

    def wl_large(sess):
        a = sess.run(prov(idx).source(src).rows_batch(batch_f)
                     .forward().to(sink).plan())
        b = sess.run(prov(idx).source(sink).rows_batch(batch_b)
                     .backward().to(src).plan())
        return a + b

    def wl_mixed(sess):
        out = []
        for i in range(0, n_stream, 4):
            out.append(sess.run(prov(idx).source(src).rows(singles_f[i])
                                .forward().to(sink).plan()))
            out.append(sess.run(prov(idx).source(sink).rows(singles_b[i])
                                .backward().to(src).plan()))
        out.append(sess.run(prov(idx).source(src).rows_batch(batch_f)
                            .forward().to(sink).plan()))
        return out

    def _assert_same(a, b):
        if isinstance(a, list) and not isinstance(a, np.ndarray):
            assert len(a) == len(b)
            for x, y in zip(a, b):
                _assert_same(x, y)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    workloads = {"small_batch_stream": wl_small, "large_batch": wl_large,
                 "mixed": wl_mixed}
    out = {"n_ops": len(idx.ops), "n_stream": n_stream, "workloads": {}}
    print("\n== cost-model routing (steady state, ms) ==")
    for wname, wl in workloads.items():
        sessions = _strategy_sessions(idx)
        answers = {}
        for sname, sess in sessions.items():
            # warm-up twice: the first pass accumulates demand and pays any
            # cold compose the policy chooses; the second confirms routing
            # has settled, so the timed reps measure steady state
            answers[sname] = wl(sess)
            wl(sess)
        # sanity: every policy answers identically
        base = answers["forced_walk"]
        for sname, ans in answers.items():
            _assert_same(base, ans)
        # PAIRED rounds: every policy runs once per round, and the headline
        # ratios are medians of PER-ROUND ratios — machine-load drift on
        # this shared host swings absolute times by tens of percent across
        # seconds, but within one ~10ms round it cancels.  Round order
        # cycles through ALL permutations (cyclic rotation alone preserves
        # adjacency, so one policy would always inherit the allocator state
        # the 20ms forced-walk workload leaves behind).
        raw = {sname: [] for sname in sessions}
        perms = list(itertools.permutations(sessions))
        # stride coprime to len(perms): any PREFIX of rounds (quick mode runs
        # only 8 of the 24 permutations) already spreads leading positions,
        # where lexicographic order would hand one policy most first slots
        stride = 7
        for r in range(reps * 8):
            for sname in perms[(r * stride) % len(perms)]:
                t0 = time.perf_counter()
                wl(sessions[sname])
                raw[sname].append((time.perf_counter() - t0) * 1e3)
        times = {sname: float(np.median(v)) for sname, v in raw.items()}
        best_forced_r = np.minimum(np.array(raw["forced_walk"]),
                                   np.array(raw["forced_hopcache"]))
        ratio_best = float(np.median(np.array(raw["auto"]) / best_forced_r))
        ratio_heur = float(np.median(
            np.array(raw["heuristic_minbatch8"]) / np.array(raw["auto"])))
        entry = {
            **{f"{s}_ms": t for s, t in times.items()},
            "auto_vs_best_forced": ratio_best,
            "speedup_vs_heuristic": ratio_heur,
            "auto_planner": sessions["auto"].counters,
        }
        out["workloads"][wname] = entry
        print(f"  {wname:20s} auto {times['auto']:8.2f} | heuristic "
              f"{times['heuristic_minbatch8']:8.2f} | walk "
              f"{times['forced_walk']:8.2f} | hopcache "
              f"{times['forced_hopcache']:8.2f}  "
              f"(auto/best {entry['auto_vs_best_forced']:.2f}x, "
              f"vs heuristic {entry['speedup_vs_heuristic']:.1f}x)")

    out["backward_probe"] = run_backward_probe_microbench(idx, src, sink,
                                                         quick=quick)
    return out


# ---------------------------------------------------------------------------
# Structured representations: composed-chain build + probes + entry memory,
# structured capture (implicit tensors, closed-form compose) vs forced COO
# ---------------------------------------------------------------------------
def run_structured(quick: bool = False, n_probes: int = 64):
    """The representation-layer headline: the SAME identity/selection-heavy
    deep chain captured structured (implicit tensors -> closed-form gather
    composition in the hop-cache) vs forced explicit COO (CSR mirrors ->
    spmm composition).  Reports cold composed-chain build time, batched
    probe latency at steady state, and the cached relation's byte footprint.
    """
    from repro.core.capture import force_coo_capture

    n = 8000 if quick else 100_000
    n_ops = 10 if quick else 14
    B = 8 if quick else n_probes
    reps = 1 if quick else 3

    idx_s, sink_s = build_deep_chain(n=n, n_ops=n_ops)
    with force_coo_capture():
        idx_c, sink_c = build_deep_chain(n=n, n_ops=n_ops)
    src = "chain_src"
    n_src = idx_s.datasets[src].n_rows
    n_sink = idx_s.datasets[sink_s].n_rows
    rng = np.random.default_rng(17)
    probes_f = [sorted(rng.choice(n_src, size=4, replace=False).tolist())
                for _ in range(B)]
    probes_b = [sorted(rng.choice(n_sink, size=4, replace=False).tolist())
                for _ in range(B)]

    def cold_build(idx, sink):
        ci = ComposedIndex(idx, memory_budget_bytes=512 << 20)
        t0 = time.perf_counter()
        ci.relation(src, sink)
        return ci, (time.perf_counter() - t0) * 1e3

    # CSR mirrors for the COO world are part of the honest cold cost, so
    # time the FIRST build.  The warm re-build (fresh cache, tensors warm)
    # is a full extra compose per world — skipped under --quick, where it
    # used to redundantly re-run work the cold pass just measured.
    ci_s, build_s_cold = cold_build(idx_s, sink_s)
    ci_c, build_c_cold = cold_build(idx_c, sink_c)
    build_s_warm = build_c_warm = None
    if not quick:
        _, build_s_warm = cold_build(idx_s, sink_s)
        _, build_c_warm = cold_build(idx_c, sink_c)

    probe_f_s, res_f_s = _time_ms_r(
        lambda: ci_s.q1_forward(src, probes_f, sink_s), reps)
    probe_f_c, res_f_c = _time_ms_r(
        lambda: ci_c.q1_forward(src, probes_f, sink_c), reps)
    probe_b_s, res_b_s = _time_ms_r(
        lambda: ci_s.q2_backward(sink_s, probes_b, src), reps)
    probe_b_c, res_b_c = _time_ms_r(
        lambda: ci_c.q2_backward(sink_c, probes_b, src), reps)

    # parity: structured answers == forced-COO answers, element for element
    # (the answers the timed reps computed — no extra probe pass)
    for a, b in zip(res_f_s, res_f_c):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(res_b_s, res_b_c):
        np.testing.assert_array_equal(a, b)

    entry_s = ci_s._relation_entry(src, sink_s)
    entry_c = ci_c._relation_entry(src, sink_c)
    tensors_s = sum(op.tensor.nbytes() for op in idx_s.ops)
    tensors_c = sum(op.tensor.nbytes() for op in idx_c.ops)
    out = {
        "n": n, "n_ops": n_ops, "n_probes": B,
        "build_structured_cold_ms": build_s_cold,
        "build_coo_cold_ms": build_c_cold,
        "build_structured_warm_ms": build_s_warm,
        "build_coo_warm_ms": build_c_warm,
        "speedup_build_cold": build_c_cold / max(build_s_cold, 1e-9),
        "speedup_build_warm": (build_c_warm / max(build_s_warm, 1e-9)
                              if build_s_warm is not None else None),
        "q1_probe_structured_ms": probe_f_s,
        "q1_probe_coo_ms": probe_f_c,
        "q2_probe_structured_ms": probe_b_s,
        "q2_probe_coo_ms": probe_b_c,
        "entry_backend_structured": entry_s.backend,
        "entry_backend_coo": entry_c.backend,
        "entry_bytes_structured": entry_s.nbytes(),
        "entry_bytes_coo": entry_c.nbytes(),
        "entry_bytes_ratio": entry_c.nbytes() / max(entry_s.nbytes(), 1),
        "tensor_bytes_structured": tensors_s,
        "tensor_bytes_coo": tensors_c,
        "tensor_bytes_ratio": tensors_c / max(tensors_s, 1),
        "hopcache_stats": ci_s.stats(),
    }
    print(f"\n== structured representations ({n_ops}-op chain, n={n}) ==")
    warm_note = (f", {out['speedup_build_warm']:.1f}x warm"
                 if out["speedup_build_warm"] is not None else "")
    print(f"  composed-chain build  structured {build_s_cold:8.2f} ms | "
          f"COO+spmm {build_c_cold:8.2f} ms "
          f"({out['speedup_build_cold']:.1f}x cold{warm_note})")
    print(f"  batched probes (B={B})  Q1 {probe_f_s:.2f} vs {probe_f_c:.2f} ms | "
          f"Q2 {probe_b_s:.2f} vs {probe_b_c:.2f} ms")
    print(f"  relation entry  {entry_s.backend} {entry_s.nbytes()/1e3:.1f} KB vs "
          f"{entry_c.backend} {entry_c.nbytes()/1e3:.1f} KB "
          f"({out['entry_bytes_ratio']:.1f}x); op tensors "
          f"{tensors_s/1e3:.1f} KB vs {tensors_c/1e3:.1f} KB "
          f"({out['tensor_bytes_ratio']:.1f}x)")
    return out


# ---------------------------------------------------------------------------
# Federation: batched cross-index trace-to-source vs the merged baseline
# ---------------------------------------------------------------------------
def build_split_chain(seed=0, n=4000, n_ops=12):
    """The SAME deep chain split at the midpoint into a prep index and a
    serve index glued by an identity catalog link — the federated twin of
    :func:`build_deep_chain`."""
    from repro.provenance import ProvCatalog

    cut = n_ops // 2
    prep = ProvenanceIndex("prep")
    d = track(make_chain_table(seed, n), prep, "chain_src")
    for i in range(cut):
        d = chain_step(d, i)
    boundary = d.dataset_id
    serve = ProvenanceIndex("serve")
    s = track(d.table, serve, "ingest")
    for i in range(cut, n_ops):
        s = chain_step(s, i)
    s.mark_sink()
    catalog = ProvCatalog("bench-fed")
    catalog.register("prep", prep).register("serve", serve)
    catalog.link(f"prep/{boundary}", "serve/ingest")
    return catalog, f"serve/{s.dataset_id}", "prep/chain_src"


def run_federation(quick: bool = False, n_probes: int = 64):
    """The redesign's headline scenario: a BATCH of cross-index
    trace-to-source queries (serve sink rows -> prep raw rows) through the
    FederatedSession — plan split at the boundary, one cost-model-routed
    pass per side, mask stitch between — against the merged-single-index
    baseline answering the identical batch with one composed-relation
    probe.  PAIRED per-round ratios (contender order alternating) keep the
    headline number robust to shared-host load drift."""
    n = 1000 if quick else 4000
    n_ops = 10 if quick else 14
    B = 8 if quick else n_probes
    reps = 8 if quick else 24
    merged_idx, merged_sink = build_deep_chain(n=n, n_ops=n_ops)
    catalog, fed_sink, fed_src = build_split_chain(n=n, n_ops=n_ops)

    n_sink = merged_idx.datasets[merged_sink].n_rows
    rng = np.random.default_rng(13)
    probes = [sorted(rng.choice(n_sink, size=4, replace=False).tolist())
              for _ in range(B)]

    merged_sess = QuerySession(merged_idx,
                               ComposedIndex(merged_idx,
                                             memory_budget_bytes=256 << 20))
    fed_sess = catalog.session()

    def run_merged():
        return merged_sess.run(prov(merged_idx).source(merged_sink)
                               .rows_batch(probes).backward()
                               .to("chain_src").plan())

    def run_fed():
        return fed_sess.run(prov(catalog).source(fed_sink)
                            .rows_batch(probes).backward()
                            .to(fed_src).plan())

    # warm-up: both sides compose whatever their cost models choose, and
    # the sanity check pins byte-identical answers
    t0 = time.perf_counter()
    fed_first = run_fed()
    fed_cold_ms = (time.perf_counter() - t0) * 1e3
    for a, b in zip(run_merged(), fed_first):
        np.testing.assert_array_equal(a, b)
    run_merged(), run_fed()

    raw = {"merged": [], "federated": []}
    for r in range(reps):
        order = (("merged", run_merged), ("federated", run_fed))
        if r % 2:
            order = order[::-1]
        for name, fn in order:
            t0 = time.perf_counter()
            fn()
            raw[name].append((time.perf_counter() - t0) * 1e3)
    merged_ms = float(np.median(raw["merged"]))
    fed_ms = float(np.median(raw["federated"]))
    overhead = float(np.median(np.array(raw["federated"])
                               / np.array(raw["merged"])))
    out = {
        "n_ops": n_ops, "n_probes": B,
        "merged_ms": merged_ms, "federated_ms": fed_ms,
        "federated_cold_ms": fed_cold_ms,
        "overhead_ratio": overhead,
        "federation_stats": fed_sess.stats()["federation"],
    }
    print(f"\n== federation: batched trace-to-source, B={B} "
          f"({n_ops}-op chain split at the midpoint) ==")
    print(f"  merged single index {merged_ms:8.2f} ms | federated "
          f"{fed_ms:8.2f} ms ({overhead:.2f}x; cold {fed_cold_ms:.2f} ms)")
    return out


# ---------------------------------------------------------------------------
# Sharded index: batched Q1/Q2 probe throughput vs shard count
# ---------------------------------------------------------------------------
def run_sharded(quick: bool = False):
    """Batched Q1/Q2 probes through the row-range-sharded hop-cache at
    S in {1, 2, 4, 8} shards (n=1M under ``--quick``, n=10M in the full
    bench).  Each shard's block probe is timed individually; ``total_ms``
    sums them (what one host running every shard sequentially pays) and
    ``critical_ms`` takes the max (the mesh-parallel critical path — what
    an S-device mesh pays, since the blocks are independent until the
    final concat/OR join).  Throughput derives from the critical path and
    is labeled as such."""
    from repro.provenance.sharded import (
        ShardedComposedIndex,
        ShardedProvenanceIndex,
    )

    n = 1_000_000 if quick else 10_000_000
    n_ops = 6 if quick else 8
    B = 8 if quick else 16
    reps = 1 if quick else 3
    shard_counts = [1, 2, 4, 8]

    idx, sink = build_deep_chain(n=n, n_ops=n_ops)
    src = "chain_src"
    n_src = idx.datasets[src].n_rows
    n_sink = idx.datasets[sink].n_rows
    rng = np.random.default_rng(29)
    masks_f = np.zeros((B, n_src), dtype=bool)
    masks_b = np.zeros((B, n_sink), dtype=bool)
    for b in range(B):
        masks_f[b, rng.choice(n_src, size=4, replace=False)] = True
        masks_b[b, rng.choice(n_sink, size=4, replace=False)] = True

    # merged baseline answers pin parity for every shard count
    ci = ComposedIndex(idx, memory_budget_bytes=1 << 30)
    want_f = ci.probe_forward(masks_f, src, sink)
    want_b = ci.probe_backward(masks_b, sink, src)
    merged_f_ms = _time_ms(lambda: ci.probe_forward(masks_f, src, sink), reps)
    merged_b_ms = _time_ms(lambda: ci.probe_backward(masks_b, sink, src), reps)

    out = {"n": n, "n_ops": len(idx.ops), "n_probes": B,
           "merged_q1_ms": merged_f_ms, "merged_q2_ms": merged_b_ms,
           "shards": {}}
    print(f"\n== sharded index: batched Q1/Q2 probes, n={n}, B={B} ==")
    print(f"  merged baseline  Q1 {merged_f_ms:8.2f} ms | "
          f"Q2 {merged_b_ms:8.2f} ms")
    for S in shard_counts:
        sv = ShardedProvenanceIndex(idx, S, engine="numpy")
        sc = sv.composed(memory_budget_bytes=1 << 30)
        t0 = time.perf_counter()
        got_f = sc.probe_forward(masks_f, src, sink)
        compose_ms = (time.perf_counter() - t0) * 1e3
        got_b = sc.probe_backward(masks_b, sink, src)
        np.testing.assert_array_equal(got_f, want_f)
        np.testing.assert_array_equal(got_b, want_b)
        entry = sc._entry(src, sink)
        # per-block timings take the pre-transposed float32 masks the probe
        # surface hoists — each device converts its replicated input once,
        # so the per-block cost is the spmm alone
        mT_f = np.ascontiguousarray(masks_f.T, dtype=np.float32)
        mT_b = np.ascontiguousarray(masks_b.T, dtype=np.float32)
        per_f = [_time_ms(lambda blk=blk: ShardedComposedIndex._block_forward(
            blk, mT_f), reps) for blk in entry.blocks]
        per_b = [_time_ms(lambda blk=blk: ShardedComposedIndex._block_backward(
            blk, mT_b[blk.lo: blk.hi]), reps) for blk in entry.blocks]
        crit_f, crit_b = max(per_f), max(per_b)
        row = {
            "q1_total_ms": float(sum(per_f)),
            "q1_critical_ms": crit_f,
            "q2_total_ms": float(sum(per_b)),
            "q2_critical_ms": crit_b,
            "compose_cold_ms": compose_ms,
            # probes/s on the mesh critical path (S devices, one per shard)
            "q1_critical_path_probes_per_s": B * 1e3 / max(crit_f, 1e-9),
            "q2_critical_path_probes_per_s": B * 1e3 / max(crit_b, 1e-9),
            "blocks": [{"rows": int(blk.hi - blk.lo), "nnz": int(blk.nnz),
                        "kind": blk.kind} for blk in entry.blocks],
        }
        out["shards"][str(S)] = row
        print(f"  S={S}  Q1 critical {crit_f:8.2f} ms "
              f"({row['q1_critical_path_probes_per_s']:10.0f} probes/s) | "
              f"Q2 critical {crit_b:8.2f} ms "
              f"({row['q2_critical_path_probes_per_s']:10.0f} probes/s) | "
              f"total {row['q1_total_ms']:.2f}/{row['q2_total_ms']:.2f} ms")
    return out


def run_backward_probe_microbench(idx, src, sink, quick: bool = False):
    """Old per-probe Python loop over relation rows vs the vectorized
    transposed-plane scatter-OR, on the bitplane backend."""
    from repro.core.provtensor import pack_bitplane

    ci = ComposedIndex(idx, backend="bitplane", memory_budget_bytes=256 << 20)
    entry = ci._relation_entry(src, sink)
    rel = entry.rel
    n_sink = idx.datasets[sink].n_rows
    rng = np.random.default_rng(5)
    B = 64 if quick else 256
    masks = np.zeros((B, n_sink), dtype=bool)
    for b in range(B):
        masks[b, rng.choice(n_sink, size=4, replace=False)] = True
    reps = 1 if quick else 3

    def old_loop():
        words = pack_bitplane(masks)
        return np.stack([(rel & w[None, :]).any(axis=1) for w in words], axis=0)

    new = ci.probe_backward(masks, sink, src)       # warms the relT plane
    np.testing.assert_array_equal(new, old_loop())  # exact parity
    old_ms = _time_ms(old_loop, reps)
    new_ms = _time_ms(lambda: ci.probe_backward(masks, sink, src), reps)
    out = {"n_probes": B, "old_loop_ms": old_ms, "vectorized_ms": new_ms,
           "speedup": old_ms / max(new_ms, 1e-9)}
    print(f"  backward-probe microbench (B={B}): loop {old_ms:.2f} ms | "
          f"vectorized {new_ms:.2f} ms ({out['speedup']:.1f}x)")
    return out


def _write_trajectory(results: dict) -> None:
    """``BENCH_query.json`` is shared: sibling benches (serving / stream /
    impact / kernels) merge their own sections into it, so carry over any
    section this bench does not produce instead of overwriting the file
    wholesale (which silently dropped ``serving`` whenever this bench ran
    last)."""
    path = os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "BENCH_query.json"))
    merged: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f)
        except ValueError:
            merged = {}
    merged.update(results)
    with open(path, "w") as f:
        json.dump(merged, f, indent=1, default=float)
    print(f"wrote {path}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced configuration (CI smoke: small chain, "
                    "1 rep) — still writes BENCH_query.json")
    args = ap.parse_args()
    _write_trajectory(run(quick=args.quick))
