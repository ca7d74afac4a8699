"""Impact-analysis benchmark: erasure closure, what-if replay, federated cells.

Three scenarios for the ``repro.provenance.impact`` subsystem:

* **closure** — one ``erasure_plan`` over a deep chain (ONE multi-seed
  forward pass covering every downstream dataset) vs the naive GDPR
  handler: one forward record query PER (erased row, downstream dataset).
  Parity is asserted per dataset before anything is timed.
* **whatif** — ``whatif_replay`` with a small perturbation set against the
  honest alternative: rebuilding the WHOLE pipeline with the patched
  source and reading the same sink rows.  Replay answers are asserted
  equal to the full re-run.  Headline: the rerun/replay ratio
  (acceptance: >= 5x at n=100k with a handful of perturbed rows).
* **federated cells** — the same cells+attrs query through a two-member
  catalog (stitched per-member term walks across a boundary link) vs the
  merged single index, byte-identical answers asserted, cold + warm
  timings reported.

Run as a script this merges an ``impact`` section into ``BENCH_query.json``
at the repo root (the perf-trajectory artifact bench_query.py owns).
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core.pipeline import ProvenanceIndex
from repro.core.recompute import fetch_rows
from repro.dataprep.table import Table
from repro.dataprep.tracked import track
from repro.provenance import ProvCatalog, erasure_plan, prov, whatif_replay


def _median_ms(fn, reps=5):
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


# ===========================================================================
# (a) erasure closure vs the naive per-row loop
# ===========================================================================
def _chain(n, hops, seed=0, name="cl"):
    rng = np.random.default_rng(seed)
    idx = ProvenanceIndex(name)
    t = track(Table.from_columns({
        "k": np.arange(n, dtype=np.float32),
        "x": rng.normal(size=n).astype(np.float32)}), idx, "src")
    for i in range(hops):
        kind = i % 3
        if kind == 0:
            t = t.value_transform("x", "scale", factor=1.0 + i)
        elif kind == 1:
            t = t.filter_rows(rng.random(t.table.n_rows) > 0.05)
        else:
            t = t.oversample(frac=0.04, seed=i, noise=0.0)
    t.mark_sink()
    return idx, t.dataset_id


def run_closure(quick: bool = False):
    n = 5_000 if quick else 50_000
    hops = 9
    n_erase = 8 if quick else 32
    idx, sink = _chain(n, hops)
    rng = np.random.default_rng(1)
    rows = np.unique(rng.integers(0, n, size=n_erase))
    targets = [ds for ds in idx.datasets if ds != "src"]

    # -- parity: plan closure == union of per-row forward queries -----------
    plan = erasure_plan(idx, "src", rows)
    sess = idx.session()

    def naive():
        out = {}
        for ds in targets:
            acc = []
            for r in rows:
                acc.append(prov(idx).source("src").rows([int(r)])
                           .forward().to(ds).run(sess))
            out[ds] = np.unique(np.concatenate(acc))
        return out

    per_row = naive()
    for ds in targets:
        imp = plan.impact(ds)
        got = imp.rows if imp is not None else np.empty(0, np.int64)
        assert np.array_equal(got, per_row[ds]), ds
    print(f"parity: erasure closure == {len(rows)}x{len(targets)} per-row "
          "queries (exact)")

    plan_ms = _median_ms(lambda: erasure_plan(idx, "src", rows))
    naive_ms = _median_ms(naive, reps=3)
    ratio = naive_ms / plan_ms
    print(f"\n== closure: n={n}, {hops} hops, {len(rows)} erased rows ==")
    print(f"erasure_plan (one multi-seed pass) p50 {plan_ms:8.2f} ms")
    print(f"naive per-(row,dataset) loop       p50 {naive_ms:8.2f} ms")
    print(f"speedup: {ratio:.1f}x")
    return {"n": n, "hops": hops, "n_erased": int(len(rows)),
            "plan_ms_p50": plan_ms, "naive_ms_p50": naive_ms,
            "speedup": float(ratio)}


# ===========================================================================
# (b) what-if replay vs full pipeline re-run
# ===========================================================================
def _build_whatif(src_cols, dims_cols, n, name):
    """Frozen-choice pipeline (filter masks drawn from a fixed rng, never
    from data; jitter seeds stored) so a re-run with a patched source is
    EXACTLY comparable to the surgical replay.  A join + a dozen ops make
    the re-run arm representative of a real preparation pipeline."""
    rng = np.random.default_rng(7)
    idx = ProvenanceIndex(name)
    t = track(Table.from_columns({c: v.copy() for c, v in src_cols.items()}),
              idx, "src")
    dims = track(Table.from_columns(
        {c: v.copy() for c, v in dims_cols.items()}), idx)
    t = t.value_transform("x", "scale", factor=1e-2)
    t = t.filter_rows(rng.random(t.table.n_rows) > 0.03)
    t = t.join(dims, on="k", how="inner")
    t = t.value_transform("w", "scale", factor=2.0)
    t = t.oversample(frac=0.02, seed=5, noise=0.1)
    for i in range(5):
        t = t.value_transform("y", "scale", factor=1.0 + 0.1 * i)
    t = t.filter_rows(rng.random(t.table.n_rows) > 0.02)
    t = t.value_transform("x", "clip", lo=-1e6, hi=1e6)
    t.mark_sink()
    return idx, t.dataset_id


def run_whatif(quick: bool = False):
    n = 20_000 if quick else 100_000
    n_perturb = 4
    rng = np.random.default_rng(3)
    src_cols = {
        "k": np.arange(n, dtype=np.float32),
        "x": rng.normal(size=n).astype(np.float32),
        "y": rng.normal(size=n).astype(np.float32),
    }
    dims_cols = {
        "k": np.arange(n, dtype=np.float32),
        "w": rng.normal(size=n).astype(np.float32),
    }
    idx, sink = _build_whatif(src_cols, dims_cols, n, "wf")
    rows = np.unique(rng.integers(0, n, size=n_perturb)).tolist()
    patch = {"x": [100.0 + i for i in range(len(rows))]}

    res = whatif_replay(idx, "src", rows, patch, sink)

    def full_rerun():
        cols = {c: v.copy() for c, v in src_cols.items()}
        cols["x"][rows] = np.asarray(patch["x"], dtype=np.float32)
        ridx, rsink = _build_whatif(cols, dims_cols, n, "wf-rerun")
        return fetch_rows(ridx, rsink, res.sink_rows)

    # -- parity: surgical replay == patched full re-run ---------------------
    truth = full_rerun()
    ok = ~truth.null
    np.testing.assert_array_equal(res.after.null, truth.null)
    np.testing.assert_allclose(res.after.data[ok], truth.data[ok],
                               rtol=1e-5, atol=1e-6)
    print(f"parity: what-if replay == full re-run on {len(res.sink_rows)} "
          "affected sink rows (exact)")

    replay_ms = _median_ms(
        lambda: whatif_replay(idx, "src", rows, patch, sink))
    rerun_ms = _median_ms(full_rerun, reps=3)
    ratio = rerun_ms / replay_ms
    n_sink = idx.datasets[sink].n_rows
    print(f"\n== what-if: n={n}, {len(rows)} perturbed rows -> "
          f"{len(res.sink_rows)}/{n_sink} sink rows ==")
    print(f"whatif_replay (affected rows only) p50 {replay_ms:8.2f} ms")
    print(f"full pipeline re-run               p50 {rerun_ms:8.2f} ms")
    print(f"speedup: {ratio:.1f}x (acceptance >= 5x at n=100k)")
    if not quick:     # the quick config is too small for the fixed bar
        assert ratio >= 5.0, \
            f"what-if replay only {ratio:.1f}x over full re-run"
    return {"n": n, "n_perturbed": len(rows),
            "n_sink_rows_recomputed": int(len(res.sink_rows)),
            "replay_ms_p50": replay_ms, "rerun_ms_p50": rerun_ms,
            "speedup": float(ratio)}


# ===========================================================================
# (c) federated cells vs merged single index
# ===========================================================================
def _cells_pipelines(n, seed=0):
    """One frozen op list applied to a merged index AND to a prep/serve
    catalog cut at the midpoint (identity boundary link)."""
    rng = np.random.default_rng(seed)
    cols = {"a": rng.normal(size=n).astype(np.float32),
            "b": rng.normal(size=n).astype(np.float32),
            "c": rng.normal(size=n).astype(np.float32)}
    mask = rng.random(n) > 0.1

    def _front(idx):
        t = track(Table.from_columns({c: v.copy() for c, v in cols.items()}),
                  idx, "src")
        t = t.value_transform("a", "scale", factor=2.0)
        t = t.filter_rows(mask)
        return t

    def _back(t):
        t = t.normalize(["b"], kind="zscore")
        t = t.oversample(frac=0.1, seed=2, noise=0.0)
        t.mark_sink()
        return t.dataset_id

    merged = ProvenanceIndex("merged")
    m_sink = _back(_front(merged))

    prep = ProvenanceIndex("prep")
    cut = _front(prep)
    cut.mark_sink()
    serve = ProvenanceIndex("serve")
    s_sink = _back(track(cut.table, serve, "ingest"))
    catalog = ProvCatalog("bench")
    catalog.register("prep", prep).register("serve", serve)
    catalog.link(f"prep/{cut.dataset_id}", "serve/ingest")
    return merged, m_sink, catalog, f"serve/{s_sink}"


def run_federated_cells(quick: bool = False):
    n = 1_000 if quick else 8_000
    merged, m_sink, catalog, f_sink = _cells_pipelines(n)
    rng = np.random.default_rng(9)
    rows = sorted(rng.integers(0, n, size=6).tolist())
    attrs = [0, 1]

    def _merged():
        return (prov(merged).source("src").rows(rows).attrs(attrs)
                .forward().to(m_sink).how().run())

    def _federated():
        return (prov(catalog).source("prep/src").rows(rows).attrs(attrs)
                .forward().to(f_sink).how().run())

    t0 = time.perf_counter()
    want, want_hops = _merged()
    merged_cold = (time.perf_counter() - t0) * 1e3
    t0 = time.perf_counter()
    got, got_hops = _federated()
    fed_cold = (time.perf_counter() - t0) * 1e3
    np.testing.assert_array_equal(np.asarray(want.todense()) if hasattr(
        want, "todense") else np.asarray(want),
        np.asarray(got.todense()) if hasattr(got, "todense")
        else np.asarray(got))
    # merged trace == federated trace minus the synthetic link crossings
    assert len([h for h in got_hops if h.category != "link"]) == len(want_hops)
    print("parity: federated cells+how == merged (link hops excluded)")

    merged_ms = _median_ms(_merged)
    fed_ms = _median_ms(_federated)
    print(f"\n== federated cells: n={n}, {len(rows)} rows x {len(attrs)} attrs ==")
    print(f"merged single index  cold {merged_cold:7.2f} ms  warm p50 {merged_ms:7.2f} ms")
    print(f"federated (stitched) cold {fed_cold:7.2f} ms  warm p50 {fed_ms:7.2f} ms")
    print(f"federated/merged warm ratio: {fed_ms / merged_ms:.2f}x")
    return {"n": n, "merged_cold_ms": merged_cold, "federated_cold_ms": fed_cold,
            "merged_ms_p50": merged_ms, "federated_ms_p50": fed_ms,
            "ratio_warm": float(fed_ms / merged_ms)}


def run(quick: bool = False):
    return {"closure": run_closure(quick=quick),
            "whatif": run_whatif(quick=quick),
            "federated_cells": run_federated_cells(quick=quick)}


def _merge_trajectory(section: dict) -> None:
    """``BENCH_query.json`` belongs to bench_query.py; this bench only
    extends it with the ``impact`` section (creating the file when the
    query bench has not run yet)."""
    path = os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "BENCH_query.json"))
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["impact"] = section
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)
    print(f"wrote {path} (impact section)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reduced configuration (CI smoke) — still merges "
                    "the impact section into BENCH_query.json")
    args = ap.parse_args()
    out = run(quick=args.quick)
    _merge_trajectory(out)
