"""Table XI + Fig 6: the join operator at TPC-DI scale factors.

Per scale factor: dataset sizes, TensProv provenance size + capture time +
why-query time, Chapman-style size + capture time (up to SF 9 — beyond that
the baseline does not scale; the paper reports the same cut-off).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.chapman import ChapmanIndex
from repro.core.pipeline import ProvenanceIndex
from repro.core.query import q2_backward
from repro.dataprep import ops as P
from repro.dataprep.usecases import TPCDI_SCALES, make_tpcdi_join_inputs

_CHAPMAN_MAX_SF = 9       # paper: '-' at SF 15/20 (does not scale)


def run(quick: bool = False):
    scales = [3, 5] if quick else [3, 5, 9, 15, 20]
    rows = []
    for sf in scales:
        left, right = make_tpcdi_join_inputs(sf)

        # --- TensProv: active capture through the merge --------------------
        t0 = time.perf_counter()
        out, info = P.join(left, right, on="key", how="inner")
        idx = ProvenanceIndex(f"tpcdi-{sf}")
        idx.add_source("L", left)
        idx.add_source("R", right)
        idx.record(["L", "R"], "J", out, info, keep_output=False,
                   input_tables=[left, right])
        # build both CSR directions (the queryable index structure)
        idx.ops[0].tensor.bwd(0)
        idx.ops[0].tensor.bwd(1)
        t_capture = time.perf_counter() - t0
        size_mb = idx.prov_nbytes() / 1e6

        # why-provenance query on the captured join
        qrows = np.linspace(0, out.n_rows - 1, 16).astype(int).tolist()
        t0 = time.perf_counter()
        for r in qrows:
            q2_backward(idx, "J", [r], "L")
        t_query = (time.perf_counter() - t0) / len(qrows)

        # --- Chapman baseline ----------------------------------------------
        if sf <= _CHAPMAN_MAX_SF and not quick:
            ch = ChapmanIndex()
            t0 = time.perf_counter()
            ch.capture(["L", "R"], [left, right], "J", out, info)
            c_capture = time.perf_counter() - t0
            c_mb = ch.total_nbytes() / 1e6
        elif sf <= 5:
            ch = ChapmanIndex()
            t0 = time.perf_counter()
            ch.capture(["L", "R"], [left, right], "J", out, info)
            c_capture = time.perf_counter() - t0
            c_mb = ch.total_nbytes() / 1e6
        else:
            c_capture, c_mb = None, None

        rows.append({
            "sf": sf, "n_left": left.n_rows, "n_right": right.n_rows,
            "n_out": out.n_rows, "tensprov_mb": size_mb,
            "tensprov_capture_s": t_capture, "query_s": t_query,
            "chapman_mb": c_mb, "chapman_capture_s": c_capture,
        })

    print("\n== Table XI / Fig 6: TPC-DI join provenance ==")
    hdr = f"{'sf':>3s} {'left/right':>18s} {'TensProv':>9s} {'cap(s)':>7s} " \
          f"{'query(s)':>9s} {'Chapman':>9s} {'cap(s)':>7s}"
    print(hdr)
    for r in rows:
        cm = f"{r['chapman_mb']:.0f}MB" if r["chapman_mb"] else "-"
        cc = f"{r['chapman_capture_s']:.1f}" if r["chapman_capture_s"] else "-"
        print(f"{r['sf']:3d} {r['n_left']:>8d}/{r['n_right']:<9d} "
              f"{r['tensprov_mb']:7.2f}MB {r['tensprov_capture_s']:7.2f} "
              f"{r['query_s']:9.4f} {cm:>9s} {cc:>7s}")
    return {"table": "XI/Fig6", "rows": rows}


if __name__ == "__main__":
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    run()
