"""Fig 3 + Table X: provenance-capture overhead — TensProv vs Chapman.

Per use case: pipeline wall time with structured TensProv capture (the
default: implicit identity/gather/range tensors, no COO allocation), with
the legacy eager-COO TensProv capture, and with Chapman-style cell-level
capture.  The Chapman mirror rides the supported ``add_record_hook``
capture-observer API — no ``idx.record`` monkeypatching.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core.capture import force_coo_capture
from repro.core.chapman import ChapmanIndex
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.usecases import USECASES


def _time(fn, reps=3):
    fn()  # warm-up: allocator + lazy imports, so reps=1 (quick) stays honest
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run(quick: bool = False):
    reps = 1 if quick else 3
    rows = []
    for name in USECASES:
        mk, runner = USECASES[name]

        def tens():
            runner(ProvenanceIndex(name), mk(0))

        def tens_coo():
            with force_coo_capture():
                runner(ProvenanceIndex(name), mk(0))

        def chap():
            idx = ProvenanceIndex(name)
            ch = ChapmanIndex()
            hook = idx.add_record_hook(
                lambda input_ids, output_id, out_table, info, input_tables:
                ch.capture(input_ids, input_tables, output_id, out_table, info))
            try:
                runner(idx, mk(0))
            finally:
                idx.remove_record_hook(hook)

        t_tens = _time(tens, reps)
        t_coo = _time(tens_coo, reps)
        t_chap = _time(chap, reps)
        rows.append((name, t_tens, t_coo, t_chap,
                     t_chap / t_tens, t_coo / t_tens))
    print("\n== Fig 3 / Table X: capture time (s) and speedup ==")
    print(f"{'usecase':10s} {'TensProv':>10s} {'Tens-COO':>10s} {'Chapman':>10s} "
          f"{'vs Chap':>8s} {'vs COO':>7s}")
    for name, t, c, ch, s, sc in rows:
        print(f"{name:10s} {t:10.3f} {c:10.3f} {ch:10.3f} {s:8.1f}x {sc:6.2f}x")
    return {"table": "Fig3/X", "rows": [
        {"usecase": n, "tensprov_s": t, "tensprov_coo_s": c, "chapman_s": ch,
         "speedup": s, "speedup_vs_coo": sc}
        for n, t, c, ch, s, sc in rows]}


if __name__ == "__main__":
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="1 rep per system")
    run(quick=ap.parse_args().quick)
