"""Table IX: memory required for storing provenance — TensProv vs Chapman.

Prints one row per use case: structured TensProv bytes (implicit tensors,
the capture default), legacy eager-COO TensProv bytes, Chapman cell-level
bytes, and the two Table-IX ratios.  The Chapman mirror uses the supported
``add_record_hook`` capture-observer API (no monkeypatching), so it sees
exactly the record stream the real index sees.
"""
from __future__ import annotations

from repro.core.capture import force_coo_capture
from repro.core.chapman import ChapmanIndex
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.usecases import USECASES


def _capture_usecase(name: str, mirror_chapman: bool = False):
    """Run one use case into a fresh index; optionally mirror the capture
    stream into a ChapmanIndex through the record-hook API."""
    mk, runner = USECASES[name]
    idx = ProvenanceIndex(name)
    ch = ChapmanIndex() if mirror_chapman else None
    hook = None
    if ch is not None:
        hook = idx.add_record_hook(
            lambda input_ids, output_id, out_table, info, input_tables:
            ch.capture(input_ids, input_tables, output_id, out_table, info))
    try:
        runner(idx, mk(0))
    finally:
        if hook is not None:
            idx.remove_record_hook(hook)
    return idx, ch


def run(quick: bool = False):
    rows = []
    for name in USECASES:
        idx, ch = _capture_usecase(name, mirror_chapman=True)
        with force_coo_capture():
            coo_idx, _ = _capture_usecase(name)
        tens_mb = idx.prov_nbytes() / 1e6
        coo_mb = coo_idx.prov_nbytes() / 1e6
        chap_mb = ch.total_nbytes() / 1e6
        rows.append((name, tens_mb, coo_mb, chap_mb,
                     chap_mb / tens_mb, chap_mb / coo_mb, coo_mb / tens_mb))
    print("\n== Table IX: provenance memory (MB) ==")
    print(f"{'usecase':10s} {'TensProv':>10s} {'Tens-COO':>10s} {'Chapman':>10s} "
          f"{'ratio':>8s} {'ratioCOO':>8s} {'improve':>8s}")
    for name, t, c, ch, r, rc, imp in rows:
        print(f"{name:10s} {t:10.3f} {c:10.3f} {ch:10.2f} "
              f"{r:7.1f}x {rc:7.1f}x {imp:7.1f}x")
    return {"table": "IX", "rows": [
        {"usecase": n, "tensprov_mb": t, "tensprov_coo_mb": c,
         "chapman_mb": ch, "ratio": r, "ratio_coo": rc,
         "improvement_vs_coo": imp}
        for n, t, c, ch, r, rc, imp in rows]}


if __name__ == "__main__":
    import os

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="accepted for harness uniformity (already cheap)")
    run(quick=ap.parse_args().quick)
