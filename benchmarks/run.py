"""Benchmark harness entry point: one benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Order: memory (Table IX), capture (Fig 3 / Table X), query (Fig 4/5),
join_scale (Table XI / Fig 6), roofline (assignment deliverable g — reads
the dry-run report if present).
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks import bench_memory, bench_capture, bench_query, bench_join_scale
from benchmarks import roofline

BENCHES = {
    "memory": bench_memory.run,
    "capture": bench_capture.run,
    "query": bench_query.run,
    "join_scale": bench_join_scale.run,
    "roofline": roofline.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced scale factors / fewer reps")
    ap.add_argument("--only", choices=list(BENCHES), default=None)
    ap.add_argument("--out", default="reports/bench_results.json")
    args = ap.parse_args()

    names = [args.only] if args.only else list(BENCHES)
    results = {}
    for name in names:
        print(f"\n######## bench: {name} ########")
        t0 = time.time()
        results[name] = BENCHES[name](quick=args.quick)
        print(f"[{name}] done in {time.time() - t0:.1f}s")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1, default=float)
    print(f"\nwrote {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
