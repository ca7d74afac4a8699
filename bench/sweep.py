#!/usr/bin/env python3
"""Find the highest rate an open-loop cell sustains, by a sweep on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

One process, one set-up: the cell's deployment, session and tier are
built and warmed once, then its loop offers each rate in turn for
``--seconds``.  Per rate, one JSON line: the rate offered and completed,
the latency quartiles from the due time, the generator's lateness, and the
median latency of the window's first and last quarters (a backlog that
grows shows as a last quarter far above the first).  The rate a cell
offers is fixed in its traffic file from such a sweep; the benchmark's own
runs never sweep.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import contextlib

    import numpy as np

    from bench import harness, spec
    from bench import run as bench_run

    bench_run.enable_compile_cache()
    os.environ["REPRO_CALIBRATION"] = bench_run.CALIBRATION
    cell = spec.resolve(args.workload)
    run = harness.Run(cell, args.seed)
    cell.loop.warm(run)
    span = lambda name: contextlib.nullcontext()   # noqa: E731
    try:
        for rate in args.rates:
            run.traffic["rate_per_s"] = rate
            served = cell.loop.drive(run, args.seconds, span)
            lat = served.latencies_ms()
            due = np.asarray(served.due) - served.t_start
            q = max(1, len(lat) // 4)
            order = np.argsort(due)
            late = served.late_ms()
            print(json.dumps({
                "rate_offered": rate,
                "rate_completed": float(served.answered().sum()
                                        / (served.t_end - served.t_start)),
                "failed": served.failed(),
                "p50_ms": float(np.percentile(lat, 50)),
                "p95_ms": float(np.percentile(lat, 95)),
                "first_quarter_p50_ms": float(np.median(lat[order[:q]])),
                "last_quarter_p50_ms": float(np.median(lat[order[-q:]])),
                "late_p95_ms": float(np.percentile(late, 95)) if len(late) else None,
                "tier": dict(run.tier.counters) if run.tier else None,
            }), flush=True)
    finally:
        run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
