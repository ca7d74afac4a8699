#!/usr/bin/env python3
"""Run a cell with its plan kind's control in the program's place.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

The control (``control_session`` of ``bench/plans/<plan>.py``) answers
every plan as a lossy index would, through the same harness, traffic and
comparison as ``bench/run.py``.  The comparison has to call it not
correct; each seed's result line gives the numbers compared beside their
limits.  The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH))

from bench import deploy, spec  # noqa: E402


def use_control(cell: spec.Cell) -> None:
    """Every session the harness opens from now on serves ``cell``'s
    control."""
    real = deploy.session
    deploy.session = lambda dep, opts: cell.plans.control_session(
        real(dep, opts), dep)


def main(argv=None) -> int:
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    use_control(spec.resolve(args.workload))
    for seed in args.seeds:
        rc = bench_run.main(["--workload", args.workload, "--seed", str(seed),
                             "--seconds", str(args.seconds), "--trace", "0"])
        if rc:
            return rc
        print(json.dumps({"control_seed": seed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
