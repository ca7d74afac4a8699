#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell's configuration, traffic and
metrics are found by the names ``BENCHMARK.json`` gives them (see
``bench/spec.py``).  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
comparison with the reference made, beside its limit).  The line before
it holds the run's diagnostics: the cost model's constants and where they
came from, every session, tier, hop-cache and kernel-launch counter over
the window, and the compilations inside the window.  The checks are also
the last lines of standard error.

Exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the chip's kind has no peaks in
``bench/peaks.json``.
"""
import time

T_PROCESS = time.time()     # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "src"))

# routing constants: a path the benchmark owns (no file there: the cost
# model's defaults), never a file in the user's home
CALIBRATION = os.path.join(BENCH, "calibration.json")


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept,
    however quickly it compiled.  The same setting as the program's
    ``repro.launch.compile_cache``, kept here so that a change to the
    program cannot move the benchmark's cache."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def check_lines(checks) -> list:
    return [f"check {name}: {value} (limit {limit})"
            for name, (value, limit) in checks.items()]


def result_line(cell, record, metrics, devices, trace: bool) -> dict:
    checks = record["checks"]
    correct = all(value <= limit for value, limit in checks.values())
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": record["memory_peak_bytes"],
    }
    out = {"correct": correct, "attempted": record["requests"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = record["trace"]["busy_s"]
        device["window_s"] = record["trace"]["window_s"]
        out["breakdown"] = {"device_ops": record["trace"]["device_ops"],
                            "idle_gaps": record["trace"]["idle_gaps"]}
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, (value, limit) in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.resolve(args.workload)
    os.environ["REPRO_CALIBRATION"] = CALIBRATION
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:cell.chips]
    peaks = spec.peaks(devices[0].device_kind)
    cache = enable_compile_cache()

    record = harness.measure(cell, args.seed, args.seconds, bool(args.trace),
                             T_PROCESS, devices, peaks)
    metrics = harness.metrics(cell, record, bool(args.trace))
    from repro.core.costmodel import constants_provenance

    print(json.dumps({"diagnostics": {
        "cell": cell.name, "seed": args.seed,
        "constants": constants_provenance(),
        "calibration_path": CALIBRATION, "compile_cache": cache,
        "counters": record["counters"],
        "compiles_in_window": record["compiles_in_window"],
        "window_s": record["window_s"], "requests": record["requests"],
        "late_ms_max": float(record["late_ms"].max())
        if len(record["late_ms"]) else None,
    }}, default=str), flush=True)
    print(json.dumps(result_line(cell, record, metrics, devices,
                                 bool(args.trace))), flush=True)
    for line in check_lines(record["checks"]):
        print(line, file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
