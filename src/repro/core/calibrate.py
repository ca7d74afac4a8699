"""Empirical calibration of the cost model (ROADMAP item 4).

The routing constants in :mod:`repro.core.costmodel` were guessed once
against the CPU container; this module MEASURES them on the actual backend:

* a microbench grid over density × shape times the three composition
  primitives the router prices — the packed-bitplane compose
  (:func:`repro.kernels.ops.bitmatmul`, through its own kernel-launch
  guard, so TPU measures the Pallas kernel and hosts measure the oracle),
  scipy CSR spmm, and the fused batched walk;
* medians per grid point feed linear least-squares fits
  ``time = overhead + slope × work`` giving ``c_word_op`` /
  ``c_spmm_flop`` / ``c_spmm_overhead`` / ``c_launch_overhead``, and the
  CSR-vs-bitplane crossover ``density_threshold =
  sqrt(c_word_op / (32 · c_spmm_flop))`` (the same identity the default
  0.06 was derived from);
* the fitted :class:`~repro.core.costmodel.Constants` persist to a JSON
  calibration file keyed by device kind, which
  :func:`repro.core.costmodel.maybe_load_calibration` installs on the
  first :class:`CostModel` of any later process — ``CostModel``,
  ``ComposedIndex(backend="auto")`` and ``QuerySession._strategy`` then
  run on measured numbers, and ``explain()`` reports their provenance.

Run directly::

    PYTHONPATH=src python -m repro.core.calibrate [--full] [--path FILE]
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

from repro.core import costmodel
from repro.core.costmodel import Constants

__all__ = [
    "default_path",
    "device_kind",
    "run_microbench",
    "fit_constants",
    "save_constants",
    "load_constants",
    "calibrate",
]

_FILE_VERSION = 1


def default_path() -> str:
    """``$REPRO_CALIBRATION`` or ``~/.cache/repro/calibration.json`` — the
    same resolution :func:`costmodel.maybe_load_calibration` uses."""
    return os.environ.get("REPRO_CALIBRATION") or os.path.join(
        os.path.expanduser("~"), ".cache", "repro", "calibration.json")


def device_kind() -> str:
    """Device-kind key for the calibration file (e.g. ``TPU-v5-lite`` /
    ``cpu``)."""
    import jax

    return str(jax.devices()[0].device_kind).replace(" ", "-")


# ---------------------------------------------------------------------------
# Microbench harness
# ---------------------------------------------------------------------------
def _median_ns(fn, reps: int, warmup: int = 1) -> float:
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e9


def _random_plane(rng, rows: int, cols: int, density: float) -> np.ndarray:
    import jax.numpy as jnp
    from repro.kernels import ref

    dense = rng.random((rows, cols)) < density
    return np.asarray(ref.pack_bits(jnp.asarray(dense)))


def run_microbench(quick: bool = True, seed: int = 0) -> Dict[str, object]:
    """Time bitmatmul / CSR-spmm / fused-walk over a density × shape grid.

    Every primitive runs through its OWN kernel-launch guard
    (``use_pallas=None``) so the measurement reflects the backend this
    process would actually route to.  Returns raw grid rows (medians, ns)
    plus the device kind — :func:`fit_constants` turns them into a
    :class:`Constants`.
    """
    from repro.kernels import ops as K

    try:
        import scipy.sparse as sp
    except ImportError:  # pragma: no cover - scipy is a baked-in dep here
        sp = None

    rng = np.random.default_rng(seed)
    if quick:
        sizes = [128, 256, 512]
        densities = [0.02, 0.1]
        reps = 3
    else:
        sizes = [128, 256, 512, 1024, 2048]
        densities = [0.005, 0.02, 0.08, 0.25]
        reps = 7
    rows: List[Dict[str, object]] = []

    for n in sizes:
        nw = (n + 31) // 32
        for d in densities:
            a = _random_plane(rng, n, n, d)
            b = _random_plane(rng, n, n, d)
            t = _median_ns(
                lambda: np.asarray(K.bitmatmul(a, b, use_pallas=None)),
                reps=reps)
            rows.append({"kind": "bitmatmul", "n": n, "density": d,
                         "word_ops": n * n * nw, "t_ns": t})
            if sp is not None:
                da = sp.random(n, n, density=d, format="csr",
                               random_state=int(rng.integers(1 << 30)),
                               dtype=np.float32)
                db = sp.random(n, n, density=d, format="csr",
                               random_state=int(rng.integers(1 << 30)),
                               dtype=np.float32)
                out_deg = db.nnz / max(n, 1)
                t = _median_ns(lambda: (da @ db).tocsr(), reps=reps)
                rows.append({"kind": "spmm", "n": n, "density": d,
                             "flops": da.nnz * out_deg, "t_ns": t})

    # fused-walk dispatch: the smallest chain isolates per-launch overhead
    n, hops = 128, 4
    planes = [_random_plane(rng, n, n, 0.05) for _ in range(hops)]
    mask = _random_plane(rng, 8, n, 0.05)
    t = _median_ns(
        lambda: tuple(np.asarray(x) for x in
                      K.batched_walk(mask, planes, use_pallas=None)),
        reps=reps)
    rows.append({"kind": "fused_walk", "n": n, "hops": hops, "t_ns": t})
    return {"device": device_kind(), "rows": rows}


def _line_fit(xs: List[float], ys: List[float]) -> tuple:
    """(slope, intercept) least squares, both clamped non-negative."""
    if len(xs) < 2:
        return 0.0, float(ys[0]) if ys else 0.0
    slope, intercept = np.polyfit(np.asarray(xs, float), np.asarray(ys, float), 1)
    return max(float(slope), 1e-6), max(float(intercept), 0.0)


def fit_constants(meas: Dict[str, object],
                  base: Optional[Constants] = None) -> Constants:
    """Fit routing constants from :func:`run_microbench` output.

    Non-measured constants (walk dispatch, stitch)
    carry over from ``base`` (default: the uncalibrated defaults).
    """
    base = base or Constants()
    rows = meas["rows"]
    bm = [r for r in rows if r["kind"] == "bitmatmul"]
    sm = [r for r in rows if r["kind"] == "spmm"]
    fw = [r for r in rows if r["kind"] == "fused_walk"]

    word_slope, word_icpt = _line_fit([r["word_ops"] for r in bm],
                                      [r["t_ns"] for r in bm])
    updates: Dict[str, object] = {
        "c_word_op": word_slope,
        "source": "calibrated",
        "device": str(meas["device"]),
    }
    launch = [word_icpt] + [float(r["t_ns"]) for r in fw]
    updates["c_launch_overhead"] = max(float(np.median(launch)), 1.0)
    if sm:
        spmm_slope, spmm_icpt = _line_fit([r["flops"] for r in sm],
                                          [r["t_ns"] for r in sm])
        updates["c_spmm_flop"] = spmm_slope
        updates["c_spmm_overhead"] = max(spmm_icpt, 1.0)
        # the CSR/bitplane crossover, from the same identity as the default
        thr = float(np.sqrt(word_slope / (32.0 * spmm_slope)))
        updates["density_threshold"] = float(np.clip(thr, 1e-4, 0.5))
    return dataclasses.replace(base, **updates)


# ---------------------------------------------------------------------------
# Persistence (JSON, keyed by device kind)
# ---------------------------------------------------------------------------
def save_constants(constants: Constants, path: Optional[str] = None) -> str:
    """Merge one device's constants into the calibration file."""
    path = path or default_path()
    data: Dict[str, object] = {"version": _FILE_VERSION, "devices": {}}
    if os.path.exists(path):
        try:
            with open(path) as f:
                old = json.load(f)
            if isinstance(old.get("devices"), dict):
                data["devices"] = old["devices"]
        except (OSError, ValueError):
            pass
    entry = dataclasses.asdict(constants)
    entry.pop("source", None)
    entry.pop("device", None)
    entry.pop("path", None)
    data["devices"][constants.device or device_kind()] = entry
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    return path


def load_constants(path: Optional[str] = None,
                   device: Optional[str] = None) -> Optional[Constants]:
    """Constants for this device kind from the calibration file, or None.

    Only the entry fitted on ``device`` (default: this process's device
    kind) applies: constants measured on another device would route this
    one by the wrong crossover.
    """
    path = path or default_path()
    if not os.path.exists(path):
        return None
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    devices = data.get("devices")
    if not isinstance(devices, dict) or not devices:
        return None
    key = device or device_kind()
    if key not in devices:
        return None
    entry = devices[key]
    fields = {f.name for f in dataclasses.fields(Constants)}
    kwargs = {k: v for k, v in entry.items() if k in fields}
    kwargs.update(source="calibrated", device=key,
                  path=os.path.abspath(path))
    try:
        return Constants(**kwargs)
    except TypeError:
        return None


def calibrate(path: Optional[str] = None, quick: bool = True,
              install: bool = True, seed: int = 0) -> Constants:
    """Measure → fit → persist → (optionally) install, in one call."""
    meas = run_microbench(quick=quick, seed=seed)
    fitted = fit_constants(meas)
    saved = save_constants(fitted, path)
    fitted = dataclasses.replace(fitted, path=os.path.abspath(saved))
    if install:
        costmodel.set_constants(fitted)
    return fitted


if __name__ == "__main__":  # pragma: no cover - CLI convenience
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="full density × shape grid (default: quick)")
    ap.add_argument("--path", default=None,
                    help=f"calibration file (default: {default_path()})")
    args = ap.parse_args()
    c = calibrate(path=args.path, quick=not args.full)
    print(f"calibrated for {c.device!r} -> {c.path}")
    for k, v in sorted(c.provenance().items()):
        print(f"  {k}: {v}")
