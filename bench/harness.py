"""One measured run of one cell, from the seed to the result line.

Set-up builds the deployment from the seed, opens the session the
configuration states, and lets the traffic's loop kind
(``bench/loops/<loop>.py``) warm every shape it will use and start the
serving tier where it drives one.  The loop then drives the timed entry
point for ``seconds``.  Once the window has closed, the device's peak
memory is read, the program's state is dropped, and every answer served
is compared with the plan kind's reference (``bench/plans/<plan>.py``).
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import time
from typing import Callable, Dict, Iterable, Optional

import numpy as np

from bench import deploy, loadgen, spec, tracing, work

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts JAX lowerings and backend compiles while armed."""

    def __init__(self) -> None:
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def _span(enabled: bool) -> Callable:
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def _numeric(d: Dict) -> Dict:
    return {k: v for k, v in d.items() if isinstance(v, (int, float))}


def _delta(after: Dict, before: Dict) -> Dict:
    return {k: after[k] - before.get(k, 0) for k in after}


class Run:
    """The state of one run: deployment, session, tier, seeded streams."""

    def __init__(self, cell: spec.Cell, seed: int) -> None:
        self.cell = cell
        self.config, self.traffic = cell.config, cell.traffic
        self.plans = cell.plans
        data, probes, loop = np.random.SeedSequence(seed).spawn(3)
        self.rng_probe = np.random.default_rng(probes)
        self.rng_loop = np.random.default_rng(loop)
        self.dep = deploy.capture(cell.config, data, cell.config_name, cell.root)
        self.session = deploy.session(self.dep, cell.config.get("session", {}))
        self.tier = None

    def start_tier(self):
        """The configuration's serving tier over the session, started."""
        from repro.serve import ServingTier

        self.tier = ServingTier(self.session, **self.config["tier"]).start()
        return self.tier

    def _rows(self, direction: str, count: int) -> np.ndarray:
        return loadgen.probe_rows(
            self.rng_probe, self.plans.probe_side(self.dep, direction), count,
            self.traffic["rows_per_probe"])

    def request(self, i: int):
        """Request ``i``: its direction, probe rows and plan."""
        t = self.traffic
        d = t["directions"][i % len(t["directions"])]
        rows = self._rows(d, t["probes_per_request"])
        if t["probes_per_request"] == 1:
            rows = rows[0]
        return d, rows, self.plans.plan(self.dep, d, rows)

    def warm_sizes(self, sizes: Iterable[int]) -> None:
        """Run one plan of each of ``sizes`` probe sets in each direction
        on the session: every batch shape the window can send it."""
        for b in sizes:
            for d in self.traffic["directions"]:
                self.session.run(self.plans.plan(self.dep, d, self._rows(d, b)))

    def counters(self) -> Dict[str, Dict]:
        from repro.kernels import ops as K

        out = {
            "session": dict(self.session.counters),
            "hopcache": _numeric(self.session.composed.stats()),
            "launches": K.launch_counts(),
            "pallas_launches": K.pallas_launch_counts(),
        }
        if self.tier is not None:
            out["tier"] = dict(self.tier.counters)
        return out

    def close(self) -> None:
        if self.tier is not None:
            self.tier.shutdown()


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def measure(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            t_process: float, devices, peaks: Optional[Dict]) -> Dict:
    """One run.  Returns the record the metric readers read, with the
    comparison's numbers under ``checks``."""
    import jax

    compiles = CompileCounter()
    run = Run(cell, seed)
    cell.loop.warm(run)
    span = _span(trace)
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        # no Python function tracing: on the chip it slowed the host so far
        # that an open loop at its fixed rate fell behind; the bench.* spans
        # are host trace events and stay
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    before = run.counters()
    compiles.armed = True
    t_setup = time.time() - t_process
    try:
        with span("bench.window"):
            served = cell.loop.drive(run, seconds, span)
    finally:
        compiles.armed = False
        if trace:
            jax.profiler.stop_trace()
    after = run.counters()
    counters = {k: _delta(_numeric(after[k]), _numeric(before.get(k, {})))
                for k in after}
    answered = served.answered()
    per = cell.traffic["probes_per_request"]
    record: Dict = {
        "cell": cell.name,
        "setup_s": t_setup,
        "window_s": served.t_end - served.t_start,
        "latencies_ms": served.latencies_ms(),
        "late_ms": served.late_ms(),
        "requests": len(served.due),
        "probes_answered": int(answered.sum()) * per,
        "counters": counters,
        "compiles_in_window": compiles.count,
        "memory_peak_bytes": _memory_peak(devices),
        "peaks": peaks,
        "trace": None,
    }
    n_src, steps = run.dep.n_source, run.dep.row_steps
    record["work"] = {
        d: work.plan_work(per, work.lineage_hops(n_src, steps, d))
        for d in cell.traffic["directions"]}
    plans = cell.plans
    ref = plans.reference(run.dep)
    run.close()
    del run
    gc.collect()
    if trace:
        try:
            chips, spans = tracing.extract(tracing.load(trace_dir))
            record["trace"] = tracing.reduce(chips, spans)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    record["checks"] = {
        "wrong_answers": (plans.wrong(ref, served.compared()), 0),
        "failed": (served.failed(), 0),
    }
    record["failed"] = served.failed()
    return record


def metrics(cell: spec.Cell, record: Dict, trace: bool) -> Dict:
    """The cell's metrics the run can report: its end-to-end metrics
    (``trace`` off) or its per-layer ones (``trace`` on).  A reader that
    finds nothing leaves its metric out."""
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"], cell.root)(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
