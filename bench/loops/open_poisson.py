"""Open-loop arrivals through the serving tier, each request timed from
the moment it was due.

Traffic keys: ``rate_per_s`` requests offered per second, arriving in
bursts of ``burst`` (1: Poisson arrivals; more: Poisson burst starts, each
burst submitted at once); ``tenants`` in turn; ``probes_per_request``
probe sets of ``rows_per_probe`` rows, ``directions`` in turn;
``warmup_requests`` of each direction through the tier before the window.

Every seed offers the same arrival gaps, in its own order: the gaps are
drawn once from a fixed stream and scaled to fill the window exactly, so
the work is the same from seed to seed.  The tier is the configuration's
``ServingTier`` over its session; requests are submitted with
backpressure (``wait=True``), so none is refused: a full front door shows
as latency.  Any fused batch the tier can form holds at most
``max_queue`` plans, and set-up runs every such size in both directions,
so nothing compiles in the window.  The generator's lateness (send time
less due time) is recorded per request.
"""
import threading
import time

import numpy as np

from bench import loadgen

GAPS_SEED = 0x5EED


def arrivals(rate: float, burst: int, seconds: float, rng) -> np.ndarray:
    """The due times of the window's bursts, in seconds from its start."""
    n = max(1, int(round(rate * seconds / burst)))
    gaps = np.random.default_rng(GAPS_SEED).exponential(1.0, n)
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def warm(run) -> None:
    t = run.traffic
    per = t["probes_per_request"]
    run.warm_sizes(range(per, per * run.config["tier"]["max_queue"] + 1, per))
    tier = run.start_tier()
    for i in range(t["warmup_requests"] * len(t["directions"])):
        tenant = t["tenants"][i % len(t["tenants"])]
        tier.submit_nowait(tenant, run.request(i)[2], wait=True).result(600)


def drive(run, seconds: float, span, grace_s: float = 60.0):
    t = run.traffic
    tier, tenants, burst = run.tier, t["tenants"], int(t["burst"])
    due = arrivals(t["rate_per_s"], burst, seconds, run.rng_loop)
    out = loadgen.Served()
    pending = [0]
    idle = threading.Event()
    lock = threading.Lock()

    def settle(k, fut):
        out.settle(k, fut)
        with lock:
            pending[0] -= 1
            if pending[0] == 0:
                idle.set()

    out.t_start = t0 = time.perf_counter()
    i = 0
    with span("bench.clients"):
        for start in due:
            wait = t0 + start - time.perf_counter()
            if wait > 0:
                with span("bench.wait"):
                    time.sleep(wait)
            with span("bench.submit"):
                for _ in range(burst):
                    d, rows, plan = run.request(i)
                    k = out.add(t0 + start, d, rows)
                    with lock:
                        pending[0] += 1
                        idle.clear()
                    out.sent[k] = time.perf_counter()
                    fut = tier.submit_nowait(tenants[i % len(tenants)], plan,
                                             wait=True)
                    fut.add_done_callback(lambda f, k=k: settle(k, f))
                    i += 1
        with span("bench.drain"):
            idle.wait(max(0.0, t0 + seconds + grace_s - time.perf_counter()))
    done = np.asarray(out.done, dtype=float)
    if not idle.is_set():
        out.t_end = time.perf_counter()
    else:
        last = np.nanmax(done) if (~np.isnan(done)).any() else t0
        out.t_end = max(last, t0 + seconds)
    return out
