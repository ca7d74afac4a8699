"""One closed-loop client calling ``QuerySession.run`` back to back.

Traffic keys: ``probes_per_request`` probe sets of ``rows_per_probe``
rows per request, ``directions`` in turn, ``warmup_requests`` of each
direction before the window.  A request is due when the client is ready
to send it (its plan is built inside its time).
"""
import concurrent.futures
import time

from bench import loadgen


def warm(run) -> None:
    """The one batch size this loop sends, both ways, then a few requests."""
    run.warm_sizes([run.traffic["probes_per_request"]])
    for i in range(run.traffic["warmup_requests"] * len(run.traffic["directions"])):
        run.session.run(run.request(i)[2])


def drive(run, seconds: float, span):
    out = loadgen.Served()
    out.t_start = time.perf_counter()
    deadline = out.t_start + seconds
    i = 0
    with span("bench.clients"):
        while time.perf_counter() < deadline:
            t = time.perf_counter()
            with span("bench.request"):
                d, rows, plan = run.request(i)
                k = out.add(t, d, rows)
                out.sent[k] = t
                fut = concurrent.futures.Future()
                try:
                    fut.set_result(run.session.run(plan))
                except Exception as exc:    # noqa: BLE001 - a failed request
                    fut.set_exception(exc)
                out.settle(k, fut)
            i += 1
    out.t_end = time.perf_counter()
    return out
