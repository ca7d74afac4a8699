"""Device milliseconds of the ``gather_walk`` kernel per plan that the
fused walk answered from a composed row map in the traced window (summed
durations of its trace events over the session's ``gather_walk`` count).
None on a program without the route."""
from bench import tracing


def read(record):
    tr = record["trace"]
    plans = record["counters"]["session"].get("gather_walk", 0)
    if tr is None or plans <= 0:
        return None
    seconds, names = tracing.kernel_seconds(tr, "gather_walk")
    if names == 0:
        return None
    return 1e3 * seconds / plans
