"""Device milliseconds of the fused ``batched_walk`` kernel per plan that
the fused walk answered in the traced window (summed durations of its
trace events over the session's ``fused_walk`` count)."""
from bench import tracing


def read(record):
    tr = record["trace"]
    plans = record["counters"]["session"].get("fused_walk", 0)
    if tr is None or plans <= 0:
        return None
    seconds, names = tracing.kernel_seconds(tr, "batched_walk")
    if names == 0:
        return None
    return 1e3 * seconds / plans
