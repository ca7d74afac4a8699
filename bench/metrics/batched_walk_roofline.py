"""The fused ``batched_walk`` kernel's share of its roofline: the least
time the plans it answered need on this chip (``bench/work.py``: bytes
over HBM bandwidth or operations over the peak operation rate, whichever
binds) over the kernel's device time in the trace."""
from bench import tracing


def read(record):
    tr = record["trace"]
    plans = record["counters"]["session"].get("fused_walk", 0)
    if tr is None or plans <= 0:
        return None
    seconds, names = tracing.kernel_seconds(tr, "batched_walk")
    if names == 0 or seconds <= 0:
        return None
    per_dir = [w.least_seconds(record["peaks"])[0]
               for w in record["work"].values()]
    least = plans * sum(per_dir) / len(per_dir)
    return 100.0 * least / seconds
