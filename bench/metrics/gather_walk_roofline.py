"""The ``gather_walk`` kernel's share of its roofline: the least time the
plans it answered need on this chip over the kernel's device time in the
trace.

A plan on this route names its probes by row id and gets its answer back
as row ids, so its least work is the int32s it cannot do without: each
probe row in, each answer row out, and the walked row map's links, read
once (one int32 per link, the smallest form of the relation, as
``bench/work.py`` reads each hop whole).  It is read from the session's
``gather_walk_rows``, ``gather_walk_hits`` and ``gather_walk_links``, over
HBM bandwidth.  None on a program without those counters."""
from bench import tracing


def read(record):
    tr = record["trace"]
    session = record["counters"]["session"]
    ints = sum(session.get(k, 0) for k in (
        "gather_walk_rows", "gather_walk_hits", "gather_walk_links"))
    if tr is None or session.get("gather_walk", 0) <= 0 or ints <= 0:
        return None
    seconds, names = tracing.kernel_seconds(tr, "gather_walk")
    if names == 0 or seconds <= 0:
        return None
    least = 4 * ints / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
