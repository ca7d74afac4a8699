"""Milliseconds per fused walk outside its copy and its wait: the
``walk.fused`` span's self time (chain audit, mask stack and pack, kernel
launch, unpack); session counters ``fused_walk_ns - walk_put_ns -
walk_wait_ns`` over ``fused_walk``."""

KEYS = ("fused_walk_ns", "walk_put_ns", "walk_wait_ns")


def read(record):
    c = record["counters"].get("session", {})
    if any(k not in c for k in KEYS) or c.get("fused_walk", 0) <= 0:
        return None
    own = c["fused_walk_ns"] - c["walk_put_ns"] - c["walk_wait_ns"]
    return own / c["fused_walk"] / 1e6
