"""Milliseconds per row-map walk outside its copy and its wait: the
``walk.gather`` span's self time (row ids from the probe masks, launch,
answer masks from the returned rows); session counters ``gather_walk_ns -
walk_put_ns - walk_wait_ns`` over ``gather_walk``.  Read in cells whose
every fused walk takes the route, so the put and wait are its own."""

KEYS = ("gather_walk_ns", "walk_put_ns", "walk_wait_ns")


def read(record):
    c = record["counters"].get("session", {})
    if any(k not in c for k in KEYS) or c.get("gather_walk", 0) <= 0:
        return None
    own = c["gather_walk_ns"] - c["walk_put_ns"] - c["walk_wait_ns"]
    return own / c["gather_walk"] / 1e6
