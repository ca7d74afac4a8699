"""Megabytes (1e6 B) put on the device per row-map walk: the probes' row
ids, and on a store miss the row map's operand; session counter
``walk_put_bytes`` over ``gather_walk``.  Read in cells whose every fused
walk takes the route."""


def read(record):
    c = record["counters"].get("session", {})
    if "walk_put_bytes" not in c or c.get("gather_walk", 0) <= 0:
        return None
    return c["walk_put_bytes"] / c["gather_walk"] / 1e6
