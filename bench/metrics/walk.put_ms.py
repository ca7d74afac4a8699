"""Milliseconds per fused walk of the host side of the copy of its packed
mask and planes to the device: the program's ``walk.put`` span, session
counter ``walk_put_ns`` over ``fused_walk``."""


def read(record):
    c = record["counters"].get("session", {})
    if "walk_put_ns" not in c or c.get("fused_walk", 0) <= 0:
        return None
    return c["walk_put_ns"] / c["fused_walk"] / 1e6
