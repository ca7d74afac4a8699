"""Milliseconds per fused walk that the host waits for the answer: the
program's ``walk.wait`` span around the read of the kernel's output, so
device time not yet overlapped plus the copy back; session counter
``walk_wait_ns`` over ``fused_walk``."""


def read(record):
    c = record["counters"].get("session", {})
    if "walk_wait_ns" not in c or c.get("fused_walk", 0) <= 0:
        return None
    return c["walk_wait_ns"] / c["fused_walk"] / 1e6
