"""Megabytes (1e6 B) per fused walk put on the device, packed mask and
planes: session counter ``walk_put_bytes`` over ``fused_walk``.  Fixed by
the shapes of the chain and the batch."""


def read(record):
    c = record["counters"].get("session", {})
    if "walk_put_bytes" not in c or c.get("fused_walk", 0) <= 0:
        return None
    return c["walk_put_bytes"] / c["fused_walk"] / 1e6
