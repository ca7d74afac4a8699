"""Share of the window's plans that the session answered on the device
through the fused walk (``counters["fused_walk"] / counters["plans"]``)."""


def read(record):
    c = record["counters"]["session"]
    if c.get("plans", 0) <= 0:
        return None
    return 100.0 * c.get("fused_walk", 0) / c["plans"]
