"""Share of the window's plans that the session answered on the device
from a composed row map (the fused walk's gather route):
``counters["gather_walk"] / counters["plans"]``.  None on a program
without the route."""


def read(record):
    c = record["counters"]["session"]
    if "gather_walk" not in c or c.get("plans", 0) <= 0:
        return None
    return 100.0 * c["gather_walk"] / c["plans"]
