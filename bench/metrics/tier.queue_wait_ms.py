"""Mean milliseconds a plan waits in the serving tier from admission to
the start of its executor pass (bucket, flush timer, ready queue, hop to
the pool): tier counter ``queue_wait_ns`` over ``pass_plans``."""


def read(record):
    c = record["counters"].get("tier", {})
    if "queue_wait_ns" not in c or c.get("pass_plans", 0) <= 0:
        return None
    return c["queue_wait_ns"] / c["pass_plans"] / 1e6
