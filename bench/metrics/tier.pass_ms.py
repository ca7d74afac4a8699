"""Mean milliseconds of one executor pass of the serving tier (one
``backend.run_many`` over every plan ready): the program's ``tier.pass``
span, tier counter ``pass_ns`` over ``passes``."""


def read(record):
    c = record["counters"].get("tier", {})
    if "pass_ns" not in c or c.get("passes", 0) <= 0:
        return None
    return c["pass_ns"] / c["passes"] / 1e6
