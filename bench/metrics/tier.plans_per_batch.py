"""Plans per fused batch the serving tier flushed in the window
(``counters["batched_plans"] / counters["batches"]``)."""


def read(record):
    c = record["counters"].get("tier")
    if not c or c.get("batches", 0) <= 0:
        return None
    return c["batched_plans"] / c["batches"]
