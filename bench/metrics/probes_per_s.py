"""Probe sets answered over the whole window, divided by its length (host
clock): from the first request's due time to the last answer."""


def read(record):
    if record["window_s"] <= 0:
        return None
    return record["probes_answered"] / record["window_s"]
