"""Share of the traced window in which the chip ran no operation:
1 - (union of device operation intervals / window), from the profiler
trace, averaged over the chips used."""


def read(record):
    tr = record["trace"]
    if tr is None or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
