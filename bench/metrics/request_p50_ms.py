"""Median request latency over every request of the window (host clock)."""
import numpy as np


def read(record):
    lat = record["latencies_ms"]
    return float(np.percentile(lat, 50)) if len(lat) else None
