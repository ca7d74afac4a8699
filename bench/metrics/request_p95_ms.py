"""95th percentile of request latency over every request of the window
(host clock).  Closed loop: from the call to its answer.  Open loop: from
the time the request was due."""
import numpy as np


def read(record):
    lat = record["latencies_ms"]
    return float(np.percentile(lat, 95)) if len(lat) else None
