"""95th percentile of how late the open-loop generator sent its requests
(send time less due time, host clock).  Lateness is inside each request's
latency, since a request is timed from its due time; a generator held up
by the host (the same process's tier threads share its interpreter) shows
here before it shows as a slower server."""
import numpy as np


def read(record):
    late = record["late_ms"]
    return float(np.percentile(late, 95)) if len(late) else None
