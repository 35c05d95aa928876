"""90th percentile, over every packet due in the window, of the time from
its due time to its result being ready, in microseconds.  Only an open
loop has due times; a closed loop has nothing to read."""
import numpy as np


def read(r):
    lat = r.window.latency_us
    if lat is None or len(lat) == 0:
        return None
    return float(np.quantile(lat, 0.90))
