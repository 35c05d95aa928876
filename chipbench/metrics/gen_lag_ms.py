"""99th percentile of how late the load generator injected each packet
(inject time minus due time), in milliseconds, over the window's packets.
A starved generator reads high here, so it is not mistaken for a fast
system.  Only an open loop has due times."""
import numpy as np


def read(r):
    lag = r.window.gen_lag_ms
    if lag is None or len(lag) == 0:
        return None
    return float(np.quantile(lag, 0.99))
