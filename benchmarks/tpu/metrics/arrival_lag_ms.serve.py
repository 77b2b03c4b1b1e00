"""95th percentile of how late the load generator sent a request after
its scheduled time (a starved generator must not read as a fast
server)."""
import numpy as np


def read(ctx):
    d = ctx["driver"]
    if not hasattr(d, "arrival_lag_ms"):
        return None
    lag = d.arrival_lag_ms()
    lag = lag[np.isfinite(lag)]
    if not len(lag):
        return None
    return float(np.percentile(lag, 95))
