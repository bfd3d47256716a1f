"""95th percentile of request latency, from due time to the answer on the
host, over every request due in the window. A request never answered
counts at the longest wait the run allowed, so it always lands in the
tail."""
import numpy as np


def read(r: dict):
    if "due" not in r:
        return None
    lat = np.where(r["answered"], r["done_t"] - r["due"], np.inf)
    lat = np.minimum(lat, r["window_s"] + 60.0)
    return float(np.percentile(lat, 95)) * 1e3
