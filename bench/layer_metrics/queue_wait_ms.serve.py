"""Batcher: 95th percentile of the wait from a request's due time to the
dispatch of the op step that serves it, over the requests due in the
traced slice."""
import numpy as np


def read(r: dict):
    if "dispatch_of_request" not in r:
        return None
    w = (r["dispatch_of_request"] - r["due"])[r["answered"]
                                              & r["layer_window"]]
    return float(np.percentile(w, 95)) * 1e3 if w.size else None
