"""Requests answered inside the window, over the window's seconds."""
import numpy as np


def read(r: dict):
    if "due" not in r:
        return None
    end = r["t0"] + r["window_s"]
    return float(np.sum(r["answered"] & (r["done_t"] <= end))) / r["window_s"]
