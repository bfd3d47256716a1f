"""Mean recall@10 of the window's queries against the exact top-10 over the
live set at each query's point (harness/check.py)."""
import numpy as np


def read(r: dict):
    rec = r.get("recall")
    return float(np.mean(rec)) if rec is not None and rec.size else None
