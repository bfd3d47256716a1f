"""Op step: median device time of one delete ``apply_ops_step`` execution in
the traced window (executions matched to the dispatch log in order)."""
import numpy as np

from harness import xplane


def read(r: dict):
    tr = r.get("trace")
    if tr is None:
        return None
    d = xplane.op_steps(tr, r["dispatched"]).get("delete")
    return float(np.median(d)) * 1e3 if d else None
