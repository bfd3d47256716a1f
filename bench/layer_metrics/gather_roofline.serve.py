"""Kernels: share of the roofline the gather_scores calls reach in the
traced window (harness/kernels.py: bytes from each call's shapes at the
chip's HBM peak, over the calls' device time)."""
from harness import kernels


def read(r: dict):
    tr = r.get("trace")
    if tr is None or not tr.devices:
        return None
    return kernels.roofline_share(tr.ops(), r["peaks"])
