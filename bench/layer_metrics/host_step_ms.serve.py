"""Batcher: median over the traced slice's serving steps of the host time
the device waits on: ``ipgm.serve.step`` less the spans in which the
serving thread waits, ``ipgm.serve.hold`` and ``ipgm.handle.wait``
(harness/program_trace.py)."""
from pathlib import Path

import numpy as np

from harness import program_trace

BENCH = Path(__file__).resolve().parents[1]


def read(r: dict):
    pt = program_trace.of_run(r, BENCH)
    if pt is None:
        return None
    host = [s.dur_ns - s.within(program_trace.NOT_HOST)
            for s in pt.spans("ipgm.serve.step")
            if "n" in s.args]
    return float(np.median(host)) * 1e-6 if host else None
