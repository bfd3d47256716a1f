"""Batcher: share of the device's idle time in the traced window during
which the serving thread was doing host work inside a step: its innermost
open span lies under an ``ipgm.serve.step`` and is none of the spans in
which the thread waits, ``ipgm.serve.hold`` and ``ipgm.handle.wait``
(harness/program_trace.py)."""
from pathlib import Path

from harness import program_trace

BENCH = Path(__file__).resolve().parents[1]
STEP = "ipgm.serve.step"


def read(r: dict):
    pt = program_trace.of_run(r, BENCH)
    if pt is None or pt.busy is None:
        return None
    line = pt.line_of(STEP)
    idle = pt.idle_ns()
    if line is None or idle <= 0:
        return None
    host = sum(ns for (root, inner), ns in pt.idle_by_span(line).items()
               if root == STEP and inner not in program_trace.NOT_HOST)
    return 100.0 * host / idle
