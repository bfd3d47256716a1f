"""Host dispatch: time the traced step's insert and delete calls spent in
their device calls (``ipgm.session.enqueue``: the key fold, each batch's
transfer and the op-step call, where the host blocks until the previous
step releases the donated state), per op step: the base of
``dispatch_ms.churn`` (harness/program_trace.py)."""
from pathlib import Path

from harness import program_trace

BENCH = Path(__file__).resolve().parents[1]


def read(r: dict):
    pt = program_trace.of_run(r, BENCH)
    steps = program_trace.op_steps(pt) if pt is not None else []
    if not steps:
        return None
    ns = sum(c.within(("ipgm.session.enqueue",))
             for c in program_trace.op_calls(pt))
    return ns * 1e-6 / len(steps)
