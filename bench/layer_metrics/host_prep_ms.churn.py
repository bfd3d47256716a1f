"""Host dispatch: the host's own work in the traced step's insert and
delete calls, outside their device calls (``ipgm.session.insert|delete``
less their ``ipgm.session.enqueue``: slicing out each batch, the call's
own bookkeeping), per op step: the base of ``dispatch_ms.churn``
(harness/program_trace.py)."""
from pathlib import Path

from harness import program_trace

BENCH = Path(__file__).resolve().parents[1]


def read(r: dict):
    pt = program_trace.of_run(r, BENCH)
    steps = program_trace.op_steps(pt) if pt is not None else []
    if not steps:
        return None
    ns = sum(c.dur_ns - c.within(("ipgm.session.enqueue",))
             for c in program_trace.op_calls(pt))
    return ns * 1e-6 / len(steps)
