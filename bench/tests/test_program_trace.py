"""The reduction of the program's own spans (harness/program_trace.py) and
the metrics that read it: on hand-made spans, and on the small traces the
harness recorded on one v5e (bench/tests/record_trace.py)."""
from __future__ import annotations

import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench_tiny import BENCH
from harness import program_trace, registry, xplane
from harness.program_trace import ProgramTrace, Span

DATA = Path(__file__).resolve().parent / "data"


def _metric(name: str):
    return registry.load_module(BENCH / "layer_metrics" / f"{name}.py")


def _line(*spans):
    return program_trace.nest([
        Span(f"ipgm.{n}", float(a), float(b - a), dict(*args))
        for n, a, b, *args in spans])


def _serve() -> ProgramTrace:
    """One serving step from 10 to 90 ns in a 100 ns window; the device
    runs 0–5 and 48–75."""
    line = _line(("serve.step", 10, 90, {"step": 0, "rid0": 0, "n": 3}),
                 ("serve.drain", 10, 40), ("serve.hold", 20, 40),
                 ("serve.pad", 40, 45), ("session.query", 45, 50),
                 ("session.step", 45, 50), ("session.enqueue", 46, 49),
                 ("handle.wait", 50, 80), ("handle.fetch", 80, 85),
                 ("serve.scatter", 85, 90))
    return ProgramTrace((0.0, 100.0), [line], [(0.0, 5.0), (48.0, 75.0)])


def test_spans_nest_by_interval_on_their_line():
    (step,) = _serve().lines[0]
    assert [c.name for c in step.children] == [
        "ipgm.serve.drain", "ipgm.serve.pad", "ipgm.session.query",
        "ipgm.handle.wait", "ipgm.handle.fetch", "ipgm.serve.scatter"]
    assert [c.name for c in step.children[0].children] == ["ipgm.serve.hold"]
    # self time: the step less the hold (inside the drain) and the wait
    assert step.within(program_trace.NOT_HOST) == 50.0
    assert _metric("host_step_ms.serve").read(
        {"program_trace": _serve()}) == pytest.approx(30e-6)


def test_idle_is_named_by_the_innermost_open_span():
    pt = _serve()
    by = pt.idle_by_span(pt.line_of("ipgm.serve.step"))
    step = "ipgm.serve.step"
    assert by == {("outside", "outside"): 15.0,
                  (step, "ipgm.serve.drain"): 10.0,
                  (step, "ipgm.serve.hold"): 20.0,
                  (step, "ipgm.serve.pad"): 5.0,
                  (step, "ipgm.session.step"): 1.0,
                  (step, "ipgm.session.enqueue"): 2.0,
                  (step, "ipgm.handle.wait"): 5.0,
                  (step, "ipgm.handle.fetch"): 5.0,
                  (step, "ipgm.serve.scatter"): 5.0}
    # host work: 28 of 68 idle ns (not the hold, not the device wait, not
    # outside a step), as host_step_ms.serve counts it
    assert _metric("idle_host_share.serve").read(
        {"program_trace": pt}) == pytest.approx(100.0 * 28 / 68)
    pt.busy = None  # no device plane: nothing to attribute
    assert _metric("idle_host_share.serve").read({"program_trace": pt}) is None


def test_churn_splits_op_calls_into_enqueue_and_host_prep():
    """Every device call of an op (the key fold before its steps, then
    each step's transfer and step call) is an enqueue span; the rest of the
    insert and delete calls is the host's own work."""
    line = _line(("session.delete", 0, 50), ("session.enqueue", 0, 1),
                 ("session.step", 1, 25), ("session.enqueue", 5, 25),
                 ("session.step", 25, 50), ("session.enqueue", 26, 50),
                 ("session.insert", 50, 62), ("session.enqueue", 50, 51),
                 ("session.step", 52, 60), ("session.enqueue", 53, 59),
                 ("session.query", 62, 72), ("session.enqueue", 62, 63),
                 ("session.step", 63, 72), ("session.enqueue", 64, 72))
    pt = ProgramTrace((0.0, 100.0), [line], None)
    assert len(program_trace.op_steps(pt)) == 3   # the query is not one
    r = {"program_trace": pt}
    enq = _metric("enqueue_block_ms.churn").read(r)
    prep = _metric("host_prep_ms.churn").read(r)
    assert enq == pytest.approx(52e-6 / 3)
    assert prep == pytest.approx(10e-6 / 3)
    # together: the insert and delete calls' time per op step, the base of
    # dispatch_ms.churn
    assert enq + prep == pytest.approx((50 + 12) * 1e-6 / 3)


def test_hold_ms_reads_the_batcher_counter():
    m = _metric("hold_ms.serve")
    assert m.read({"server_stats": {"batches": 4, "hold_s": 0.01}}) == (
        pytest.approx(2.5))
    # a program whose batcher keeps no hold counter: nothing to report
    assert m.read({"server_stats": {"batches": 4, "requests": 9}}) is None


def _recorded(tag: str, tmp_path) -> tuple[dict, dict]:
    """The run records a metric reads of a recorded chip trace, and what
    the harness reported from it on the chip."""
    dst = tmp_path / "out" / "trace" / "t.xplane.pb"
    dst.parent.mkdir(parents=True)
    with gzip.open(DATA / f"{tag}.xplane.pb.gz", "rb") as f, open(
            dst, "wb") as g:
        shutil.copyfileobj(f, g)
    r = {"trace": xplane.read(str(dst))}
    program_trace.of_run(r, tmp_path)
    return r, json.loads((DATA / f"{tag}.json").read_text())["metrics"]


@pytest.mark.parametrize("tag", ["tiny-l2-serve", "tiny-l2-churn"])
def test_trace_without_program_spans_reads_none(tag, tmp_path):
    r, _ = _recorded(tag, tmp_path)
    assert r["program_trace"] is None   # read once, for every metric
    for name in ("host_step_ms.serve", "idle_host_share.serve",
                 "enqueue_block_ms.churn", "host_prep_ms.churn"):
        assert _metric(name).read(r) is None
    assert program_trace.of_run({}, tmp_path) is None   # untraced run


@pytest.mark.parametrize("tag,names", [
    ("tiny-l2-serve-program", ("host_step_ms.serve", "idle_host_share.serve")),
    ("tiny-l2-churn-program", ("enqueue_block_ms.churn",
                               "host_prep_ms.churn")),
])
def test_recorded_program_trace_reads_every_new_metric(tag, names,
                                                       tmp_path):
    """The program's spans as one v5e recorded them
    (bench/tests/record_trace.py): each span metric reads a number there."""
    r, on_chip = _recorded(tag, tmp_path)
    pt = r["program_trace"]
    assert pt is not None
    got = {name: _metric(name).read(r) for name in names}
    for name, value in got.items():
        assert value is not None and value > 0, name
    if "serve" in tag:
        for name, value in got.items():   # what the harness reported there
            assert value == pytest.approx(on_chip[name]), name
        assert on_chip["hold_ms.serve"] > 0
        # every step of the slice sits inside the benchmark's serve_step
        # span, on one clock
        outer = [e for e in r["trace"].spans if e.name == "bench.serve_step"]
        for s in pt.spans("ipgm.serve.step"):
            assert any(e.start_ns <= s.start_ns and s.end_ns <= e.end_ns
                       for e in outer)
    else:
        # together: the insert and delete calls' time per op step
        calls = program_trace.op_calls(pt)
        per_step = sum(c.dur_ns for c in calls) * 1e-6 / len(
            program_trace.op_steps(pt))
        assert sum(got.values()) == pytest.approx(per_step)


@pytest.mark.parametrize("workload,names", [
    ("tiny-l2.serve", {"hold_ms.serve", "host_step_ms.serve"}),
    ("tiny-l2.churn", {"enqueue_block_ms.churn", "host_prep_ms.churn"}),
])
def test_traced_run_reads_the_program_spans(tiny_root, workload, names):
    """A whole traced run through the real program (CPU): the metrics that
    need only host spans are reported; the idle share needs a device plane,
    which a CPU trace lacks, and is left out."""
    import time

    from harness import cell

    res = cell.run(tiny_root, workload, 2**31 + 29, 1.0, True,
                   t_start=time.perf_counter(), check_device=False,
                   report=lambda *a, **k: None)
    assert res["correct"], res["checks"]
    for name in names:
        assert res["metrics"][name]["value"] > 0, name
    assert "idle_host_share.serve" not in res["metrics"]
