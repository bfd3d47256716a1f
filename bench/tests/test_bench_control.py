"""The control: the plain reference in the program's place (CPU, tiny).

At full precision it must pass every check (the check does not fail a
sound answer); one rung of precision below the configuration's, or with
its answers altered, it must fail one.
"""
from __future__ import annotations

import time

import pytest

from harness import cell, control


def _run(root, workload, precision, altered=False):
    def make(ctx):
        ix = ctx.config["index"]
        return control.ReferenceSession(ix["capacity"], ix["dim"],
                                        ix["metric"], precision=precision,
                                        altered=altered)
    return cell.run(root, workload, 2**31 + 3, 0.5, False,
                    t_start=time.perf_counter(), check_device=False,
                    make_session=make, report=lambda *a, **k: None)


@pytest.mark.parametrize("workload", ["tiny-cos.serve", "tiny-l2.churn"])
def test_reference_in_program_place_is_correct(tiny_root, workload):
    res = _run(tiny_root, workload, "highest")
    assert res["correct"], res["checks"]
    assert res["checks"]["miss_rate"]["value"] == 0.0


@pytest.mark.parametrize("workload,rung", [
    ("tiny-cos.serve", "high"),
    ("tiny-l2.churn", "high"),
    # SIFT's own integer queries are exact in bf16 and in three bf16
    # passes, so the nearest rung that changes a result there is int8
    ("tiny-l2.serve", "int8"),
])
def test_control_is_not_correct(tiny_root, workload, rung):
    res = _run(tiny_root, workload, rung)
    assert res["correct"] is False
    assert res["checks"]["score_gap"]["value"] > (
        res["checks"]["score_gap"]["limit"])


def test_altered_answers_are_not_correct(tiny_root):
    res = _run(tiny_root, "tiny-l2.serve", "highest", altered=True)
    assert res["correct"] is False
    assert res["checks"]["miss_rate"]["value"] > (
        res["checks"]["miss_rate"]["limit"])
