"""The trace reduction: on hand-made events, and on a small trace the
harness recorded on one v5e (bench/tests/record_trace.py)."""
from __future__ import annotations

import gzip
import json
import shutil
from pathlib import Path

import pytest

from bench_tiny import BENCH
from harness import device, registry, xplane

DATA = Path(__file__).resolve().parent / "data"
RECORDED = ("tiny-l2-serve", "tiny-l2-churn")


def _ev(name, start, dur):
    return xplane.Event(name, float(start), float(dur))


def _trace(ops, modules=(), spans=()):
    dev = {"XLA Ops": [_ev(*o) for o in ops],
           "XLA Modules": [_ev(*m) for m in modules]}
    return xplane.Trace((0.0, 100.0), {"/device:TPU:0": dev},
                        [_ev(xplane.WINDOW, 0, 100)] + [_ev(*s) for s in spans])


def test_busy_is_the_union_of_op_intervals_in_the_window():
    tr = _trace([("a", -10, 20), ("b", 5, 10), ("c", 30, 10), ("d", 95, 20)])
    assert tr.busy_s() == pytest.approx((15 + 10 + 5) * 1e-9)
    assert tr.window_s == pytest.approx(100e-9)


def test_gaps_are_named_by_the_innermost_host_span():
    tr = _trace([("a", 0, 10), ("b", 60, 40)],
                spans=[("bench.serve_step", 5, 80), ("bench.idle", 20, 30)])
    assert tr.gaps(5) == [["bench.idle", pytest.approx(50e-9)]]
    assert tr.top_ops(1) == [["b", pytest.approx(40e-9)]]


def test_op_steps_follow_dispatch_order_and_refuse_a_miscount():
    mods = [("jit_apply_ops_step", 0, 10), ("jit_fold_in", 12, 1),
            ("jit_apply_ops_step", 20, 30), ("jit_apply_ops_step", 60, 5)]
    tr = _trace([], modules=mods)
    got = xplane.op_steps(tr, ["delete", "insert", "query"])
    assert got == {"delete": [pytest.approx(1e-8)],
                   "insert": [pytest.approx(3e-8)],
                   "query": [pytest.approx(5e-9)]}
    with pytest.raises(ValueError):
        xplane.op_steps(tr, ["delete", "insert"])


@pytest.fixture(scope="module", params=RECORDED)
def recorded(request, tmp_path_factory):
    tag = request.param
    src = DATA / f"{tag}.xplane.pb.gz"
    dst = tmp_path_factory.mktemp(tag) / "t.xplane.pb"
    with gzip.open(src, "rb") as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    meta = json.loads((DATA / f"{tag}.json").read_text())
    return tag, xplane.read(str(dst)), meta


def test_recorded_trace_reduces_as_when_recorded(recorded):
    tag, tr, meta = recorded
    assert meta["device"]["kind"] == "TPU v5 lite"
    assert 0 < tr.busy_s() <= tr.window_s
    assert tr.busy_s() == pytest.approx(meta["device"]["busy_s"], rel=1e-9)
    assert tr.window_s == pytest.approx(meta["device"]["window_s"], rel=1e-9)
    steps = xplane.op_steps(tr, meta["dispatched"])
    assert sum(map(len, steps.values())) == len(meta["dispatched"])
    assert json.loads(json.dumps({"device_ops": tr.top_ops(10),
                                  "idle_gaps": tr.gaps(10)})) == (
        meta["breakdown"])
    records = {"trace": tr, "dispatched": meta["dispatched"],
               "peaks": device.peaks_for(meta["device"]["kind"])}
    for name, value in meta["metrics"].items():
        path = BENCH / "layer_metrics" / f"{name}.py"
        if not path.is_file() or registry.load_module(path).read(
                records) is None:
            continue   # read from the run's own records, not the trace
        got = registry.load_module(path).read(records)
        assert got == pytest.approx(value, rel=1e-9), name


@pytest.mark.parametrize("text,want", [
    # the call as the v5e compiler writes it (compile, one-row DMA, d=128)
    ("%gather_scores.1 = f32[64,16,1]{2,1,0:T(8,128)S(1)} custom-call("
     "%reshape.0, %t.1, %broadcast.16, %reshape.21, %bitcast.3), "
     'custom_call_target="tpu_custom_call", operand_layout_constraints={'
     "s32[1024]{0}, f32[262144,128]{1,0}, s32[64,16,1]{2,1,0}, "
     "f32[64,16,1]{2,1,0}, f32[64,1,128]{2,1,0}}", (64, 16, 128)),
    # d=256: the table is read as 8-row groups, the need is one row
    ("%gather_scores.1 = f32[64,16,1]{2,1,0} custom-call(...), "
     "operand_layout_constraints={s32[1024]{0}, f32[32768,8,256]{2,1,0}, "
     "s32[64,16,1]{2,1,0}, f32[64,16,1]{2,1,0}, f32[64,1,256]{2,1,0}}",
     (64, 16, 256)),
])
def test_gather_need_from_hlo_shapes(text, want):
    from harness import kernels

    b, c, d = want
    assert kernels.gather_need(text) == (
        4 * (b * c * (d + 1) + b * c + b * d + b * c), 2 * b * c * d)
    assert kernels.gather_need("f32[3,4]") is None


def test_roofline_share_is_need_over_kernel_time():
    from harness import kernels

    call = ("%gather_scores.3 = f32[64,16,1]{2,1,0} custom-call(s32[1024]{0} "
            "%r, f32[262144,128]{1,0} %t, f32[64,1,128]{2,1,0} %q)")
    ev = [xplane.Event(call, 0.0, 1e6),
          xplane.Event("%copy.1 = f32[64,16,1]{0,1,2} copy(f32[64,16,1]"
                       "{2,1,0} %gather_scores.3)", 0.0, 5e6)]
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    nbytes, _ = kernels.gather_need(call)
    assert kernels.roofline_share(ev, peaks) == pytest.approx(
        100.0 * nbytes / 819e9 / 1e-3)
    assert kernels.roofline_share(ev[1:], peaks) is None


@pytest.mark.parametrize("hlo,want", [
    ("%gather_scores.9 = f32[64,16,1]{2,1,0:T(8,128)S(1)} custom-call(s32[1]"
     "{0} %a)", "gather_scores.9 custom-call"),
    ("%while.4 = (s32[64,64]{0,1:T(8,128)}, f32[64]{0}) while((s32[64,64]"
     "{0,1} %t), condition=%c", "while.4 while"),
    ("%fusion.2 = pred[4096]{0} fusion(pred[8]{0} %p), kind=kLoop",
     "fusion.2 fusion"),
])
def test_op_names_are_instruction_and_opcode(hlo, want):
    assert xplane.op_name(hlo) == want
