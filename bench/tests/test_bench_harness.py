"""The harness: lookup by name, the device gate, and whole runs of
test-only cells through the real Session and BatchedServer (CPU)."""
from __future__ import annotations

import json
import time
import types

import pytest

from bench_tiny import REPO
from harness import cell, device, registry

KEYS = ("correct", "attempted", "failed", "metrics", "device", "checks")


def _dev(platform="tpu", kind="TPU v5 lite"):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_resolves_committed_cells_by_name():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in spec["workloads"]:
        c = registry.resolve(REPO, w["name"])
        assert c.config["name"] == w["config"]
        assert c.chips == w["chips"]
        assert "setup_s" in {m["name"] for m in c.end_to_end}
        assert c.per_layer, "every cell reports a per-layer metric"
        registry.plugin("drivers", c.traffic["driver"], c.bench_dir)
        registry.plugin("generators", c.config["generator"]["kind"],
                        c.bench_dir)
        for m in c.end_to_end:
            registry.plugin("end_to_end", m["name"], c.bench_dir)
        for m in c.per_layer:
            registry.plugin("layer_metrics", m["name"], c.bench_dir)


def test_unknown_workload_is_refused():
    with pytest.raises(registry.SpecError):
        registry.resolve(REPO, "no-such.cell")


@pytest.mark.parametrize("devices,chips", [
    ([_dev(platform="cpu", kind="cpu")], 1),
    ([_dev(kind="TPU v9 imaginary")], 1),
    ([_dev()], 4),
    ([], 1),
], ids=["cpu", "unknown_kind", "too_few_chips", "none"])
def test_device_gate_refuses(devices, chips):
    with pytest.raises(device.DeviceError):
        device.check(devices, chips)


def test_device_gate_accepts_v5e():
    dev, peaks = device.check([_dev()], 1)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_run_py_exits_nonzero_without_tpu(capsys):
    import run

    assert run.main(["--workload", "sift-l2.serve", "--seed", "1",
                     "--seconds", "1"]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


@pytest.mark.parametrize("workload", ["tiny-l2.serve", "tiny-cos.churn"])
def test_test_only_cell_runs_end_to_end(tiny_root, workload):
    res = cell.run(tiny_root, workload, 2**31 + 17, 1.0, False,
                   t_start=time.perf_counter(), check_device=False,
                   report=lambda *a, **k: None)
    assert list(res)[-1] == "checks" and set(KEYS) <= set(res)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    want = {"recall_at_10", "setup_s"} | (
        {"query_p95_ms", "query_qps"} if workload.endswith("serve")
        else {"update_items_per_s"})
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"]["memory_peak_bytes"] >= 0
    json.dumps(res)


def test_traced_run_reports_added_metric(tiny_root):
    """A per-layer metric added as a file and an entry, with no edit to
    an existing file, is read; device-trace metrics find no device plane
    on the CPU and are left out, never reported as 0."""
    res = cell.run(tiny_root, "tiny-l2.serve", 5, 1.0, True,
                   t_start=time.perf_counter(), check_device=False,
                   report=lambda *a, **k: None)
    assert res["metrics"]["answered_share.tiny"]["value"] == 100.0
    assert "batch_fill.serve" in res["metrics"]
    assert "op_step_ms.query" not in res["metrics"]
    assert "device_idle_share.serve" not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
