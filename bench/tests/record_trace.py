#!/usr/bin/env python3
"""Record the small trace that test_bench_xplane.py reduces, on the chip.

    python3 bench/tests/record_trace.py OUT_DIR

Runs the test-only ``tiny-l2.serve`` and ``tiny-l2.churn`` cells (see
bench_tiny.py) through the harness with ``--trace 1`` on the TPU this process
finds, and writes to OUT_DIR, for each cell, the ``.xplane.pb`` the profiler
wrote, gzipped, with the op-step dispatch order of its traced window and
the numbers the reduction gave.
"""
from __future__ import annotations

import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent), str(HERE.parent.parent / "src")]

import bench_tiny  # noqa: E402
from harness import cell, xplane  # noqa: E402


def main(out: Path) -> int:
    out.mkdir(parents=True, exist_ok=True)
    root = bench_tiny.make_root(Path(tempfile.mkdtemp(dir=out)))
    for workload in ("tiny-l2.serve", "tiny-l2.churn"):
        records: dict = {}
        res = cell.run(root, workload, 7, 2.0, True,
                       t_start=time.perf_counter(), keep_trace=True,
                       records=records)
        src = xplane.find(str(root / "bench" / "out" / "trace"))
        tag = workload.replace(".", "-")
        with open(src, "rb") as f, gzip.open(out / f"{tag}.xplane.pb.gz",
                                             "wb") as g:
            shutil.copyfileobj(f, g)
        (out / f"{tag}.json").write_text(json.dumps({
            "dispatched": records["dispatched"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "device": res["device"], "breakdown": res["breakdown"]},
            indent=1))
        shutil.rmtree(root / "bench" / "out")
    shutil.rmtree(root)
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
