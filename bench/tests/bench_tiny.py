"""Helpers of the benchmark's own tests: CPU only, tiny sizes.

``make_root`` builds a checkout of its own: a copy of bench/ plus test-only
files (a configuration, two traffic mixes, a per-layer metric) and a
BENCHMARK.json that names them. Nothing of bench/ is edited to add them,
which is what a later change adding a cell does too.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_METRIC = '''"""Test-only: share of the window's requests that were answered."""
import numpy as np


def read(r):
    return 100.0 * float(np.mean(r["answered"])) if "answered" in r else None
'''


def tiny_config(metric: str = "l2") -> dict:
    cfg = json.loads((BENCH / "configs" / "sift-l2.json").read_text())
    cfg["index"].update(capacity=1024, dim=32, d_out=8, metric=metric)
    cfg["index"]["search"] = {"pool_size": 16}
    cfg["index"]["insert_search"] = {"pool_size": 16}
    cfg["build"] = {"rows": 700, "chunk": 128}
    if metric == "l2":
        cfg["generator"].update(dim=32, latent_dim=8)
    else:
        real = json.loads((BENCH / "configs" / "nytimes-cos.json").read_text())
        cfg["limits"] = real["limits"]
        cfg["generator"] = {"kind": "clustered_sphere", "model_seed": 0,
                            "dim": 32, "latent_dim": 8, "clusters": 10,
                            "pareto_shape": 1.5, "local_scale": 1.5,
                            "noise_scale": 0.1}
    return cfg


def make_root(root: Path) -> Path:
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        "tests", "out", ".jax_cache", "__pycache__"))
    b = root / "bench"
    (b / "configs" / "tiny-l2.json").write_text(json.dumps(tiny_config()))
    (b / "configs" / "tiny-cos.json").write_text(
        json.dumps(tiny_config("cos")))
    serve = json.loads((BENCH / "traffic" / "open-sift-l2.json").read_text())
    serve.update(rate_qps=150, max_batch=16,
                 trace={"start_s": 0.3, "seconds": 0.3})
    (b / "traffic" / "tiny-open.json").write_text(json.dumps(serve))
    churn = json.loads((BENCH / "traffic" / "churn-640.json").read_text())
    churn.update(deletes=32, inserts=32, probes=16, chunk=16, max_steps=6)
    (b / "traffic" / "tiny-churn.json").write_text(json.dumps(churn))
    (b / "layer_metrics" / "answered_share.tiny.py").write_text(TINY_METRIC)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("tiny-l2", "tiny-cos"):
        spec["configs"].append({"name": name, "source": "test-only",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test-only"})
    spec["workloads"] += [
        {"name": f"{c}.{t}", "config": c, "traffic": f"tiny-{m}",
         "chips": 1, "why": "test-only"}
        for c in ("tiny-l2", "tiny-cos")
        for t, m in (("serve", "open"), ("churn", "churn"))]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = "serve" if any(w.endswith(".serve")
                                  for w in m["workloads"]) else "churn"
            m["workloads"] += [f"tiny-l2.{kind}", f"tiny-cos.{kind}"]
    spec["per_layer"].append({
        "name": "answered_share.tiny", "unit": "%", "better": "higher",
        "source": "host_clock", "layer": "batcher", "moves": "query_p95_ms",
        "workloads": ["tiny-l2.serve", "tiny-cos.serve"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
