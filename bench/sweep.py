#!/usr/bin/env python3
"""Find a serve cell's knee: one build, then open-loop windows at each rate.

    python3 bench/sweep.py --workload sift-l2.serve --rates 1000,2000,3000 \
        --seconds 6 --seed 1

For each offered rate it prints one JSON line: the requests answered per
second inside the window, the median and 95th-percentile latency, the
batch fill and the backlog left when the window closed. The knee is the
highest rate whose answered rate keeps up with the offered rate and whose
backlog does not grow; a serve cell's traffic offers about four fifths of
it. The sweep is run once, when a cell is defined, and its output is
recorded in PERF.md. It does not judge answers.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, setup_jax  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=6.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    jax = setup_jax()
    import numpy as np

    from harness import cell, device, registry

    c = registry.resolve(ROOT, args.workload)
    dev, _ = device.check(jax.devices(), c.chips)
    ctx = cell.Ctx(c, args.seed, args.seconds, cell.Tracer(None),
                   np.random.default_rng(args.seed))
    drv = ctx.plugin("drivers", c.traffic["driver"])
    drv.prepare(ctx)
    t = time.perf_counter()
    cell.build(ctx)
    build_s = time.perf_counter() - t
    drv.warm(ctx)
    print(json.dumps({"workload": c.name, "device": dev, "build_s": build_s,
                      "setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        ctx.cell = dataclasses.replace(c, traffic=dict(c.traffic,
                                                       rate_qps=rate))
        drv.prepare(ctx)
        drv.window(ctx)
        r = ctx.records
        lat = (r["done_t"] - r["due"])[r["answered"]]
        end = r["t0"] + r["window_s"]
        print(json.dumps({
            "rate_qps": rate,
            "answered_qps": float((r["done_t"] <= end).sum()) / r["window_s"],
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "batch_fill": r["server_stats"]["requests"] / max(
                r["server_stats"]["batches"] * r["max_batch"], 1),
            "backlog_at_close": int((r["done_t"] > end).sum()
                                    + (~r["answered"]).sum()),
            "unanswered": int((~r["answered"]).sum())}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
