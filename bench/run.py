#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU this process finds.

    python3 bench/run.py --workload sift-l2.serve --seed 7 --seconds 20 --trace 0

The cell, its configuration, traffic and metrics are looked up by name in
BENCHMARK.json at the root of the checkout (harness/registry.py). With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the profiler records a slice of the window and the result
carries the per-layer metrics and the trace's breakdown. The last line of
standard output is the result as one JSON object; the compared numbers and
their limits are also the last lines of standard error.

Exits 2 without printing a result when JAX finds no TPU, fewer chips than
the cell asks for, or a device kind missing from peaks.json. Compiled
programs persist in ``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise
in bench/.jax_cache/ inside the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def setup_jax():
    """Put the program and the harness on the path and the compile cache in
    place; returns the ``jax`` module."""
    for p in (str(BENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # every program the cell runs is cached, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def main(argv=None) -> int:
    args = _args(argv)
    setup_jax()
    from harness import cell, device, registry

    try:
        result = cell.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except (device.DeviceError, registry.SpecError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
