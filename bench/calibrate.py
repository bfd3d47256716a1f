#!/usr/bin/env python3
"""Readings behind the limits of ``correct``, on the chip, in one process.

    python3 bench/calibrate.py --workload sift-l2.serve --seeds 1,2,3 \
        --seconds 20 --modes program,high,bf16,int8,altered

For each seed and mode it runs the whole cell and prints one JSON line with
every compared number:

  program   the program, as bench/run.py runs it: the lower readings
  high, bf16, int8
            the control: the plain reference put in the program's place
            (harness/control.py), computed one rung of precision below the
            configuration's float32 at HIGHEST; the nearest rung that
            changes a result has to come out not correct, and its smallest
            reading is the upper end of a limit
  altered   the reference at full precision with every answer's ids moved
            to other live slots: the planted "answer altered" fault

The benchmark's own runs never run this. PERF.md records the readings and
the limits set from them.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, setup_jax  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--modes", default="program,high,bf16,int8,altered")
    args = p.parse_args(argv)
    setup_jax()
    from harness import cell, control

    def reference_session(precision, altered=False):
        def make(ctx):
            ix = ctx.config["index"]
            return control.ReferenceSession(
                ix["capacity"], ix["dim"], ix["metric"],
                precision=precision, altered=altered)
        return make

    makers = {"program": cell.program_session,
              "altered": reference_session("highest", altered=True)}
    for prec in ("high", "bf16", "int8"):
        makers[prec] = reference_session(prec)
    for seed in (int(s) for s in args.seeds.split(",")):
        for mode in args.modes.split(","):
            t = time.perf_counter()
            res = cell.run(ROOT, args.workload, seed, args.seconds, False,
                           t_start=t, make_session=makers[mode],
                           report=lambda *a, **k: None)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "mode": mode,
                "correct": res["correct"],
                "numbers": {k: v["value"] for k, v in res["checks"].items()},
                "metrics": {k: v["value"] for k, v in res["metrics"].items()},
                "run_s": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
