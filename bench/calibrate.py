#!/usr/bin/env python3
"""Read a cell's correctness numbers for the program and for its control.

The limits under ``limits`` in a traffic file are set from these readings
(the lower one: the largest the program gives over a dozen seeds or more;
the upper one: the smallest the control gives).  The benchmark's own runs
never run the control.  Usage, on a machine with the chip::

  python3 bench/calibrate.py --workload hpcg-168.cg-rgcsr --seeds 1 2 3
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import run      # noqa: E402


def main(argv=None, *, allow_cpu: bool = False, cell_hook=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--iterations", type=int, default=50,
                    help="CG cells: iterations the control runs")
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_spec(run.ROOT), args.workload)
    if cell_hook is not None:
        cell_hook(cell)
    jax = run.configure_jax()
    if not allow_cpu and jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 3
    readings = cell.runner().calibrate(cell, args)
    for r in readings:
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
