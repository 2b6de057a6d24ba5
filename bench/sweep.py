#!/usr/bin/env python3
"""Find a serving cell's knee: one window per offered rate, in one process.

Prints one JSON line per rate (offered rate, output tokens/s in the
window, queue left at its end, TTFT and TPOT percentiles) and writes them
all to ``--out``.  The chosen rate goes into the traffic file by hand,
with the commit it was found on.  Usage, on a machine with the chip::

  python3 bench/sweep.py --workload granite-3-2b.chat --rates 4 6 8 --seconds 30 --seed 5 --out chiprun_out/sweep.json
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(harness.load_spec(run.ROOT), args.workload)
    jax = run.configure_jax()
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 3
    rows = cell.runner().sweep(cell, args)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "seed": args.seed,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
