#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

Usage, from the root of a checkout on a machine with the chips the cell
asks for::

  python3 bench/run.py --workload hpcg-168.cg-rgcsr --seed 7 --seconds 10 --trace 0

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of a slice of the window.  The
last line of standard output is the result; set-up phases, compilations
in the window and counts go on earlier lines, and every number that
decides ``correct`` is printed beside its limit as the last lines of
standard error.  Without a TPU (or with fewer chips than the cell asks
for) the run prints no result and exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))      # the system under test

import harness  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")


def _since_process_start() -> float:
    """Seconds the process lived before this module ran (Linux)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK")
                   - (time.perf_counter() - T_START))
    except (OSError, ValueError, IndexError):
        return 0.0


class Context:
    """What a runner gets: the cell, the arguments, the set-up clock."""

    def __init__(self, cell, args, phases, compiles):
        self.cell = cell
        self.seed = int(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.phases = phases
        self.compiles = compiles

    @staticmethod
    def log(msg: str) -> None:
        print(msg, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax():
    import jax
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # No eviction: with a size limit (which the environment may set) every
    # write first reads each entry's access-time file, and one entry without
    # it (left by a run that had no limit) makes every later write fail.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax


def main(argv=None, *, allow_cpu: bool = False, cell_hook=None) -> int:
    """``allow_cpu`` and ``cell_hook`` (which may resize the cell in place)
    exist for the benchmark's own tests; a real run passes neither."""
    args = parse_args(argv)
    phases = harness.Phases(T_START - _since_process_start())
    try:
        cell = harness.find_cell(harness.load_spec(ROOT), args.workload)
    except harness.SetupError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if cell_hook is not None:
        cell_hook(cell)
    jax = configure_jax()
    devices = jax.devices()
    if not allow_cpu and devices[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: the cell needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    phases.mark("start and imports")
    ctx = Context(cell, args, phases, harness.CompileCounter())
    out = cell.runner().run(ctx)
    for key, value in out.notes.items():
        print(f"{key}: {value}", flush=True)
    print(f"compile cache: {ctx.compiles.line()} ({CACHE_DIR})", flush=True)
    line = harness.result_line(cell, out, bool(args.trace))
    harness.print_checks(out)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
