"""The benchmark's spine: find a cell's files by name, run it, print the result.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``   sizes, the deployment it stands for, and
  the name of its plain reference module (``bench/configs/*.reference.py``);
* ``bench/traffic/<mix>.json``      parameters for one general generator,
  named by its ``generator`` key (``bench/runners/<generator>.py``);
* ``bench/metrics/<metric>.py``     a reader ``read(run) -> float | None``.

A runner drives the system under test for one run and returns a
:class:`RunOutput`; the metric readers reduce it; :func:`result_line` prints
the one JSON line the caller reads.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class SetupError(RuntimeError):
    """The run cannot start: no chip, a file missing, a name unknown."""


# ----------------------------------------------------------------- the spec


def load_spec(root: str = ROOT) -> dict:
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SetupError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None):
    """Import a file by path (metric and reference files carry dots and
    dashes in their names, so they are not importable as packages)."""
    if not os.path.exists(path):
        raise SetupError(f"missing module file {path}")
    mod_name = name or "bench_" + os.path.basename(path).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files resolved."""
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH

    def reference(self):
        """The configuration's plain reference module."""
        return load_module(os.path.join(self.bench_dir, "configs",
                                        self.config["reference"]))

    def runner(self):
        return load_module(os.path.join(self.bench_dir, "runners",
                                        self.traffic["generator"] + ".py"))


def _metric_applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def find_cell(spec: dict, name: str, bench_dir: str = BENCH) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg_entry = configs[w["config"]]
    root = os.path.dirname(bench_dir)
    config = load_json(os.path.join(root, cfg_entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in spec["end_to_end"] if _metric_applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _metric_applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


# ------------------------------------------------------------- run records


@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` iff value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class RunOutput:
    """What a runner hands back.

    ``end_to_end`` holds the host-clock metrics by name; ``layer`` is the
    free-form record the per-layer readers take their numbers from (work
    counts, program counters and spans, the reduced trace).
    """
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    checks: List[Check]
    layer: Dict[str, Any]
    device: Dict[str, Any]
    trace: Any = None            # bench.trace.Reduced when --trace 1
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c.ok for c in self.checks)


class Phases:
    """Set-up phases on the host clock, printed on an earlier line."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.t_last = t_start
        self.items: List[tuple] = []

    def mark(self, name: str) -> float:
        now = time.perf_counter()
        self.items.append((name, now - self.t_last))
        self.t_last = now
        return now - self.t_start

    def line(self) -> str:
        return ", ".join(f"{n} {s:.2f}s" for n, s in self.items)


class CompileCounter:
    """XLA executables created (compiled or loaded from the persistent
    cache), from ``jax.monitoring``: any inside the window means a shape
    was not warmed.  ``hits`` counts those loaded from the persistent
    cache, ``writes`` those compiled and written to it."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    WRITE = "/jax/compilation_cache/cache_misses"   # recorded on a write

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration

    def _on_event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.WRITE:
            self.writes += 1

    def line(self) -> str:
        return (f"{self.count} programs: {self.hits} loaded from the "
                f"persistent cache, {self.writes} compiled and written to "
                f"it, {self.seconds:.2f} s in all")


def device_record() -> dict:
    """The devices as JAX reports them, with the peak memory of the
    fullest chip.  Runners read it right after the window, before the
    reference runs on the chip."""
    import jax
    devices = jax.devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


# ----------------------------------------------------------------- metrics


def read_per_layer(cell: Cell, out: RunOutput) -> Dict[str, dict]:
    """Run every per-layer reader of the cell; a reader that finds nothing
    returns None and its metric is left out."""
    found = {}
    for m in cell.per_layer:
        mod = load_module(os.path.join(cell.bench_dir, "metrics",
                                       m["name"] + ".py"))
        value = mod.read(out)
        if _finite(value):
            found[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return found


def result_line(cell: Cell, out: RunOutput, trace: bool) -> dict:
    if trace:
        metrics = read_per_layer(cell, out)
    else:
        metrics = {m["name"]: {"value": float(out.end_to_end[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if _finite(out.end_to_end.get(m["name"]))}
    line: Dict[str, Any] = {"correct": out.correct,
                            "attempted": int(out.attempted),
                            "failed": int(out.failed),
                            "metrics": metrics,
                            "device": dict(out.device)}
    if trace and out.trace is not None:
        line["device"]["busy_s"] = out.trace.busy_s
        line["device"]["window_s"] = out.trace.window_s
        line["breakdown"] = out.trace.breakdown()
    line["checks"] = {c.name: {"value": c.value if _finite(c.value)
                               else None, "limit": c.limit}
                      for c in out.checks}
    return line


def _finite(x) -> bool:
    return x is not None and math.isfinite(x)


def print_checks(out: RunOutput, stream=sys.stderr) -> None:
    for c in out.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=stream, flush=True)
