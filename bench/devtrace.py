"""Profiler capture and the reduction from a device trace to numbers.

A traced run wraps a slice of its window in :class:`Capture`, which runs
``jax.profiler`` and marks the slice with a host span ``bench.traced``.
:func:`load` reads the ``.xplane.pb`` that the profiler writes into a
:class:`Trace`: per chip the ``XLA Ops`` and ``XLA Modules`` lines of the
device plane, and the host spans whose names start with ``bench.``.
Everything after that is plain arithmetic on intervals, kept here so that
every PR computes it the same way; ``bench/tests`` checks it on a recorded
trace (``bench/data/trace_small.json``).
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import shutil
import tempfile
from typing import Callable, Dict, List, Optional, Sequence, Tuple

TRACED_SPAN = "bench.traced"
HOST_PREFIX = "bench."

Interval = Tuple[float, float]          # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start: float                        # ns
    end: float                          # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Chip:
    ops: List[Event]                    # 'XLA Ops' line
    modules: List[Event]                # 'XLA Modules' line


@dataclasses.dataclass
class Trace:
    chips: List[Chip]
    host: List[Event]                   # bench.* host spans

    # ------------------------------------------------------------ (de)ser
    def to_json(self) -> dict:
        ev = lambda es: [[e.name, e.start, e.end] for e in es]  # noqa: E731
        return {"chips": [{"ops": ev(c.ops), "modules": ev(c.modules)}
                          for c in self.chips],
                "host": ev(self.host)}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda es: [Event(n, float(s), float(e)) for n, s, e in es]  # noqa: E731
        return cls(chips=[Chip(ev(c["ops"]), ev(c["modules"]))
                          for c in d["chips"]], host=ev(d["host"]))

    # ------------------------------------------------------------- window
    def window(self) -> Interval:
        spans = [e for e in self.host if e.name == TRACED_SPAN]
        if not spans:
            raise ValueError(f"trace has no {TRACED_SPAN!r} span")
        return spans[0].start, spans[0].end


# ------------------------------------------------------------ interval math


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Interval]:
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, t in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], t)
        else:
            merged.append([s, t])
    return [(s, t) for s, t in merged]


def total(intervals: Sequence[Interval]) -> float:
    return sum(t - s for s, t in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, t in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def self_times(evs: Sequence[Event]) -> List[Tuple[str, float]]:
    """Each event's duration less that of events nested directly inside it
    (a ``while`` op encloses the ops of its body on the same line).
    ``evs`` is sorted by (start, -end); the result follows that order."""
    out: Dict[int, float] = {}
    stack: List[int] = []
    for i, e in enumerate(evs):
        while stack and evs[stack[-1]].end <= e.start:
            stack.pop()
        out[i] = e.dur
        if stack and e.end <= evs[stack[-1]].end:
            out[stack[-1]] -= e.dur
        stack.append(i)
    return [(evs[i].name, max(v, 0.0)) for i, v in out.items()]


def op_short(name: str) -> str:
    """'%fusion.3 = f32[..] fusion(...)' -> 'fusion.3'."""
    head = name.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def module_short(name: str) -> str:
    """'jit_fused(1234)' -> 'jit_fused'."""
    return name.split("(", 1)[0]


# ------------------------------------------------------------------ reduced


@dataclasses.dataclass
class Reduced:
    """A trace reduced to the traced window: what readers and the result
    line use."""
    trace: Trace
    lo: float
    hi: float

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def busy_intervals(self, chip: Chip) -> List[Interval]:
        return union(clip(chip.ops, self.lo, self.hi))

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        chips = self.trace.chips
        return sum(total(self.busy_intervals(c)) for c in chips) \
            / len(chips) * 1e-9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, pred: Callable[[str], bool]) -> float:
        """Device seconds of the programs whose name passes ``pred``
        (clipped to the window, averaged over chips)."""
        chips = self.trace.chips
        return sum(total(union(clip(
            [m for m in c.modules if pred(module_short(m.name))],
            self.lo, self.hi))) for c in chips) / len(chips) * 1e-9

    def top_ops(self, k: int = 10) -> List[list]:
        """Device ops by self time in the window, named module/op."""
        chip = self.trace.chips[0]
        mods = sorted(chip.modules, key=lambda m: m.start)
        starts = [m.start for m in mods]
        import bisect
        totals: Dict[str, float] = {}
        evs = sorted((e for e in chip.ops if self.lo <= e.start < self.hi),
                     key=lambda e: (e.start, -e.end))
        for (name, st), e in zip(self_times(evs), evs):
            i = bisect.bisect_right(starts, e.start) - 1
            mod = module_short(mods[i].name) if i >= 0 \
                and mods[i].end >= e.start else "?"
            key = f"{mod}/{op_short(name)}"
            totals[key] = totals.get(key, 0.0) + st
        top = sorted(totals.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s * 1e-9] for n, s in top]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """Longest gaps between device ops, named by the innermost host
        span in progress at the gap's middle."""
        chip = self.trace.chips[0]
        spans = [h for h in self.trace.host if h.name != TRACED_SPAN]
        out = []
        for s, t in gaps(self.busy_intervals(chip), self.lo, self.hi):
            mid = 0.5 * (s + t)
            cover = [h for h in spans if h.start <= mid <= h.end]
            name = min(cover, key=lambda h: h.dur).name if cover \
                else "no bench span"
            out.append([name, (t - s) * 1e-9])
        return sorted(out, key=lambda g: -g[1])[:k]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def reduce(trace: Trace) -> Reduced:
    lo, hi = trace.window()
    return Reduced(trace, lo, hi)


# ------------------------------------------------------------------ loading


def load(xplane_path: str) -> Trace:
    """Read the device and host lines of a profiler ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    chips, host = [], []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            ev = lambda ln: [Event(e.name, e.start_ns,  # noqa: E731
                                   e.start_ns + e.duration_ns)
                             for e in ln.events]
            chips.append((plane.name, Chip(
                ev(lines["XLA Ops"]),
                ev(lines["XLA Modules"]) if "XLA Modules" in lines
                else [])))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                            for e in ln.events
                            if e.name.startswith(HOST_PREFIX))
    chips.sort(key=lambda c: int(c[0].rsplit(":", 1)[1]))
    if not chips:
        raise RuntimeError("the profiler trace holds no TPU device plane")
    return Trace(chips=[c for _, c in chips], host=host)


class Capture:
    """Trace a slice of the run: ``start()`` before it, ``stop()`` after.

    The profiler writes into a temporary directory (under ``$TMPDIR``),
    which is read and removed at ``stop()``.  A profiler that fails raises:
    a traced run never falls back to the host clock.
    """

    def __init__(self):
        self.dir: Optional[str] = None
        self._span = None

    def start(self) -> None:
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._span.__enter__()

    def stop(self) -> Reduced:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            hits = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                          "*", "*.xplane.pb"))
            if not hits:
                raise RuntimeError("the profiler wrote no .xplane.pb")
            return reduce(load(hits[0]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def save_json(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)


def load_json(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
