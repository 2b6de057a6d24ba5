"""Unified observability subsystem (DESIGN.md §13, ISSUE 10).

Covers the typed metrics registry (counters/gauges/histograms, the
StatsView dict facade, declarative cross-replica merge, JSON snapshot
round-trip), the structured span/event tracer (deterministic under
FakeClock: two identical runs export byte-identical Chrome trace JSON),
the trace-event validator and counter cross-check the CI trace lane
gates on, metrics survival across the §7.6 kill-all drill (no resets, no
double counts), and the kernel-timing provenance path (``time_us``
warmup semantics, ``autotune.timing_source()``).

Determinism note: every engine test runs FakeClock advanced per decode
step with greedy sampling — byte-identity assertions would be impossible
on wall-clock.
"""
import json

import numpy as np
import pytest

from repro.configs import get_smoke
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs.trace import NOOP, Tracer
from repro.serve import Engine, Request, Router, RouterConfig, ServeConfig
from repro.serve.paging import SERVE_MERGE_SPEC, merge_replica_stats

S_MAX = 64
PS = 4


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _tick_decode(eng, clock, dt=1.0):
    orig = eng._decode
    orig_fused = eng._fused_decode

    def wrapped(*a):
        clock.advance(dt)
        return orig(*a)

    def wrapped_fused(*a):
        out = orig_fused(*a)
        clock.advance(dt * int(out[1]))
        return out

    eng._decode = wrapped
    eng._fused_decode = wrapped_fused


def _engine(cfg=None, clock=None, params=None, tracer=None, **serve_kw):
    cfg = cfg or get_smoke("granite-3-2b")
    skw = dict(max_seq=S_MAX, n_slots=2, page_size=PS, temperature=0.0,
               eos_id=-1)
    skw.update(serve_kw)
    eng = Engine(cfg, ServeConfig(**skw), params=params)
    if tracer is not None:
        eng.tracer = tracer
    if clock is not None:
        eng.clock = clock
        _tick_decode(eng, clock)
    return cfg, eng


def _reqs(cfg, n, seed=11, prompt_len=8, max_new=4):
    rng = np.random.default_rng(seed)
    return [Request(tokens=rng.integers(0, cfg.vocab,
                                        (prompt_len,)).astype(np.int32),
                    max_new_tokens=max_new) for _ in range(n)]


# ------------------------------------------------------------- registry


def test_registry_get_or_create_and_kind_conflict():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("preemptions")
    assert reg.counter("preemptions") is c
    c.inc(3)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("page_high_water")
    g.set_max(5)
    g.set_max(2)
    assert g.value == 5
    # labels distinguish children of one logical metric
    assert reg.counter("faults", replica=0) \
        is not reg.counter("faults", replica=1)
    with pytest.raises(TypeError):
        reg.histogram("preemptions")


def test_stats_view_is_dict_compatible():
    reg = obs_metrics.MetricsRegistry()
    stats = reg.view(counters=("preemptions",), gauges=("peak",))
    stats["preemptions"] += 1
    stats["preemptions"] += 1
    stats["new_counter"] = 7         # created on the fly
    assert stats["preemptions"] == 2
    assert dict(stats) == {"preemptions": 2, "peak": 0, "new_counter": 7}
    assert len(stats) == 3 and "preemptions" in stats
    # the values live in typed registry cells, not a shadow dict
    assert reg.counter("preemptions").value == 2
    with pytest.raises(TypeError):
        del stats["preemptions"]
    with pytest.raises(KeyError):
        stats["never_set"]


def test_histogram_percentiles_and_overflow_visibility():
    h = obs_metrics.Histogram("latency_s")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.dropped == 0
    pcts = obs_metrics.percentile_summary(h.state())
    assert pcts["p50"] == pytest.approx(50.5)
    assert pcts["p95"] < pcts["p99"] <= 100.0
    assert obs_metrics.percentile_summary({"samples": []}) == {}
    # overflow keeps count/sum exact and counts the discard
    h2 = obs_metrics.Histogram("big")
    h2.MAX_SAMPLES = 10  # instance override keeps the test tiny
    for v in range(25):
        h2.observe(v)
    assert h2.count == 25 and len(h2.samples) == 10 and h2.dropped == 15


def test_registry_snapshot_restore_roundtrip():
    reg = obs_metrics.MetricsRegistry()
    reg.counter("completed").inc(4)
    reg.gauge("peak").set(9)
    reg.histogram("queue_s").observe(0.5)
    reg.histogram("queue_s").observe(1.5)
    snap = json.loads(json.dumps(reg.snapshot()))  # must be JSON-clean
    reg2 = obs_metrics.MetricsRegistry()
    reg2.restore(snap)
    assert reg2.counter("completed").value == 4
    assert reg2.gauge("peak").value == 9
    assert reg2.histogram("queue_s").state() == \
        reg.histogram("queue_s").state()
    assert reg2.snapshot() == reg.snapshot()


def test_merge_stats_serve_spec_semantics():
    a = {"preemptions": 2, "completed": 3, "n_pages": 16, "page_size": 4,
         "page_high_water": 5, "peak_live_tokens": 40,
         "straggler_decode_steps": 1,
         "request_timing": {"latency_s": {"count": 1, "sum": 2.0,
                                          "dropped": 0, "samples": [2.0]}}}
    b = {"preemptions": 1, "completed": 4, "n_pages": 99, "page_size": 4,
         "page_high_water": 7, "straggler_decode_steps": 0,
         "request_timing": {"latency_s": {"count": 1, "sum": 4.0,
                                          "dropped": 0, "samples": [4.0]}}}
    m = merge_replica_stats([a, b])
    assert m["preemptions"] == 3 and m["completed"] == 7      # sum
    assert m["n_pages"] == 16                                  # first
    assert m["page_high_water"] == 7                           # max
    assert m["page_high_water_per_replica"] == [5, 7]          # list_as
    assert m["straggler_decode_steps_per_replica"] == [1, 0]
    # gate: peak_live_tokens merges because page_high_water is present,
    # replica b's missing entry contributing 0
    assert m["peak_live_tokens"] == 40
    # hist_map: samples concatenate, percentiles come from merged samples
    lat = m["request_timing"]["latency_s"]
    assert lat["count"] == 2 and sorted(lat["samples"]) == [2.0, 4.0]
    assert obs_metrics.timing_percentiles(m["request_timing"])[
        "latency_s"]["p50"] == pytest.approx(3.0)
    # keys outside the spec are dropped; empty input merges to {}
    assert "not_a_key" not in merge_replica_stats([{"not_a_key": 1}])
    assert merge_replica_stats([]) == {}
    # every session counter the engine seeds has a rule (schema drift guard)
    for key in ("requests", "completed", "preemptions", "rejected",
                "failed", "timed_out", "restores", "pages_quarantined",
                "decode_steps", "request_timing", "decode_enqueue_s",
                "decode_wait_s", "decode_commit_s"):
        assert key in SERVE_MERGE_SPEC


# --------------------------------------------------------------- tracer


def _scripted_tracer():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    req = Request(tokens=np.zeros(4, np.int32), max_new_tokens=2)
    tr.request_begin(req, ("router", "main"), prompt=4)
    clock.advance(0.5)
    tr.begin("prefill", ("replica0", "slot0"), tokens=4)
    clock.advance(1.0)
    tr.end("prefill", ("replica0", "slot0"))
    tr.instant("preempt", ("replica0", "slot0"), slot=0)
    tr.counter("free_pages", ("replica0", "session"), free=3)
    tr.request_point(req, "migrated", ("router", "main"))
    clock.advance(0.25)
    tr.request_end(req, ("router", "main"), status="ok")
    return tr


def test_tracer_export_is_deterministic_and_valid():
    t1, t2 = _scripted_tracer(), _scripted_tracer()
    e1 = obs_export.export_chrome_trace(t1)
    e2 = obs_export.export_chrome_trace(t2)
    assert e1 == e2                      # byte-identical
    doc = json.loads(e1)
    assert obs_export.validate_chrome_trace(doc) == []
    # track naming made it into the metadata records
    names = {ev["args"]["name"] for ev in doc["traceEvents"]
             if ev.get("ph") == "M"}
    assert {"router", "replica0", "main", "slot0", "session"} <= names


def test_noop_tracer_records_nothing():
    req = Request(tokens=np.zeros(2, np.int32), max_new_tokens=1)
    NOOP.begin("x", ("a", "b"))
    NOOP.request_begin(req, ("a", "b"))
    assert NOOP.enabled is False and not hasattr(NOOP, "events")


def test_null_tracer_span_records_nothing():
    """Tracing off: every span site gets the one shared no-op context."""
    ctx = NOOP.span("dispatch", ("replica0", "session"), chunk=8)
    assert ctx is NOOP.span("fetch", ("replica0", "session"))
    with ctx as entered:
        assert entered is None
    with pytest.raises(KeyError):
        with NOOP.span("admit", ("replica0", "session")):
            raise KeyError("passes through")
    assert not hasattr(NOOP, "events")


def test_tracer_span_nests_and_closes_on_exception():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    lane = ("replica0", "session")
    with tr.span("decode_chunk", lane, chunk=8):
        clock.advance(1.0)
        with tr.span("dispatch", lane):
            clock.advance(0.5)
    with pytest.raises(RuntimeError):
        with tr.span("admit", lane):
            with tr.span("prefill", ("replica0", "slot0"), tokens=4):
                clock.advance(0.25)
                raise RuntimeError("prefill fault")
    got = [(e["ph"], e["name"], e["ts"], e.get("args")) for e in tr.events]
    assert got == [
        ("B", "decode_chunk", 0, {"chunk": 8}),
        ("B", "dispatch", 1_000_000, None),
        ("E", "dispatch", 1_500_000, None),
        ("E", "decode_chunk", 1_500_000, None),
        ("B", "admit", 1_500_000, None),
        ("B", "prefill", 1_500_000, {"tokens": 4}),
        ("E", "prefill", 1_750_000, {"error": True}),
        ("E", "admit", 1_750_000, {"error": True}),
    ]
    doc = json.loads(obs_export.export_chrome_trace(tr))
    assert obs_export.validate_chrome_trace(doc) == []


def test_request_lifeline_guards():
    tr = Tracer(clock=FakeClock())
    req = Request(tokens=np.zeros(2, np.int32), max_new_tokens=1)
    tr.request_point(req, "early", ("r", "m"))   # before begin: dropped
    tr.request_end(req, ("r", "m"))              # before begin: dropped
    assert tr.events == []
    tr.request_begin(req, ("r", "m"))
    tr.request_begin(req, ("r", "m"))            # idempotent
    tr.request_end(req, ("r", "m"))
    assert [e["ph"] for e in tr.events] == ["b", "e"]


def test_validator_catches_malformed_traces():
    def doc(events):
        return {"traceEvents": events}

    base = {"pid": 1, "tid": 1, "cat": "serve"}
    # E without B
    assert obs_export.validate_chrome_trace(doc(
        [{"name": "x", "ph": "E", "ts": 1, **base}]))
    # bad nesting (E closes a differently-named B)
    assert obs_export.validate_chrome_trace(doc(
        [{"name": "a", "ph": "B", "ts": 1, **base},
         {"name": "b", "ph": "E", "ts": 2, **base}]))
    # unclosed B
    assert obs_export.validate_chrome_trace(doc(
        [{"name": "a", "ph": "B", "ts": 1, **base}]))
    # timestamps must be non-decreasing per (pid, tid)
    assert obs_export.validate_chrome_trace(doc(
        [{"name": "a", "ph": "i", "ts": 5, **base},
         {"name": "b", "ph": "i", "ts": 3, **base}]))
    # async instant outside its lifeline
    assert obs_export.validate_chrome_trace(doc(
        [{"name": "request", "ph": "n", "ts": 1, "id": 7, **base}]))
    # missing required keys / unknown phase
    assert obs_export.validate_chrome_trace(doc([{"ph": "i", "ts": 0}]))
    assert obs_export.validate_chrome_trace(doc(
        [{"name": "a", "ph": "?", "ts": 1, **base}]))


def test_export_closes_abandoned_spans():
    """A crash kills the process mid-span: the export synthesizes closers
    (tagged abandoned) so the trace still validates."""
    clock = FakeClock()
    tr = Tracer(clock=clock)
    req = Request(tokens=np.zeros(2, np.int32), max_new_tokens=1)
    tr.begin("decode_chunk", ("replica0", "session"))
    tr.request_begin(req, ("router", "main"))
    clock.advance(2.0)
    doc = json.loads(obs_export.export_chrome_trace(tr))
    assert obs_export.validate_chrome_trace(doc) == []
    closers = [ev for ev in doc["traceEvents"]
               if (ev.get("args") or {}).get("abandoned")]
    assert {ev["ph"] for ev in closers} == {"E", "e"}


def test_cross_check_counters_exact_at_least_and_attribution():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    tr.instant("migrate", ("replica1", "session"), replica=1)
    tr.instant("preempt", ("replica0", "slot0"), slot=0)
    doc = json.loads(obs_export.export_chrome_trace(tr))
    ok = {"migrations": 1, "preemptions": 1}
    assert obs_export.cross_check_counters(doc, ok) == []
    # count mismatch is caught in exact mode, tolerated upward in at_least
    assert obs_export.cross_check_counters(doc, {"migrations": 2})
    under = {"migrations": 0, "preemptions": 1}
    assert obs_export.cross_check_counters(doc, under, mode="at_least") \
        == []
    assert obs_export.cross_check_counters(doc, {"preemptions": 2},
                                           mode="at_least")
    with pytest.raises(ValueError):
        obs_export.cross_check_counters(doc, ok, mode="bogus")
    # replica-attribution: an event tagged replica=N on the wrong process
    tr2 = Tracer(clock=FakeClock())
    tr2.instant("migrate", ("replica0", "session"), replica=1)
    doc2 = json.loads(obs_export.export_chrome_trace(tr2))
    assert obs_export.cross_check_counters(doc2, {"migrations": 1})


def test_span_summary_counts_and_durations():
    tr = _scripted_tracer()
    summ = obs_export.span_summary(tr)
    assert summ["spans"]["prefill"]["n"] == 1
    assert summ["spans"]["prefill"]["total_s"] == pytest.approx(1.0)
    assert summ["events"]["preempt"] == 1
    assert summ["events"]["migrated"] == 1   # request_point by args.point


# -------------------------------------------------- engine integration


def _traced_serve(seed_params=None):
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    cfg, eng = _engine(clock=clock, params=seed_params, tracer=tracer)
    reqs = _reqs(cfg, 3)
    eng.serve(reqs)
    assert all(r.ok_like for r in reqs)
    return eng, tracer


def test_engine_trace_deterministic_byte_identical():
    """THE determinism acceptance: two identical FakeClock serves export
    byte-identical Chrome traces, and the trace validates + cross-checks
    against the run's own stats."""
    eng1, t1 = _traced_serve()
    eng2, t2 = _traced_serve(seed_params=eng1.params)
    e1 = obs_export.export_chrome_trace(t1)
    e2 = obs_export.export_chrome_trace(t2)
    assert e1 == e2
    doc = json.loads(e1)
    assert obs_export.validate_chrome_trace(doc) == []
    assert obs_export.cross_check_counters(doc, eng1.paging_stats) == []
    # the span taxonomy actually showed up
    summ = obs_export.span_summary(doc)
    assert summ["spans"]["request"]["n"] == 3
    assert summ["spans"]["prefill"]["n"] == 3
    assert summ["spans"]["decode_chunk"]["n"] >= 1
    assert summ["spans"]["dispatch"]["n"] >= 1


SESSION_SPANS = ("admit", "prefill", "commit_prefill", "ensure_pages",
                 "decode_chunk", "dispatch", "fetch", "commit_tokens")


def _span_tree(events):
    """(name, parent name) of each ``Tracer.span`` in opening order: the
    spans nest in the call stack whatever their track, so one stack over
    all tracks rebuilds the tree (lane-long ``request`` spans are not
    ``span()``s and are left out)."""
    out, stack = [], []
    for ev in events:
        if ev["name"] not in SESSION_SPANS or ev["ph"] not in "BE":
            continue
        if ev["ph"] == "B":
            out.append((ev["name"], stack[-1] if stack else None))
            stack.append(ev["name"])
        else:
            assert stack.pop() == ev["name"]
    assert not stack
    return out


def test_engine_session_spans_match_stats():
    """A FakeClock serve exports byte-identical, valid traces whose layer
    spans count what the stats count: one dispatch, fetch and
    commit_tokens per fused dispatch, inside its decode_chunk; one
    prefill per request slotted, inside an admit."""
    eng1, t1 = _traced_serve()
    eng2, t2 = _traced_serve(seed_params=eng1.params)
    e1 = obs_export.export_chrome_trace(t1)
    assert e1 == obs_export.export_chrome_trace(t2)
    doc = json.loads(e1)
    assert obs_export.validate_chrome_trace(doc) == []
    st = eng1.paging_stats
    spans = obs_export.span_summary(doc)["spans"]
    n = {name: spans[name]["n"] for name in SESSION_SPANS}
    assert n["dispatch"] == n["fetch"] == n["commit_tokens"] \
        == n["decode_chunk"] == st["decode_dispatches"] >= 1
    slotted = st["request_timing"]["queue_s"]["count"]
    assert n["prefill"] == n["commit_prefill"] == slotted == 3
    assert n["admit"] >= 1 and n["ensure_pages"] == n["decode_chunk"]
    tree = _span_tree(t1.events)
    assert {p for name, p in tree if name in ("prefill",
                                              "commit_prefill")} \
        == {"admit"}
    assert {p for name, p in tree if name in ("dispatch", "fetch",
                                              "commit_tokens")} \
        == {"decode_chunk"}
    assert {p for name, p in tree if name in ("admit", "ensure_pages",
                                              "decode_chunk")} == {None}
    # within one chunk: launch, then wait, then commit
    chunk_kids = [name for name, p in tree if p == "decode_chunk"]
    assert chunk_kids == ["dispatch", "fetch", "commit_tokens"] \
        * st["decode_dispatches"]


class TickingClock(FakeClock):
    """Advances one second on every read, so any two reads differ."""

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.mark.parametrize("traced", [False, True])
def test_decode_split_counters_lie_within_the_dispatches(traced):
    """decode_enqueue_s + decode_wait_s + decode_commit_s are always kept,
    each phase is non-empty, and together they are at most the wall time
    of the decode chunks (the traced run's decode_chunk spans, on the same
    clock)."""
    clock = TickingClock()
    tracer = Tracer(clock=clock) if traced else None
    cfg, eng = _engine(tracer=tracer)
    eng.clock = clock
    eng.serve(_reqs(cfg, 3))
    st = eng.paging_stats
    split = [st[k] for k in ("decode_enqueue_s", "decode_wait_s",
                             "decode_commit_s")]
    assert all(s >= st["decode_dispatches"] for s in split)
    if traced:
        wall = obs_export.span_summary(tracer)["spans"]["decode_chunk"]
        assert sum(split) < wall["total_s"]
    # the counters merge across replicas by sum
    merged = merge_replica_stats([st, st])
    assert merged["decode_wait_s"] == 2 * st["decode_wait_s"]


def test_straggler_dispatch_logs_its_split(caplog):
    """A dispatch the watchdog flags records where its time went: here
    the launch stalls (the clock jumps inside ``_fused_decode``), so the
    log and the trace's straggler_flagged instant name the enqueue."""
    from repro.train.fault import FaultConfig
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    cfg = get_smoke("granite-3-2b")
    eng = Engine(cfg, ServeConfig(max_seq=S_MAX, n_slots=2, page_size=PS,
                                  decode_chunk=1, eos_id=-1),
                 fault_cfg=FaultConfig(straggler_factor=2.0))
    eng.clock, eng.tracer = clock, tracer
    orig, calls = eng._fused_decode, []

    def fused(*a):
        out = orig(*a)
        calls.append(1)
        clock.advance(10.0 if len(calls) == 8 else 1.0)
        return out

    eng._fused_decode = fused
    session = eng.start_session(_reqs(cfg, 2, max_new=12))
    with caplog.at_level("WARNING", logger="repro.serve.engine"):
        session.drain()
    assert session.straggler_log == [(7, 10.0, 0.0, 0.0)]
    flagged = [e for e in tracer.events if e["name"] == "straggler_flagged"]
    assert [e["args"] for e in flagged] == [
        {"step": 7, "enqueue_s": 10.0, "wait_s": 0.0, "commit_s": 0.0}]
    assert "dispatch at step 7: enqueue 10.000s, wait 0.000s" in caplog.text


def test_profiler_sink_nests_spans_on_the_host_plane(tmp_path):
    """Tracer(profiler=True) under jax.profiler: every span lands on the
    profiler's host plane as ``repro.serve.<name>``, nested as the
    in-memory spans are."""
    import jax

    from repro.obs.trace import PROFILER_PREFIX
    cfg, eng = _engine()
    eng.serve(_reqs(cfg, 3, seed=5))          # compile outside the capture
    tracer = Tracer(clock=eng.clock, profiler=True)
    eng.tracer = tracer
    with jax.profiler.trace(str(tmp_path)):
        eng.serve(_reqs(cfg, 3))
    pb = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert len(pb) == 1
    prof = jax.profiler.ProfileData.from_file(str(pb[0]))
    evs = sorted(((e.start_ns, -e.duration_ns, e.start_ns + e.duration_ns,
                   e.name[len(PROFILER_PREFIX):])
                  for plane in prof.planes if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name.startswith(PROFILER_PREFIX)))
    tree, stack = [], []
    for start, _, end, name in evs:
        while stack and stack[-1][0] <= start:
            stack.pop()
        tree.append((name, stack[-1][1] if stack else None))
        stack.append((end, name))
    assert tree == _span_tree(tracer.events)
    assert {name for name, _ in tree} == set(SESSION_SPANS)


def test_session_stats_are_registry_backed_with_percentiles():
    clock = FakeClock()
    cfg, eng = _engine(clock=clock)
    reqs = _reqs(cfg, 3)
    eng.serve(reqs)
    st = eng.paging_stats
    assert st["completed"] == 3
    timing = st["request_timing"]
    assert timing["latency_s"]["count"] == 3
    assert timing["queue_s"]["count"] == 3
    pcts = st["latency_percentiles"]
    assert set(pcts["latency_s"]) == {"p50", "p95", "p99"}
    # FakeClock ticks once per decode step → latencies are exact step
    # counts, so the percentiles are deterministic values, not just shapes
    assert pcts["latency_s"]["p50"] > 0


def test_metrics_survive_kill_all_snapshot_restore():
    """§7.6 drill: counters and histograms ride the snapshot — restored
    totals continue from the pre-crash values (no reset), re-enqueued
    requests are not re-counted (no double count), and the continuous
    trace cross-checks against the restored stats in at_least mode."""
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    cfg, eng = _engine(clock=clock, tracer=tracer)
    reqs = _reqs(cfg, 4, max_new=6)
    sess = eng.start_session(list(reqs))
    sess.step(4)
    pre = dict(sess.stats)
    pre_timing = {k: dict(v) for k, v in sess.snapshot()
                  ["request_timing"].items()}
    snap = json.loads(json.dumps(sess.snapshot()))
    assert pre["requests"] == 4

    # "new process": fresh engine + fresh host state, params survive
    _, eng2 = _engine(clock=clock, params=eng.params, tracer=tracer)
    sess2, restored = eng2.restore_session(snap)
    st = dict(sess2.stats)
    assert st["requests"] == pre["requests"]        # no double count
    assert st["completed"] == pre["completed"]      # no reset
    assert st["restores"] == 1
    # pre-crash histogram population carried over
    timing = {k: v for k, v in sess2.snapshot()["request_timing"].items()}
    for name, state in pre_timing.items():
        assert timing[name]["count"] >= state["count"]
    sess2.drain()
    final = sess2.stats_snapshot()
    assert final["completed"] == 4
    assert final["requests"] == 4                   # still no double count
    assert final["request_timing"]["latency_s"]["count"] >= 4
    # the continuous trace (same tracer across the "kill") validates and
    # cross-checks: restore rolled counters back to the snapshot, so the
    # trace may hold MORE events than the counters — never fewer
    doc = json.loads(obs_export.export_chrome_trace(tracer))
    assert obs_export.validate_chrome_trace(doc) == []
    assert obs_export.cross_check_counters(doc, final,
                                           mode="at_least") == []
    names = {(ev.get("args") or {}).get("point") or ev["name"]
             for ev in doc["traceEvents"] if ev.get("ph") in ("i", "n")}
    assert {"snapshot", "restore"} <= names


def test_router_stats_trace_cross_check_on_kill():
    """Failover drill with tracing: the migrate/fault/restart instants
    land on the right replica tracks and match the router counters
    exactly."""
    from repro.train.fault import FaultConfig, FaultInjector
    clock = FakeClock()
    tracer = Tracer(clock=clock)
    cfg = get_smoke("granite-3-2b")
    scfg = ServeConfig(max_seq=S_MAX, n_slots=2, page_size=PS,
                       temperature=0.0, eos_id=-1)
    fault_cfg = FaultConfig(max_restarts=3, backoff_s=0.5)
    first = Engine(cfg, scfg, fault_cfg=fault_cfg)
    engines = [first, Engine(cfg, scfg, params=first.params,
                             fault_cfg=fault_cfg)]
    engines[1].fault_injector = FaultInjector(
        fail_at_steps=(("replica", 2),))
    for e in engines:
        e.clock = clock
        _tick_decode(e, clock)
    router = Router(engines, cfg=RouterConfig(n_replicas=2),
                    fault_cfg=fault_cfg, clock=clock, sleep=clock.advance,
                    tracer=tracer)
    reqs = _reqs(cfg, 4, max_new=5)
    router.serve(reqs)
    assert all(r.ok_like for r in reqs)
    st = router.stats()
    assert st["replica_faults"] == 1 and st["migrations"] >= 1
    assert "latency_percentiles" in st
    doc = json.loads(obs_export.export_chrome_trace(tracer))
    assert obs_export.validate_chrome_trace(doc) == []
    assert obs_export.cross_check_counters(doc, st) == []
    # the fault landed on replica1's track, by name
    pnames = {ev["pid"]: ev["args"]["name"] for ev in doc["traceEvents"]
              if ev.get("ph") == "M" and ev.get("name") == "process_name"}
    faults = [ev for ev in doc["traceEvents"]
              if ev.get("name") == "replica_fault" and ev.get("ph") == "i"]
    assert faults and all(pnames[ev["pid"]] == "replica1" for ev in faults)


# ------------------------------------------------- timing provenance


def test_time_us_warmup_zero_and_blocking():
    """Satellite regression: warmup=0 must run zero warmup calls (the old
    ``range(max(warmup, 1))`` forced one), and every warmup iteration is
    blocked, not just dispatched."""
    from repro.core.timing import time_us
    calls = []

    def fn():
        calls.append(1)
        return np.zeros(1)

    time_us(fn, repeats=2, warmup=0)
    assert len(calls) == 2
    calls.clear()
    time_us(fn, repeats=2, warmup=3)
    assert len(calls) == 5


def test_timing_source_provenance(monkeypatch, deterministic_autotune):
    """The autotuner records HOW it timed: a monkeypatched ``time_us``
    (the deterministic_autotune fixture) must force wallclock provenance,
    and the recorded TuneResult carries it."""
    from repro.kernels import autotune
    # fixture patched autotune.time_us → source must report wallclock
    assert autotune.timing_source() == "wallclock"
    rng = np.random.default_rng(0)
    a = (rng.uniform(size=(64, 64)) < 0.1).astype(np.float32)
    result = autotune.autotune_spmv(a, repeats=1)
    assert result.timing_source == "wallclock"
    with pytest.raises(ValueError):
        autotune.set_timing_source("bogus")
    autotune.set_timing_source("wallclock")
    try:
        assert autotune.timing_source() == "wallclock"
    finally:
        autotune.set_timing_source("auto")
