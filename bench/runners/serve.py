"""Serving through ``repro.serve.Engine`` sessions, driven by a traffic file.

Two modes, chosen by the traffic file's ``mode``:

* ``open_loop`` — requests arrive on a schedule whatever the engine does
  (``submit`` when due, ``step`` in between).  Arrivals start ``lead_s``
  before the window, so it opens on a loaded engine, and go on after it
  until every request due in the window has finished.  Latencies run from
  when a request was due; a token is visible when the ``step()`` that
  produced it returns.
* ``offline`` — every request is queued before the window, so slots never
  wait for work; the window counts the output tokens generated in it.

The request sizes come from the traffic file: a fixed table of prompt
lengths and a lognormal of output lengths, drawn with the arrival times
from the file's ``base_seed``.  The run's ``--seed`` only decides which
size arrives when, and draws the token ids and the weights, so every seed
serves the same work at the same moments.

Correctness: after the window, a sample of finished requests drawn from
the seed, the longest among them, is run through the float32 reference
(``configs/granite.reference.py``); the widest gap by which a served
token's logit lies below the reference's best is held to its limit.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import time

import numpy as np

import harness

# ------------------------------------------------------------------ traffic


def _lognormal_lengths(rng, spec: dict, n: int) -> np.ndarray:
    """Lognormal lengths with the given mean and sigma (so the median is
    ``mean · exp(-sigma² / 2)``), at least ``min``."""
    sigma = spec["sigma"]
    raw = rng.lognormal(math.log(spec["mean"]) - sigma ** 2 / 2, sigma, n)
    return np.maximum(np.rint(raw), spec["min"]).astype(np.int64)


@dataclasses.dataclass
class Planned:
    due: float              # seconds from the schedule's origin
    prompt: np.ndarray      # int32 token ids
    max_new: int
    in_window: bool


def plan_requests(traffic: dict, seed: int, seconds: float,
                  vocab: int) -> list:
    """The run's requests in due order.

    Everything but the order is fixed by the file's ``base_seed``: the
    arrival times (Poisson at ``rate_per_s``; the window's
    ``round(rate · seconds)`` gaps scaled to fill it exactly, and the
    arrivals ``lead_s`` before and ``tail_s`` after it) and the multiset of
    request sizes.  An answer is cut so that prompt + answer is at most
    ``output_len.max_total``.  ``seed`` decides which size arrives when,
    and draws the token ids.  Offline traffic queues ``queue_requests``,
    all due at 0.
    """
    base = np.random.default_rng(traffic["base_seed"])
    rng = np.random.default_rng([seed, 7])
    table = np.asarray(traffic["prompt_len_table"], np.int64)
    out_spec = traffic["output_len"]

    def sizes(n):
        idx = base.integers(0, len(table), n)
        answer = np.minimum(_lognormal_lengths(base, out_spec, n),
                            out_spec["max_total"] - table[idx])
        s = np.stack([idx, answer], 1)
        return s[rng.permutation(n)]

    if traffic["mode"] == "open_loop":
        rate, lead = float(traffic["rate_per_s"]), float(traffic["lead_s"])
        n_win = max(1, int(round(rate * seconds)))
        gaps = base.exponential(1.0, n_win)
        due_win = lead + seconds * (np.cumsum(gaps) - gaps) / gaps.sum()
        lead_due = lead - np.cumsum(base.exponential(
            1.0 / rate, int(np.ceil(rate * lead * 1.5)) + 1))
        lead_due = lead_due[lead_due >= 0]
        tail_due = lead + seconds + np.cumsum(base.exponential(
            1.0 / rate, int(np.ceil(rate * float(traffic["tail_s"]) * 1.5))
            + 1))
        groups = [(lead_due, False), (due_win, True), (tail_due, False)]
    else:
        groups = [(np.zeros(int(traffic["queue_requests"])), True)]
    out = []
    for dues, inside in groups:
        for d, (ti, n_out) in zip(dues, sizes(len(dues))):
            out.append((float(d), int(table[ti]), int(n_out), inside))
    out.sort(key=lambda r: r[0])
    return [Planned(d, rng.integers(0, vocab, p).astype(np.int32), n, w)
            for d, p, n, w in out]


# ------------------------------------------------------------------ weights


def make_params(abstract, seed: int):
    """Weights for the program's parameter tree, made on the device in one
    jitted call from the seed, in the tree's own dtype: norm scales 1, the
    embedding N(0, 1/d), every other matrix N(0, 1/fan_in).  Layer-stacked
    leaves are drawn one layer at a time, so no temporary of a whole stack
    is held."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    word = int(np.random.SeedSequence([seed, 11]).generate_state(1)[0])

    def one(key, path, leaf):
        names = [str(getattr(k, "key", k)) for k in path]
        shape, dtype = leaf.shape, leaf.dtype
        if names[-1] == "scale":
            return jnp.ones(shape, dtype)
        stacked = names[0] == "stack" and names[1] == "body"
        inner = shape[1:] if stacked else shape
        std = inner[-1] ** -0.5 if names[-1] == "table" else \
            inner[-2] ** -0.5

        def draw(k):
            return (std * jax.random.normal(k, inner, jnp.float32)
                    ).astype(dtype)
        if stacked:
            return jax.lax.map(draw, jax.random.split(key, shape[0]))
        return draw(key)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(leaves))
        return [one(k, path, leaf)
                for k, (path, leaf) in zip(keys, leaves)]

    vals = build(jax.random.PRNGKey(word % (2 ** 31)))
    return jax.tree_util.tree_unflatten(treedef, vals)


def program_config(cfg: dict):
    """The program's model config for this file, checked against it."""
    import dataclasses as dc
    from repro.configs import get_config
    pc = get_config(cfg["program_config"])
    s = cfg["serve"]
    pc = dc.replace(pc, dtype=s["compute_dtype"],
                    param_dtype=s["param_dtype"],
                    kv_cache_dtype=s["kv_cache_dtype"])
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "n_layers": cfg["num_hidden_layers"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "vocab": cfg["vocab_size"],
            "rope_theta": cfg["rope_theta"],
            "tie_embeddings": cfg["tie_word_embeddings"]}
    got = {k: getattr(pc, k) for k in want}
    if got != want:
        raise harness.SetupError(f"the program's {cfg['program_config']} "
                                 f"differs from the file: {got} != {want}")
    return pc


# ------------------------------------------------------------------- engine


class Tracker:
    """Host-side timestamps of every request: submitted, first token
    visible, finished — each taken when the call that made it returns."""

    def __init__(self, session):
        self.session = session
        self.live = {}          # id -> (planned, Request, submit time)
        self.rows = []          # finished: dicts
        self.first = {}         # id -> first token visible
        self.seen = {}          # id -> tokens seen at last look
        self.decode_ctx = 0     # Σ context over decoded tokens
        self.decode_tokens = 0  # tokens made by decode steps
        self.prefill_tokens = []

    def submit(self, plan, req, now):
        self.session.submit(req)
        self.live[id(req)] = (plan, req, now)
        self.seen[id(req)] = 0

    def look(self, now):
        """After a step(): stamp first tokens and completions; count the
        tokens made since the last look and the context they attended."""
        made = 0
        for key in list(self.live):
            plan, req, t_sub = self.live[key]
            n = len(req.out or ())
            prev = self.seen[key]
            if n > prev:
                if prev == 0:
                    self.first[key] = now
                    self.prefill_tokens.append(len(req.tokens))
                    dec = range(1, n)
                else:
                    dec = range(prev, n)
                # the decode step writing out[j] attends prompt + j tokens
                self.decode_ctx += sum(len(req.tokens) + j for j in dec)
                self.decode_tokens += len(dec)
                made += n - prev
                self.seen[key] = n
            if req.done:
                self.rows.append({"plan": plan, "req": req, "submit": t_sub,
                                  "first": self.first.pop(key, None),
                                  "last": now})
                del self.live[key], self.seen[key]
        return made

    def take_counts(self):
        counts = {"decode_context_tokens": self.decode_ctx,
                  "decode_tokens": self.decode_tokens,
                  "prefill_lengths": self.prefill_tokens}
        self.decode_ctx, self.decode_tokens, self.prefill_tokens = 0, 0, []
        return counts


def _warm(engine, traffic, serve_cfg, vocab, Request):
    """Admit one request per slot, cycling through every prompt length, and
    run them out: every prefill length, every slot's commit and the fused
    decode compile (or load from the cache) here and not in the window."""
    table = list(traffic["prompt_len_table"])
    rng = np.random.default_rng(0)
    n = max(serve_cfg.n_slots, len(table))
    session = engine.start_session()
    reqs = [Request(tokens=rng.integers(0, vocab, table[i % len(table)]
                                        ).astype(np.int32),
                    max_new_tokens=serve_cfg.decode_chunk + 2)
            for i in range(n)]
    for req in reqs:
        session.submit(req)
    session.drain()
    bad = [r for r in reqs if not r.ok_like]
    if bad:
        raise RuntimeError(f"{len(bad)} of {n} warm-up requests failed; "
                           f"first: {bad[0].status}: {bad[0].error}")


def _percentile(xs, q):
    if not len(xs):
        return float("nan")
    return float(np.percentile(np.asarray(xs, np.float64), q))


class Server:
    """The system under test, built once: weights, engine, warmed shapes."""

    def __init__(self, ctx):
        import jax
        from repro.models import LanguageModel
        from repro.serve import Engine, Request, ServeConfig

        cfg, traffic, ph = ctx.cell.config, ctx.cell.traffic, ctx.phases
        sc = cfg["serve"]
        pc = program_config(cfg)
        self.Request = Request
        self.cfg, self.traffic = cfg, traffic
        self.serve_cfg = ServeConfig(
            max_seq=sc["max_seq"], n_slots=sc["n_slots"], kv_layout="paged",
            page_size=sc["page_size"], n_pages=sc["n_pages"],
            decode_chunk=sc["decode_chunk"], eos_id=sc["eos_id"],
            admission_policy=sc["admission_policy"], seed=0)
        ph.mark("program config")
        self.abstract = LanguageModel(pc).abstract_params()
        self.params = make_params(self.abstract, ctx.seed)
        jax.block_until_ready(self.params)
        ph.mark("weights on device")
        self.engine = Engine(pc, self.serve_cfg, params=self.params)
        self.engine.clock = time.perf_counter
        ph.mark("engine")
        c0 = ctx.compiles.count
        _warm(self.engine, traffic, self.serve_cfg, cfg["vocab_size"],
              Request)
        gc.collect()                   # free the warm-up session's pool
        ph.mark(f"warm-up ({ctx.compiles.count - c0} programs compiled "
                f"or loaded)")

    def reseed(self, seed: int) -> None:
        """New weights for another seed (calibration reads many seeds in
        one process)."""
        import jax
        self.params = self.engine.params = None
        gc.collect()
        self.params = make_params(self.abstract, seed)
        self.engine.params = self.params
        jax.block_until_ready(self.params)


class Window:
    """One measured window of a traffic mix against a :class:`Server`."""

    def __init__(self, server, traffic, seed, seconds, capture=None,
                 trace_s=0.0, on_window_open=None):
        self.server, self.traffic = server, traffic
        self.plans = plan_requests(traffic, seed, seconds,
                                   server.cfg["vocab_size"])
        self.seconds = seconds
        self.capture, self.trace_s = capture, trace_s
        self.on_window_open = on_window_open
        self.session = server.engine.start_session()
        self.tracker = Tracker(self.session)
        self.traced = None
        self.lateness = []
        self.steps0 = self.session.stats["decode_steps"]
        self.w1 = float("inf")
        self.tr_start = None
        self.made_in_window = 0         # output tokens seen by w1
        self.queued_at_close = None     # queue length at the window's end
        self.w0 = float("inf")
        self.step_times = []            # (start, seconds) of each step()
        self.gc_log = []                # (generation, start, seconds)
        self._gc_t0 = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        elif self._gc_t0 is not None:
            self.gc_log.append((info["generation"], self._gc_t0,
                                now - self._gc_t0))

    def pauses(self) -> str:
        """Host pauses in the window: the slowest step() calls and the
        garbage collector's runs, in seconds from the window's opening."""
        w0, w1 = self.w0, self.w0 + self.seconds
        steps = [(t - w0, d) for t, d in self.step_times if w0 <= t < w1]
        gcs = [(g, t - w0, d) for g, t, d in self.gc_log if w0 <= t < w1]
        if not steps:
            return "no steps in the window"
        slow = sorted(steps, key=lambda x: -x[1])[:3]
        per_gen = [sum(1 for g, _, _ in gcs if g == k) for k in range(3)]
        worst = max(gcs, key=lambda x: x[2], default=None)
        return (f"step() median {1e3 * float(np.median([d for _, d in steps])):.1f}"
                f" ms over {len(steps)}, slowest "
                + ", ".join(f"{1e3 * d:.0f} ms at {t:.2f} s" for t, d in slow)
                + f"; gc runs gen0/1/2 {per_gen[0]}/{per_gen[1]}/"
                f"{per_gen[2]}, gen2 {1e3 * sum(d for g, _, d in gcs if g == 2):.0f}"
                f" ms in all"
                + (f", longest {1e3 * worst[2]:.0f} ms (gen{worst[0]}) at "
                   f"{worst[1]:.2f} s" if worst else ""))

    # ----------------------------------------------------------- tracing
    def _start_trace(self, now):
        if self.capture is not None:
            self.capture.start()
            self.tracker.take_counts()
            self.tr_start = (now, self.session.stats["decode_steps"])

    def _maybe_stop_trace(self, now):
        if self.tr_start is None or self.traced is not None:
            return
        if now - self.tr_start[0] < self.trace_s:
            return
        counts = self.tracker.take_counts()
        reduced = self.capture.stop()
        self.traced = (reduced, dict(
            counts, t0=self.tr_start[0], t1=now,
            decode_steps=self.session.stats["decode_steps"]
            - self.tr_start[1]))

    def _step(self):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.engine_step"):
            self.session.step(self.server.serve_cfg.decode_chunk)
        now = time.perf_counter()
        self.step_times.append((t0, now - t0))
        made = self.tracker.look(now)
        self._maybe_stop_trace(now)
        if now <= self.w1:
            self.made_in_window += made
        elif self.queued_at_close is None:
            self.queued_at_close = self.session.num_queued
        return now, made

    # ------------------------------------------------------------- modes
    def offline(self):
        """Queue everything, run ``lead_s``, then count tokens made in
        ``seconds``.  Returns the end-to-end metrics."""
        Request, tr = self.server.Request, self.tracker
        t_origin = time.perf_counter()
        for pl in self.plans:
            tr.submit(pl, Request(tokens=pl.prompt,
                                  max_new_tokens=pl.max_new), t_origin)
        lead_end = t_origin + float(self.traffic["lead_s"])
        while time.perf_counter() < lead_end:
            self._step()
        tr.take_counts()
        if self.on_window_open is not None:
            self.on_window_open()
        w0 = self.w0 = time.perf_counter()
        self._start_trace(w0)
        made = 0
        while True:
            now, k = self._step()
            made += k
            if now - w0 >= self.seconds:
                break
            if self.session.idle:
                raise RuntimeError("the offline queue ran dry inside the "
                                   "window (statuses: "
                                   f"{self.statuses()})")
        self.window_s = time.perf_counter() - w0
        self.window_rows = []
        return {"tokens_per_s": made / self.window_s}

    def open_loop(self):
        """Arrivals on the schedule; run until every request due in the
        window has finished (or ``drain_limit_s`` after it)."""
        import jax
        Request, tr, plans = self.server.Request, self.tracker, self.plans
        t_origin = time.perf_counter()
        lead = float(self.traffic["lead_s"])
        w0, w1 = t_origin + lead, t_origin + lead + self.seconds
        deadline = w1 + float(self.traffic["drain_limit_s"])
        self.w1 = w1
        i, opened = 0, False
        while True:
            now = time.perf_counter()
            if not opened and now >= w0:
                opened = True
                self.w0 = now
                if self.on_window_open is not None:
                    self.on_window_open()
                self.made_in_window = 0
                self._start_trace(now)
            while i < len(plans) and t_origin + plans[i].due <= now:
                pl = plans[i]
                req = Request(tokens=pl.prompt, max_new_tokens=pl.max_new)
                req.arrival_t = t_origin + pl.due
                tr.submit(pl, req, now)
                if pl.in_window:
                    self.lateness.append(now - req.arrival_t)
                i += 1
            if self.session.idle:
                if i >= len(plans):
                    break
                nxt = t_origin + plans[i].due
                if not opened:
                    nxt = min(nxt, w0)
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(0.0, nxt - time.perf_counter()))
                continue
            now, _ = self._step()
            if now >= w1 and not any(p.in_window for p, _, _ in
                                     tr.live.values()) \
                    and not any(p.in_window for p in plans[i:]):
                break
            if now > deadline:
                break
        self.window_s = self.seconds
        rows = [r for r in tr.rows if r["plan"].in_window]
        self.window_rows = rows
        ok = [r for r in rows if r["req"].ok_like and r["first"] is not None]
        self.ttft = [(r["first"] - r["req"].arrival_t) * 1e3 for r in ok]
        self.tpot = [(r["last"] - r["first"]) * 1e3 / (len(r["req"].out) - 1)
                     for r in ok if len(r["req"].out) > 1]
        return {"ttft_p95_ms": _percentile(self.ttft, 95),
                "tpot_p95_ms": _percentile(self.tpot, 95)}

    def counts(self):
        """(attempted, failed) requests of the window."""
        rows = self.tracker.rows
        if self.traffic["mode"] == "offline":
            started = [r for r in self.tracker.live.values()
                       if len(r[1].out or ())]
            return (len(rows) + len(started),
                    sum(1 for r in rows if not r["req"].ok_like))
        attempted = sum(1 for p in self.plans if p.in_window)
        return attempted, attempted - sum(1 for r in self.window_rows
                                          if r["req"].ok_like)

    def statuses(self) -> str:
        from collections import Counter
        rows = self.tracker.rows
        errors = [r["req"].error for r in rows if r["req"].error]
        return (f"{dict(Counter(r['req'].status for r in rows))}"
                + (f"; first error: {errors[0][:300]}" if errors else ""))

    def close(self):
        """Free the session's KV pool; keep the finished requests."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        self.stats = dict(self.session.stats)
        self.finished = [r for r in self.tracker.rows if r["req"].ok_like]
        self.session = self.tracker.session = None
        gc.collect()


def run(ctx) -> harness.RunOutput:
    import devtrace

    traffic, ph = ctx.cell.traffic, ctx.phases
    server = Server(ctx)
    marks = {}

    def opened():
        marks["setup_s"] = ph.mark("lead (load before the window)")
        marks["compiles"] = ctx.compiles.count

    win = Window(server, traffic, ctx.seed, ctx.seconds,
                 capture=devtrace.Capture() if ctx.trace else None,
                 trace_s=float(traffic["trace_seconds"]),
                 on_window_open=opened)
    e2e = win.offline() if traffic["mode"] == "offline" else win.open_loop()
    e2e["setup_s"] = marks["setup_s"]
    compiles_in_window = ctx.compiles.count - marks["compiles"]
    device = harness.device_record()
    attempted, failed = win.counts()
    win.close()
    checks, ref_note = _check(ctx, server.cfg, traffic, server.params,
                              win.finished)
    st = win.stats
    notes = {
        "setup phases": ph.line(),
        "window": (f"{win.window_s:.3f} s; {compiles_in_window} "
                   f"compilations in the window; "
                   f"{st['decode_steps'] - win.steps0} decode steps, "
                   f"{st['decode_dispatches']} dispatches in the session; "
                   f"preemptions {st['preemptions']}, admission deferrals "
                   f"{st['admission_deferrals']}, rejected {st['rejected']}"),
        "requests": f"attempted {attempted}, completed "
                    f"{attempted - failed}, failed or refused {failed}; "
                    f"statuses of all finished: {win.statuses()}",
        "reference": ref_note,
        "host pauses": win.pauses(),
    }
    if win.lateness:
        notes["generator lateness"] = (
            f"p50 {1e3 * _percentile(win.lateness, 50):.2f} ms, max "
            f"{1e3 * max(win.lateness):.2f} ms over {len(win.lateness)} "
            f"window requests")
    if traffic["mode"] != "offline":
        notes["latency"] = (
            f"ttft p50 {_percentile(win.ttft, 50):.1f} ms p95 "
            f"{e2e['ttft_p95_ms']:.1f} ms over {len(win.ttft)}; tpot p50 "
            f"{_percentile(win.tpot, 50):.2f} ms p95 "
            f"{e2e['tpot_p95_ms']:.2f} ms over {len(win.tpot)}")
    layer = {"model": server.cfg, "device_kind": device["kind"],
             "compiles_in_window": compiles_in_window}
    reduced = None
    if win.traced is not None:
        reduced, counts = win.traced
        layer.update(counts)
        if traffic["mode"] != "offline":
            t0, t1 = counts["t0"], counts["t1"]
            layer["queue_s"] = [          # requests slotted in the slice
                r["req"].queue_s for r in win.tracker.rows
                if t0 <= r["req"].arrival_t + r["req"].queue_s <= t1]
    return harness.RunOutput(
        end_to_end=e2e, attempted=attempted, failed=failed, checks=checks,
        layer=layer, device=device, trace=reduced, notes=notes)


# -------------------------------------------------------------- reference


def sample_rows(finished: list, seed: int, spec: dict) -> list:
    """The longest finished request and others drawn from the seed, until
    ``min_served_tokens`` served tokens or ``max_requests`` requests."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -len(finished[i]["req"].out))
    rest = list(np.random.default_rng([seed, 13]).permutation(order[1:]))
    picked, tokens = [order[0]], len(finished[order[0]]["req"].out)
    for i in rest:
        if tokens >= spec["min_served_tokens"] \
                or len(picked) >= spec["max_requests"]:
            break
        picked.append(int(i))
        tokens += len(finished[i]["req"].out)
    return [finished[i] for i in picked]


def logit_gaps(ref, params, cfg: dict, seqs: list, length: int,
               batch: int, control: bool = False) -> np.ndarray:
    """Per served token: max over the vocabulary of the reference's logits
    minus the reference's logit of the token.  ``seqs`` are (prompt,
    served) pairs.  With ``control`` the token is the one the float8
    control puts first at that position, not the served one."""
    import jax
    import jax.numpy as jnp
    fwd = jax.jit(lambda p, t: ref.forward(p, t, cfg))
    ctl = jax.jit(lambda p, t: ref.forward(p, t, cfg, quantize=True))
    gaps = []
    for b0 in range(0, len(seqs), batch):
        block = seqs[b0:b0 + batch]
        toks = np.zeros((batch, length), np.int32)
        for j, (prompt, out) in enumerate(block):
            seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
            toks[j, :len(seq)] = seq
        logits = np.asarray(fwd(params, jnp.asarray(toks)))
        picks = np.asarray(jnp.argmax(ctl(params, jnp.asarray(toks)), -1)) \
            if control else None
        for j, (prompt, out) in enumerate(block):
            lo = len(prompt) - 1
            pos = slice(lo, lo + len(out))
            lg = logits[j, pos]
            tok = picks[j, pos] if control else np.asarray(out)
            gaps.append(lg.max(-1) - np.take_along_axis(
                lg, tok[:, None], -1)[:, 0])
    return np.concatenate(gaps) if gaps else np.zeros(0)


def _check(ctx, cfg, traffic, params, finished):
    t0 = time.perf_counter()
    spec = traffic["sample"]
    rows = sample_rows(finished, ctx.seed, spec)
    seqs = [(r["req"].tokens, r["req"].out) for r in rows]
    gaps = logit_gaps(ctx.cell.reference(), params, cfg, seqs,
                      cfg["serve"]["max_seq"], spec["batch"])
    value = float(gaps.max()) if len(gaps) else float("nan")
    if len(gaps) and not np.isfinite(gaps).all():
        value = float("nan")
    note = (f"float32 reference over {len(rows)} requests, {len(gaps)} "
            f"served tokens (longest {max((len(s[1]) for s in seqs), default=0)}) "
            f"took {time.perf_counter() - t0:.1f} s after the window")
    return [harness.Check("max_logit_gap", value,
                          traffic["limits"]["max_logit_gap"])], note


# ------------------------------------------------------ calibration, sweep


def calibrate(cell, args) -> list:
    """Per seed, in one process: a window at the cell's load, then the
    program's widest logit gap and the float8 control's on the same
    sampled requests."""
    import run as run_mod
    ctx = run_mod.Context(cell, argparse_ns(args.seeds[0], args.seconds),
                          harness.Phases(time.perf_counter()),
                          harness.CompileCounter())
    server = Server(ctx)
    ref = cell.reference()
    out = []
    for n, seed in enumerate(args.seeds):
        if n:
            server.reseed(seed)
        win = Window(server, cell.traffic, seed, args.seconds)
        if cell.traffic["mode"] == "offline":
            win.offline()
        else:
            win.open_loop()
        win.close()
        rows = sample_rows(win.finished, seed, cell.traffic["sample"])
        seqs = [(r["req"].tokens, r["req"].out) for r in rows]
        length, batch = cell.config["serve"]["max_seq"], \
            cell.traffic["sample"]["batch"]
        prog = logit_gaps(ref, server.params, cell.config, seqs, length,
                          batch)
        ctl = logit_gaps(ref, server.params, cell.config, seqs, length,
                         batch, control=True)
        out.append({"seed": seed, "requests": len(rows),
                    "tokens": int(len(prog)),
                    "program_max_logit_gap": float(prog.max()),
                    "control_max_logit_gap": float(ctl.max()),
                    "control_tokens_changed": int((ctl > 0).sum())})
        print(json.dumps(out[-1]), flush=True)
    return out


def argparse_ns(seed, seconds):
    import argparse
    return argparse.Namespace(seed=seed, seconds=seconds, trace=0)


def sweep(cell, args) -> list:
    """Offered rate against what the engine sustains: one window per rate
    in one process (``bench/sweep.py``)."""
    import run as run_mod
    ctx = run_mod.Context(cell, argparse_ns(args.seed, args.seconds),
                          harness.Phases(time.perf_counter()),
                          harness.CompileCounter())
    server = Server(ctx)
    out = []
    for rate in args.rates:
        # the knee is read from the window; past it the drain says nothing
        traffic = dict(cell.traffic, rate_per_s=rate,
                       drain_limit_s=min(20, cell.traffic["drain_limit_s"]))
        win = Window(server, traffic, args.seed, args.seconds)
        win.open_loop()
        n_win = sum(1 for p in win.plans if p.in_window)
        done = sum(1 for r in win.window_rows if r["req"].ok_like)
        row = {"rate_per_s": rate, "window_s": args.seconds,
               "requests_due": n_win, "completed": done,
               "output_tokens_per_s": win.made_in_window / args.seconds,
               "queued_at_window_end": win.queued_at_close,
               "ttft_p50_ms": _percentile(win.ttft, 50),
               "ttft_p95_ms": _percentile(win.ttft, 95),
               "tpot_p50_ms": _percentile(win.tpot, 50),
               "tpot_p95_ms": _percentile(win.tpot, 95),
               "statuses": win.statuses()}
        win.close()
        out.append(row)
        print(json.dumps(row), flush=True)
    return out
