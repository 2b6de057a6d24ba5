"""Serving engine: batched prefill + decode with KV caches.

Production structure on the latency path:

* jit'd ``prefill`` (prompt → logits + caches) and ``decode`` (one token,
  donated cache) — the same functions the decode dry-run cells lower, so
  serving perf analysis and the roofline table talk about identical HLO.
* **Continuous mixed-length batching**: a fixed decode batch of
  ``n_slots`` with a **per-slot KV position index**, so requests of any
  prompt length share one live batch and a finished slot immediately pulls
  the next queued request — no cache resets, no drain barriers.
* **Paged KV cache** (``kv_layout="paged"``, the default — DESIGN.md §6,
  ``serve/paging.py``): K/V live in a shared page pool addressed through
  per-slot block tables; pages are allocated lazily as slots grow and
  freed on completion, so resident KV memory tracks *actual* sequence
  lengths.  ``kv_layout="dense"`` keeps the per-slot ``(n_slots, S_max)``
  slabs (still per-slot-indexed, so mixed lengths work there too) — the
  layout ``generate()`` and training-eval equivalence use.
* **Graceful overload** (DESIGN.md §6.4): admission reserves prompt pages
  only (``admission_policy="prompt"``) and decode-boundary pool
  exhaustion **recompute-preempts** the latest-admitted slot instead of
  blocking; oversized requests are rejected per-request, mid-request
  faults fail only the affected request, and per-request deadlines shed
  expired work — each terminal outcome lands in ``Request.status``
  (``worst_case`` admission + ``strict=True`` restore the PR 5
  defer/fail-stop behavior).  A ``train/fault.py`` Watchdog flags
  straggler decode steps into ``paging_stats``.
* Sampling: greedy / temperature / top-k, fp32 logits.
"""
from __future__ import annotations

import dataclasses
import hashlib
import logging
import time
from collections import deque
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import LanguageModel
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve import device_loop, paging

__all__ = ["ServeConfig", "Engine", "EngineSession", "Request",
           "request_to_state", "request_from_state"]

log = logging.getLogger(__name__)


def request_to_state(req: "Request", now: float) -> Dict:
    """JSON-serializable crash-consistent state of one undone request
    (DESIGN.md §7.6).  KV tensors are NOT captured — the generated
    prefix in ``out`` is enough for the recompute path to resume the
    stream exactly.  The arrival timestamp is stored as an *age* so the
    restoring process can rebase it onto its own clock (deadlines keep
    running across the restart)."""
    return {
        "tokens": np.asarray(req.tokens, np.int32).tolist(),
        "max_new_tokens": int(req.max_new_tokens),
        "out": None if req.out is None else [int(t) for t in req.out],
        "preemptions": int(req.preemptions),
        "retries": int(req.retries),
        "deadline_s": req.deadline_s,
        "age_s": 0.0 if req.arrival_t is None
        else float(now - req.arrival_t),
        "queue_s": float(req.queue_s),
        "prefill_s": float(req.prefill_s),
    }


def request_from_state(state: Dict, now: float) -> "Request":
    """Inverse of :func:`request_to_state`: rebuild a live
    :class:`Request` in the restoring process, arrival rebased to
    ``now - age_s``."""
    req = Request(tokens=np.asarray(state["tokens"], np.int32),
                  max_new_tokens=state["max_new_tokens"])
    req.out = None if state.get("out") is None else list(state["out"])
    req.preemptions = state.get("preemptions", 0)
    req.retries = state.get("retries", 0)
    req.deadline_s = state.get("deadline_s")
    req.arrival_t = now - state.get("age_s", 0.0)
    req.queue_s = state.get("queue_s", 0.0)
    req.prefill_s = state.get("prefill_s", 0.0)
    if req.preemptions:
        req.status = f"preempted_{req.preemptions}"
    return req


@dataclasses.dataclass
class ServeConfig:
    max_seq: int = 1024
    n_slots: int = 4                    # decode batch size
    temperature: float = 0.0            # 0 → greedy
    top_k: int = 0
    eos_id: int = -1                    # -1 → run to max_new_tokens
    seed: int = 0
    # --- KV-cache layout (DESIGN.md §6) ---
    kv_layout: str = "paged"            # paged | dense
    page_size: int = 16                 # tokens per KV page
    n_pages: int = 0                    # 0 → auto: dense capacity + null page
    # --- fused decode loop (DESIGN.md §7.1) ---
    # max decode steps per fused on-device dispatch; 1 restores the
    # stepwise one-dispatch-per-token cadence (host sync every step)
    decode_chunk: int = 8
    # --- overload behavior (DESIGN.md §6.4) ---
    # prompt     → admit on the resident tokens' pages only and
    #              recompute-preempt a victim at decode-boundary exhaustion
    # worst_case → reserve each request's worst case at admission and
    #              defer admissions when the pool can't cover it (PR 5)
    admission_policy: str = "prompt"
    # strict=True restores fail-stop serving: oversized requests and
    # mid-request exceptions raise out of serve() (the pre-overload-layer
    # behavior) instead of failing only the affected request.
    strict: bool = False
    # default completion deadline (seconds from serve() entry) applied to
    # requests that don't carry their own ``deadline_s``; 0 → no deadline.
    deadline_s: float = 0.0
    # --- KV-page integrity (DESIGN.md §7.6) ---
    # kv_integrity=True arms two independent detectors for silent
    # device-memory corruption in the long-lived page pools: per-page
    # crc32 checksums recorded at chunk-commit boundaries and verified
    # before every dispatch (corruption at rest), and a NaN/Inf logit
    # screen in the commit loop (corruption that strikes inside the
    # dispatch window).  Detection quarantines the page and
    # recompute-preempts exactly the requests that touched it.
    kv_integrity: bool = False


@dataclasses.dataclass
class Request:
    """One serving request.

    Terminal state (set by ``serve``/the router): ``done`` flips True
    exactly once, and ``status`` says how the request ended —

    * ``"ok"``            — completed normally;
    * ``"preempted_<n>"`` — completed normally after ``n`` recompute
      preemptions (still a success — ``ok_like`` covers both);
    * ``"rejected"``      — refused at admission (budget overflows
      ``max_seq``, or its worst-case page count exceeds the whole pool);
    * ``"failed"``        — a mid-request exception (prefill/decode fault)
      killed this request, or a router-migrated request exhausted its
      retry budget; the rest of the batch kept serving;
    * ``"timed_out"``     — its ``deadline_s`` passed (queued or
      mid-decode); partial output is kept in ``out``;
    * ``"shed"``          — refused at the router's door: the bounded
      router queue was full (backpressure, DESIGN.md §7) — the request
      never reached an engine.

    ``error`` carries the reason for the failure statuses.
    ``deadline_s`` is a completion deadline in seconds measured from the
    request's **arrival** — the moment it was submitted to a session or
    router (``arrival_t``; batch-submitted ``serve()`` requests arrive at
    call entry, keeping the original semantics).  It bounds queue wait +
    processing and keeps running across router migrations; ``None`` falls
    back to ``ServeConfig.deadline_s``.

    ``retries`` counts router migrations of this request off faulted
    replicas (bounded by the router's ``FaultConfig.max_restarts``).

    Timing fields (all seconds, set by ``serve``):

    * ``queue_s``   — time from arrival until this request was first
      slotted (head-of-line wait).
    * ``prefill_s`` — its own (first) prefill forward duration.
    * ``latency_s`` — end-to-end latency measured from *this request's own
      processing start* (first slotting; re-measured from re-slotting
      after a router migration) to its completion — NOT from the start of
      the whole serve call, which would bill earlier requests' work to
      late-slotted ones.
    """
    tokens: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 32
    out: Optional[List[int]] = None
    done: bool = False
    deadline_s: Optional[float] = None
    status: str = "ok"
    error: Optional[str] = None
    preemptions: int = 0
    retries: int = 0
    arrival_t: Optional[float] = None
    latency_s: float = 0.0
    queue_s: float = 0.0
    prefill_s: float = 0.0

    @property
    def ok_like(self) -> bool:
        """Completed with full output (possibly after preemptions)."""
        return self.done and (self.status == "ok"
                              or self.status.startswith("preempted"))


class Engine:
    def __init__(self, model_cfg, serve_cfg: ServeConfig, params=None,
                 fault_cfg=None, fault_injector=None):
        from repro.train.fault import FaultConfig
        self.cfg = serve_cfg
        # fault/overload knobs (DESIGN.md §6.4): the watchdog config drives
        # straggler flagging of decode steps; an engine-level injector (or
        # one passed to serve()) exercises per-request fault isolation.
        self.fault_cfg = fault_cfg if fault_cfg is not None else FaultConfig()
        self.fault_injector = fault_injector
        # injectable clock: every serve() timestamp (deadlines, latency,
        # watchdog) flows through this, so tests drive deadlines with a
        # fake timer instead of wall-clock sleeps.
        self.clock = time.time
        # observability (DESIGN.md §13): attach a repro.obs.trace.Tracer
        # (and a per-replica label) BEFORE start_session() and every
        # session event lands on this replica's track; None keeps the
        # no-op fast path.  The router attaches these for its fleet.
        self.tracer = None
        self.trace_label = "replica0"
        self.model = LanguageModel(model_cfg)
        self.params = params if params is not None else \
            self.model.init(jax.random.PRNGKey(serve_cfg.seed))
        # one decode-step definition (device_loop.make_decode_step) feeds
        # both the per-step jit (generate() and the stepwise oracle) and
        # the fused lax.while_loop chunk runner EngineSession dispatches
        self._decode = jax.jit(device_loop.make_decode_step(self.model),
                               donate_argnums=(1,))
        self._fused_decode = device_loop.build_fused_decode(self.model,
                                                            serve_cfg)
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, self.cfg.max_seq),
            static_argnums=())
        self._key = jax.random.PRNGKey(serve_cfg.seed)
        # stats from the most recent serve() call — a plain-dict render
        # of the session's metrics registry (EngineSession.stats_snapshot;
        # DESIGN.md §13.1), kept under the historical name
        self.paging_stats: Optional[Dict] = None
        # Sparse (RgCSR) weights: pre-stage kernel plan containers at model
        # load for eager per-layer paths (DESIGN.md §3.2).  The jit'd
        # prefill/decode below assemble their plans at trace time, so the
        # latency path pays no per-call host plan work either way; warming
        # is a no-op for layer-stacked param trees (plans_warmed == 0).
        self.plans_warmed = 0
        self.spmv_plans_warmed = 0
        self.sharded_spmv_plans_warmed = 0
        # append-only observability log: one small host dict per warmed
        # (matrix, mesh) — deliberately never pruned, unlike _warm_sharded
        # below, which holds device arrays and must release superseded plans
        self.sharded_spmv_shard_stats: List[Dict] = []
        # strong refs keep the sharded-plan cache entries alive; keyed on
        # (mesh signature, x_mode, exact matrix content) so re-warming the
        # same matrix on the same mesh REPLACES its entry — the superseded
        # plan's device arrays are released to the weakref-evicted caches
        # instead of accumulating for the engine's lifetime.  The key must
        # be the exact content (not the tuner's log2-bucketed signature):
        # two distinct matrices sharing a bucket must both stay warmed.
        self._warm_sharded: Dict[tuple, tuple] = {}
        if model_cfg.sparsity.enabled and model_cfg.sparsity.impl_is_kernel():
            from repro.kernels import ops as kops
            # warm at the model's compute dtype — the dtype the eager apply
            # path will request (a float32 default would never be hit under
            # the bfloat16 default config)
            self.plans_warmed = kops.warm_plans_from_params(
                self.params, dtype=jnp.dtype(model_cfg.dtype))

    def warm_spmv_plans(self, matrices, *, repeats: int = 1, mesh=None,
                        mesh_axis: Optional[str] = None,
                        x_mode: str = "replicated",
                        per_shard_tune: bool = True):
        """Pre-tune and stage SpMV plans for auxiliary sparse matrices.

        Serving deployments that also answer SpMV traffic (iterative
        solvers, graph scoring) hand their matrices here at startup: each
        one runs the joint autotune search — ``(chunks_per_step,
        group_size, ordering, spill_threshold)``, DESIGN.md §5 — and the
        winning plan (block or adaptive, whichever measured faster) lands
        in the process-wide ``PLAN_CACHE`` before the first request.

        Contract: the warmed entries are keyed to the tuner's own RgCSR
        containers (retained per matrix signature), so the request path
        hits them by fetching through ``autotune.tuned_plan(dense)`` —
        a signature-memo hit, no re-timing, no plan rebuild.  A caller
        that instead runs ``core.spmv`` on its *own* RgCSR object gets a
        fresh plan under that object's identity and must thread the
        returned config's ``(ordering, spill_threshold, chunks_per_step)``
        itself.  Returns the winning
        :class:`repro.kernels.autotune.TuneConfig` per matrix, in order.

        With ``mesh`` set, each matrix is additionally row-sharded over the
        resolved mesh axis (``mesh_axis`` or the partitioner's
        ``sparse_rows`` rule) and, with ``per_shard_tune`` (the default),
        **each shard is tuned independently** (DESIGN.md §12,
        ``autotune.autotune_spmv_per_shard``): the heavy shard of a skewed
        matrix gets spill/adaptive while light shards keep plain block
        cps>1, all at the global winner's ``group_size`` so the stacked
        plan stays uniform.  The stacked shard_map plan is built at those
        per-shard winners and staged in the sharded plan cache — keyed on
        the shard/device count, so re-warming on a resized mesh builds a
        fresh plan instead of reusing a stale stacked one.  Per-matrix
        shard stats (slots, steps, remote columns, exchange volume per the
        §12 sparse-collective schedule, per-shard winner configs) land in
        ``sharded_spmv_shard_stats``.  The sharded matrices are retained
        on the engine so the cache entries survive warmup.
        """
        from repro.kernels import autotune
        winners = []
        if mesh is not None and mesh_axis is None:
            from repro.sharding import resolve_spmv_shard_axis
            mesh_axis = resolve_spmv_shard_axis(mesh)
        for dense in matrices:
            dense = np.asarray(dense)
            _, result = autotune.tuned_plan(dense, repeats=repeats)
            winners.append(result.config)
            if mesh is not None:
                from repro.core.formats import ShardedRgCSR
                from repro.kernels import ops as kops
                from repro.sharding import mesh_signature
                cfg = result.config
                n_shards = int(mesh.shape[mesh_axis])
                shard_cfgs = None
                if per_shard_tune:
                    shard_results = autotune.autotune_spmv_per_shard(
                        dense, n_shards, group_size=cfg.group_size,
                        repeats=repeats, x_mode=x_mode)
                    shard_cfgs = autotune.harmonize_shard_winners(
                        shard_results)
                sm = ShardedRgCSR.from_dense(
                    dense, n_shards=n_shards, group_size=cfg.group_size)
                splan = kops.get_sharded_plan(
                    sm, chunks_per_step=cfg.chunks_per_step,
                    ordering=cfg.ordering,
                    spill_threshold=cfg.spill_threshold, x_mode=x_mode,
                    shard_configs=shard_cfgs)
                content = hashlib.sha1(
                    np.ascontiguousarray(dense).tobytes()).hexdigest()
                self._warm_sharded[(mesh_signature(mesh), x_mode,
                                    dense.shape, str(dense.dtype),
                                    content)] = (sm, splan)
                self.sharded_spmv_plans_warmed += 1
                self.sharded_spmv_shard_stats.append({
                    "n_shards": splan.n_shards,
                    "mesh": mesh_signature(mesh),
                    "x_mode": splan.x_mode,
                    "stored_slots": list(splan.shard_stored_slots),
                    "num_steps": list(splan.shard_num_steps),
                    "remote_cols": list(splan.shard_remote_cols),
                    "exchange_recv_cols": list(
                        splan.shard_exchange_recv_cols),
                    "exchange_send_cols": list(
                        splan.shard_exchange_send_cols),
                    "exchange_bytes": list(splan.shard_exchange_bytes),
                    "kernel_chunks_per_step": splan.chunks_per_step,
                    "shard_winners": [list(c) for c in splan.shard_configs],
                })
        self.spmv_plans_warmed += len(winners)
        return winners

    def plan_cache_stats(self):
        """Plan-cache counters: the matrix PlanCache (core spmv dispatch)
        and the SparseLinear param-plan memo (this engine's sparse layers),
        plus how many plans this engine warmed at init."""
        from repro.kernels import ops as kops
        return {"plan_cache": kops.PLAN_CACHE.stats(),
                "param_plans": kops.param_plan_stats(),
                "sharded_plan_cache": kops.sharded_plan_cache_stats(),
                "plans_warmed": self.plans_warmed,
                "spmv_plans_warmed": self.spmv_plans_warmed,
                "sharded_spmv_plans_warmed": self.sharded_spmv_plans_warmed}

    # ---------------------------------------------------------------- sample
    def _sample(self, logits) -> jax.Array:
        """Host-side sampling: split the engine key once per step and
        defer to the pure sampler the fused device loop also uses."""
        if self.cfg.temperature <= 0.0:
            return device_loop.sample_tokens(logits, None, 0.0, 0)
        self._key, sub = jax.random.split(self._key)
        return device_loop.sample_tokens(logits, sub, self.cfg.temperature,
                                         self.cfg.top_k)

    # ------------------------------------------------------------- one-shot
    def generate(self, prompts: np.ndarray, max_new_tokens: int = 32
                 ) -> np.ndarray:
        """Batch-synchronous generation (all prompts same length).

        Output is always ``(b, max_new_tokens)``; with ``eos_id >= 0``,
        sequences that sample EOS (including at prefill — the first token
        counts) stop consuming decode steps and their remaining positions
        are filled with ``eos_id``.  Once every sequence has finished the
        decode loop exits instead of burning the rest of the budget.
        """
        b = prompts.shape[0]
        eos = self.cfg.eos_id
        batch = {"tokens": jnp.asarray(prompts, jnp.int32)}
        logits, caches = self._prefill(self.params, batch)
        tok = self._sample(logits)[:, None]
        done = np.asarray(tok[:, 0] == eos) if eos >= 0 else np.zeros(b, bool)
        outs = [tok]
        for _ in range(max_new_tokens - 1):
            if eos >= 0 and done.all():
                pad = jnp.full((b, 1), eos, jnp.int32)
                outs.extend([pad] * (max_new_tokens - len(outs)))
                break
            logits, caches = self._decode(self.params, caches, tok)
            nxt = self._sample(logits)
            if eos >= 0:
                nxt = jnp.where(jnp.asarray(done), eos, nxt)
                done |= np.asarray(nxt == eos)
            tok = nxt[:, None]
            outs.append(tok)
        return np.asarray(jnp.concatenate(outs, axis=1))

    # ------------------------------------------------- continuous batching
    def start_session(self, requests: Optional[List[Request]] = None,
                      fault_injector=None) -> "EngineSession":
        """Open a reentrant serving session (DESIGN.md §7).

        The returned :class:`EngineSession` owns the decode batch, page
        allocator, and request queue, and hands control back to the host
        between decode steps: ``submit()`` enqueues requests at any time,
        ``step(k)`` runs up to ``k`` decode steps (admissions, deadline
        sweeps, and completions happen at the step boundaries), and
        ``drain()`` runs to quiescence.  ``serve()`` below is the thin
        blocking wrapper; a :class:`~repro.serve.router.Router` interleaves
        many sessions — one per replica — through this interface.  The
        "run K steps, then sync host state" cadence is also the shape the
        ROADMAP's on-device ``lax.while_loop`` decode body slots into: the
        host side of this session is already written against it.
        """
        injector = fault_injector if fault_injector is not None \
            else self.fault_injector
        return EngineSession(self, requests or [], injector)

    def restore_session(self, snap, fault_injector=None):
        """Crash-recovery convenience: fresh session + load a
        :meth:`EngineSession.snapshot`.  Returns ``(session, requests)``
        where ``requests`` are the re-enqueued handles in queue order —
        ``session.drain()`` completes them token-identically to the
        streams the dead process was producing."""
        session = self.start_session([], fault_injector)
        return session, session.restore(snap)

    def serve(self, requests: List[Request],
              fault_injector=None) -> List[Request]:
        """Continuous mixed-length batching over a request queue.

        Thin blocking wrapper over :meth:`start_session` +
        :meth:`EngineSession.drain`.  Slots share one jit'd decode over
        the fixed batch; prefill is per-request (batch 1) and its cache is
        committed into the slot — page-pool scatter for paged layers,
        slot-axis splice for rings / recurrent state / dense mode
        (``serve/paging.commit_prefill``).  Finished slots immediately
        pull the next queued request — no head-of-line blocking on long
        generations, no drain barriers, no cache resets.

        Semantics:

        * prompt lengths may differ freely within one live batch: the
          per-slot position index keeps each slot's attention offsets
          independent, so a request admitted into a half-decoded batch
          neither inherits the batch's write head (the old stale-offset
          drift) nor disturbs the other slots;
        * paged layout, ``admission_policy="prompt"`` (default): admission
          reserves only the pages the request's *resident* tokens need;
          when a decode boundary then finds the pool dry, the
          latest-admitted slot is **recompute-preempted** — its pages are
          freed and the request re-enqueued at the queue head with its
          generated prefix prepended, to be re-prefilled when pages free
          (DESIGN.md §6.4).  Earlier-admitted requests always keep their
          pages (FIFO: the earliest active slot can never be starved), so
          pools sized below aggregate worst case make progress instead of
          blocking.  ``admission_policy="worst_case"`` restores the PR 5
          behavior: worst-case reservations, admission **defers** on
          exhaustion, decode-boundary allocation never fails;
        * per-request fault isolation (unless ``strict=True``): an
          oversized request (budget beyond ``max_seq``, or a worst-case
          page count larger than the whole pool) is **rejected**
          (``status="rejected"``) instead of raising; an exception during
          a request's prefill, or an injected per-request decode fault,
          **fails** that request (``status="failed"``) and frees its
          slot/pages while the rest of the batch keeps serving.  A
          :class:`~repro.train.fault.FaultInjector` (argument, or the
          engine's ``fault_injector``) is consulted at the per-request
          prefill and token-commit sites.  The injector's ``"replica"``
          site is the exception: it models a whole-engine fault (node
          loss) and raises out of ``step()``/``serve()`` regardless of
          ``strict`` — the router catches it and migrates the session's
          in-flight requests to surviving replicas (DESIGN.md §7);
        * deadlines: a request whose ``deadline_s`` (or the config
          default) elapses — measured from its **arrival**
          (``Request.arrival_t``; for batch-submitted calls like this one,
          serve() entry), so queue wait counts — is timed out at the next
          decode boundary (or while still queued), keeping its partial
          ``out``;
        * a request whose first (prefill-sampled) token is EOS, or whose
          ``max_new_tokens <= 1``, completes immediately without spending
          decode steps, a slot, or pages;
        * per-request timing lands in ``queue_s`` / ``prefill_s`` /
          ``latency_s`` (see :class:`Request`) — ``latency_s`` is measured
          from the request's own processing start, not the serve() call;
        * observability lands in ``self.paging_stats`` after every call —
          a plain-dict view rendered from the session's typed metrics
          registry (:meth:`EngineSession.stats_snapshot`, DESIGN.md §13):
          pages in use / high-water, fragmentation, deferrals, preemption
          counters (``preemptions``, ``recompute_tokens``, ``evictions``,
          ``pages_evicted``), per-status counts (``completed`` /
          ``rejected`` / ``failed`` / ``timed_out``), straggler decode
          steps flagged by a :class:`~repro.train.fault.Watchdog` over
          ``self.fault_cfg``, plus ``request_timing`` histogram states
          and ``latency_percentiles`` (p50/p95/p99 of queue_s /
          prefill_s / latency_s).  Attach a
          :class:`repro.obs.trace.Tracer` to ``self.tracer`` before the
          call for the matching per-request span timeline.
        """
        session = self.start_session(requests, fault_injector)
        session.drain()
        self.paging_stats = session.stats_snapshot()
        return requests


class EngineSession:
    """Reentrant serving stepper over one :class:`Engine` (DESIGN.md §7).

    Holds everything ``Engine.serve`` used to keep as loop locals — the
    decode batch, page allocator, request queue, per-slot bookkeeping, and
    stats — so the host can run ``step(k)`` decode steps, regain control,
    and interleave other work (other replicas, admissions, I/O) between
    bursts.  All the §6 serving semantics (recompute preemption,
    per-request fault isolation, deadlines, prefill-EOS fast path) live
    here unchanged; ``Engine.serve`` is a ``drain()`` around this class.

    Faults split into two tiers:

    * **request tier** — prefill/decode-site injections and real
      exceptions in a request's prefill fail only that request
      (``strict=False``), exactly as before;
    * **replica tier** — an injected ``("replica", k)`` fault (checked
      once per decode step, ``k`` = this session's decode-step count) or
      any exception escaping the decode dispatch itself raises out of
      ``step()``: the whole session is presumed lost.  The router
      harvests ``inflight()`` (generated prefixes intact in ``out``) and
      re-prefills them on surviving replicas — the same prompt+prefix
      recompute path preemption uses, so migrated streams stay
      oracle-identical.
    """

    def __init__(self, engine: Engine, requests: List[Request],
                 injector=None):
        from repro.train.fault import Watchdog
        self.engine = engine
        cfg = engine.cfg
        self.cfg = cfg
        self.n = cfg.n_slots
        self.paged = cfg.kv_layout == "paged"
        self.strict = cfg.strict
        self.clock = engine.clock
        self.injector = injector
        self.geom = self.alloc = None
        if self.paged:
            self.geom = paging.geometry(cfg.max_seq, cfg.page_size, self.n,
                                        cfg.n_pages)
            self.alloc = paging.PageAllocator(self.geom, self.n,
                                              policy=cfg.admission_policy,
                                              strict=cfg.strict)
        self.kv_integrity = cfg.kv_integrity and self.paged
        self.caches = engine.model.init_cache(self.n, cfg.max_seq,
                                              paging=self.geom)
        self.queue: deque = deque()
        self.active: List[Optional[Request]] = [None] * self.n
        self.remaining = [0] * self.n
        self.pos = [0] * self.n             # tokens resident per slot
        self.admit_seq = [-1] * self.n      # admission order per slot
        self.seq_counter = 0
        self.started: Dict[int, float] = {}  # id(req) → first slotting time
        self.cur_tok = jnp.zeros((self.n, 1), jnp.int32)
        self.t_start = self.clock()
        self.watchdog = Watchdog(engine.fault_cfg)
        self.prefill_count = 0              # prefill site index (injector)
        # observability (DESIGN.md §13): ``stats`` keeps its historical
        # dict interface but is a view over a typed metrics registry;
        # request timing feeds histograms so percentiles survive replica
        # merging and host-state snapshots.  The tracer comes from the
        # engine (NOOP when tracing is off); spans land on this replica's
        # track — one ``slot<k>`` lane per slot plus a ``session`` lane.
        self.trace = engine.tracer if engine.tracer is not None \
            else obs_trace.NOOP
        self.label = engine.trace_label
        self.track = (self.label, "session")
        self.metrics = obs_metrics.MetricsRegistry()
        self.stats = self.metrics.view(
            counters=("decode_steps", "decode_dispatches",
                      "admission_deferrals"),
            gauges=("peak_live_tokens", "frag_at_high_water"))
        for key in ("requests", "completed", "preemptions",
                    "recompute_tokens", "rejected", "failed", "timed_out",
                    "restores", "restore_recompute_tokens",
                    "nonfinite_logits"):
            self.stats[key] = 0
        self.stats["frag_at_high_water"] = 0.0
        # host seconds of the decode dispatches, split at the clock reads
        # of _run_chunk: launching, waiting on the device, committing
        for key in ("decode_enqueue_s", "decode_wait_s", "decode_commit_s"):
            self.stats[key] = 0.0
        # (decode step, enqueue_s, wait_s, commit_s) of each dispatch the
        # watchdog flagged
        self.straggler_log: List[tuple] = []
        self.hists = {name: self.metrics.histogram(name)
                      for name in ("queue_s", "prefill_s", "latency_s")}
        if self.alloc is not None and self.trace.enabled:
            self.alloc.tracer = self.trace
            self.alloc.trace_track = self.track
        for req in requests:
            self.submit(req)

    # ------------------------------------------------------------ queries
    @property
    def idle(self) -> bool:
        """No queued and no resident work."""
        return not self.queue and all(a is None for a in self.active)

    @property
    def num_queued(self) -> int:
        return len(self.queue)

    @property
    def num_active(self) -> int:
        return sum(a is not None for a in self.active)

    @property
    def has_free_slot(self) -> bool:
        return any(a is None for a in self.active)

    @property
    def free_pages(self) -> int:
        """Routing signal: free pages in this session's pool (dense
        sessions report free slots — the analogous capacity unit)."""
        if self.alloc is not None:
            return self.alloc.free_pages
        return sum(a is None for a in self.active)

    def inflight(self) -> List[Request]:
        """Undone requests this session owns, FIFO: resident slots in
        admission order, then the queue.  This is what a router migrates
        when the replica dies — each request's generated prefix is in
        ``out``, so re-admission elsewhere resumes it exactly."""
        resident = sorted((s for s in range(self.n)
                           if self.active[s] is not None),
                          key=lambda s: self.admit_seq[s])
        return [self.active[s] for s in resident] + \
            [r for r in self.queue if not r.done]

    # ---------------------------------------------------------- lifecycle
    def submit(self, req: Request, front: bool = False) -> None:
        """Enqueue a request (``front=True``: ahead of the line — used for
        preemption re-entry and router migrations).  Stamps ``arrival_t``
        on first submission; a migrated request keeps its original arrival
        so its deadline keeps running across replicas."""
        if req.arrival_t is None:
            req.arrival_t = self.clock()
        self.stats["requests"] += 1
        # idempotent per request: a router-migrated request keeps its
        # one open lifeline instead of starting a second one
        self.trace.request_begin(req, self.track, prompt=len(req.tokens))
        if front:
            self.queue.appendleft(req)
        else:
            self.queue.append(req)

    def _deadline_expired(self, req: Request, now: float) -> bool:
        d = req.deadline_s if req.deadline_s is not None else \
            (self.cfg.deadline_s if self.cfg.deadline_s > 0 else None)
        return d is not None and (now - req.arrival_t) > d

    def _finish_ok(self, req: Request) -> None:
        req.done = True
        req.status = "ok" if req.preemptions == 0 \
            else f"preempted_{req.preemptions}"
        req.latency_s = self.clock() - self.started[id(req)]
        self.stats["completed"] += 1
        self.hists["latency_s"].observe(req.latency_s)
        self.trace.request_end(req, self.track, status=req.status,
                               tokens=len(req.out or ()))

    def _finish_bad(self, req: Request, status: str, error: str,
                    slot: Optional[int] = None) -> None:
        """Terminal failure for ONE request: record status/error, free
        its slot and pages, leave everyone else serving."""
        req.done = True
        req.status = status
        req.error = error
        if req.out is None:
            req.out = []
        if id(req) in self.started:
            req.latency_s = self.clock() - self.started[id(req)]
            self.hists["latency_s"].observe(req.latency_s)
        self.stats[status] += 1
        if status == "timed_out":
            self.trace.instant("deadline_expired", self.track,
                               queued=slot is None)
        self.trace.request_end(req, self.track, status=status)
        if slot is not None:
            self.trace.end("request", (self.label, f"slot{slot}"),
                           status=status)
            self.active[slot] = None
            if self.paged:
                self.alloc.release(slot)

    def _preempt_slot(self, slot: int) -> None:
        """Recompute-preempt one specific slot: free its pages (corrupt
        ones land in quarantine at release), re-enqueue the request at
        the queue HEAD with its generated prefix kept in ``out`` —
        re-admission prefills prompt+prefix and resumes sampling where
        it left off."""
        req = self.active[slot]
        req.preemptions += 1
        req.status = f"preempted_{req.preemptions}"
        self.stats["preemptions"] += 1
        self.stats["recompute_tokens"] += self.pos[slot]
        self.trace.end("request", (self.label, f"slot{slot}"),
                       status=req.status)
        self.trace.instant("preempt", (self.label, f"slot{slot}"),
                           slot=slot, recompute_tokens=self.pos[slot])
        self.active[slot] = None
        if self.paged:
            self.alloc.release(slot, evicted=True)
        self.queue.appendleft(req)

    def _preempt_victim(self) -> int:
        """Recompute-preempt the latest-admitted (fewest tokens
        generated) active slot (see :meth:`_preempt_slot`).  Returns the
        victim slot.  FIFO: the victim was admitted before anything
        still queued (later evictions are earlier admissions —
        appendleft keeps them ordered ahead of this one)."""
        victim = max((s for s in range(self.n)
                      if self.active[s] is not None),
                     key=lambda s: (self.admit_seq[s],
                                    -len(self.active[s].out)))
        self._preempt_slot(victim)
        return victim

    # ---------------------------------------------------- page integrity
    def _record_checksums(self) -> None:
        """Chunk-commit boundary: fingerprint every live page's committed
        contents into the allocator's checksum table (DESIGN.md §7.6).
        A slot with ``pos`` resident tokens has committed exactly the
        first ``pos`` rows of its page chain; lookahead pages with no
        committed rows carry no record (nothing to protect yet)."""
        alloc, ps = self.alloc, self.geom.page_size
        committed: Dict[int, int] = {}
        for slot in range(self.n):
            if self.active[slot] is None:
                continue
            for j, page in enumerate(alloc.slot_pages[slot]):
                ntok = min(ps, self.pos[slot] - j * ps)
                if ntok > 0:
                    committed[page] = ntok
        for page in list(alloc.checksums):
            if page not in committed:
                del alloc.checksums[page]
        for page, crc in paging.page_fingerprints(self.caches,
                                                  committed).items():
            alloc.record_checksum(page, committed[page], crc)

    def _verify_integrity(self) -> None:
        """Pre-dispatch verify: recompute every recorded page's crc over
        its recorded committed length and compare.  A mismatch means the
        page mutated between commit boundaries with no token having been
        sampled from it yet (the verify runs before the next dispatch),
        so recovery is surgical and oracle-exact: quarantine the page,
        recompute-preempt exactly the slots whose block tables reference
        it (their ``out`` prefixes predate the corruption), null the
        affected table rows on device, and leave every other slot
        untouched."""
        alloc = self.alloc
        if not alloc.checksums:
            return
        recorded = dict(alloc.checksums)
        crcs = paging.page_fingerprints(
            self.caches, {p: lc[0] for p, lc in recorded.items()})
        bad = [p for p, crc in crcs.items() if crc != recorded[p][1]]
        if not bad:
            return
        victims = set()
        for page in bad:
            owner = alloc.owner_of(page)
            alloc.quarantine(page)
            if owner is not None and self.active[owner] is not None:
                victims.add(owner)
        # preempt in reverse admission order so appendleft leaves the
        # earliest-admitted victim at the queue head (FIFO preserved)
        for slot in sorted(victims, key=lambda s: self.admit_seq[s],
                           reverse=True):
            self._preempt_slot(slot)
        self.caches = paging.sync_block_tables(self.caches, alloc.table)

    def _quarantine_slot_pages(self, slot: int) -> None:
        """A slot's logits went non-finite mid-dispatch: localize the
        poison in its page chain and quarantine it (the preempting
        release then withholds those pages from the free list).  NaN
        leaks through the attention mask from any position of a touched
        page — including uncommitted tail positions the checksums don't
        cover — so localization scans the pages for non-finite values
        directly, falls back to checksum mismatches, and as a last
        resort quarantines the whole chain (losing a few clean pages
        beats re-admitting onto a poisoned one)."""
        alloc = self.alloc
        chain = list(alloc.slot_pages[slot])
        bad = paging.pages_nonfinite(self.caches, chain)
        if not bad:
            recorded = {p: alloc.checksums[p][0] for p in chain
                        if p in alloc.checksums}
            bad = {p for p, crc in paging.page_fingerprints(
                self.caches, recorded).items()
                if crc != alloc.checksums[p][1]}
        if not bad:
            bad = set(chain)
        for page in bad:
            alloc.quarantine(page)

    def _admit(self) -> None:
        """Fill free slots from the queue; a request finishing at prefill
        (EOS as its first token, or an exhausted budget) completes without
        ever occupying the slot, so the next queued request slots in."""
        cfg, alloc = self.cfg, self.alloc
        deferred = False
        for slot in range(self.n):
            while self.active[slot] is None and self.queue and not deferred:
                req = self.queue[0]
                now = self.clock()
                if self._deadline_expired(req, now):
                    self.queue.popleft()
                    self.started.setdefault(id(req), now)
                    req.queue_s = now - req.arrival_t
                    self.hists["queue_s"].observe(req.queue_s)
                    self._finish_bad(req, "timed_out",
                                     "deadline exceeded after "
                                     f"{now - req.arrival_t:.3f}s in queue")
                    continue
                prefix = req.out or []      # preempted: generated so far
                length = len(req.tokens) + len(prefix)
                budget = max(req.max_new_tokens, 1) - len(prefix)
                # max resident tokens: the last decode step has written
                # length + max_new - 1 of them (the final sampled token
                # never enters the cache) — preemption never raises it
                max_resident = len(req.tokens) \
                    + max(req.max_new_tokens, 1) - 1
                if max_resident > cfg.max_seq:
                    msg = (f"request needs {max_resident} cache "
                           f"positions (prompt {len(req.tokens)} + "
                           f"max_new_tokens {req.max_new_tokens} - 1) "
                           f"but max_seq is {cfg.max_seq}")
                    if self.strict:
                        raise ValueError(msg)
                    self.queue.popleft()
                    self._finish_bad(req, "rejected", msg)
                    continue
                worst = 0
                if self.paged:
                    worst = alloc.pages_for(max_resident)
                    if worst > alloc.usable:
                        msg = (f"request needs up to {worst} pages but "
                               f"the pool has {alloc.usable}: raise "
                               f"n_pages or lower max_new_tokens")
                        if self.strict:
                            raise ValueError(msg)
                        self.queue.popleft()
                        self._finish_bad(req, "rejected", msg)
                        continue
                    if not alloc.can_admit(
                            alloc.admission_pages(length, worst)):
                        # FIFO: don't let shorter later requests starve
                        # the head — stop admitting until pages free
                        self.stats["admission_deferrals"] += 1
                        deferred = True
                        break
                self.queue.popleft()
                t0 = self.clock()
                if id(req) not in self.started:
                    self.started[id(req)] = t0
                    req.queue_s = t0 - req.arrival_t
                    self.hists["queue_s"].observe(req.queue_s)
                lane = (self.label, f"slot{slot}")
                self.trace.begin("request", lane,
                                 prompt=len(req.tokens),
                                 prefix=len(prefix))
                tokens = req.tokens if not prefix else np.concatenate(
                    [np.asarray(req.tokens, np.int32),
                     np.asarray(prefix, np.int32)])
                site = self.prefill_count
                self.prefill_count += 1
                try:
                    # through the first token's int(): the host waits
                    # for the prefill here
                    with self.trace.span("prefill", lane,
                                         tokens=len(tokens)):
                        if self.injector is not None:
                            self.injector.check(site, site="prefill")
                        logits, slot_cache = self.engine._prefill(
                            self.engine.params,
                            {"tokens": jnp.asarray(tokens[None, :],
                                                   jnp.int32)})
                        first = int(self.engine._sample(logits)[0])
                except Exception as e:  # noqa: BLE001 — isolate request
                    if self.strict:
                        raise
                    self.trace.end("request", lane, status="failed")
                    self._finish_bad(req, "failed", repr(e))
                    continue
                if req.out is None:
                    req.out = []
                req.out.append(first)
                if not prefix:
                    req.prefill_s = self.clock() - t0
                    self.hists["prefill_s"].observe(req.prefill_s)
                if first == cfg.eos_id or budget <= 1:
                    self.trace.end("request", lane, status="ok")
                    self._finish_ok(req)
                    continue
                paged_args = ()
                if self.paged:
                    alloc.admit(slot, length, worst)
                    paged_args = (alloc.table, self.geom.page_size)
                with self.trace.span("commit_prefill", lane):
                    self.caches = paging.commit_prefill(
                        self.caches, slot_cache, slot, length, *paged_args)
                self.active[slot] = req
                self.admit_seq[slot] = self.seq_counter
                self.seq_counter += 1
                self.remaining[slot] = budget - 1
                self.pos[slot] = length
                self.cur_tok = self.cur_tok.at[slot, 0].set(first)

    def _sweep_deadlines(self) -> None:
        """Decode-boundary deadline sweep: expired slots free their pages
        before anyone is preempted for space."""
        now = self.clock()
        for slot in range(self.n):
            req = self.active[slot]
            if req is not None and self._deadline_expired(req, now):
                self._finish_bad(req, "timed_out",
                                 "deadline exceeded after "
                                 f"{now - req.arrival_t:.3f}s with "
                                 f"{len(req.out)} tokens", slot=slot)

    def _ensure_pages(self, horizon: int = 1) -> int:
        """Grow each active slot's pages for the next fused chunk and
        return the chunk length the pool can actually cover.

        Phase A (mandatory, unchanged §6.4 semantics): the next decode
        step writes each active slot's token at position ``pos[slot]`` —
        allocate that boundary page up front, earliest-admitted first.
        worst_case policy: always succeeds under the reservation
        invariant.  prompt policy: pool exhaustion preempts the
        latest-admitted slot (possibly the requester itself) and retries
        — the earliest active slot can always make progress, since alone
        it fits by the worst-case-vs-pool admission check.

        Phase B (chunk horizon): extend surviving slots to cover
        ``min(horizon, remaining)`` further steps, shrinking ``horizon``
        until the extension fits the FREE pool — extension never
        preempts and never raises, so a fused chunk of the returned
        length cannot exhaust the pool mid-flight.  A slot running ``s``
        steps writes positions ``pos .. pos+s-1`` (its final sampled
        token never enters the cache), and ``pos + remaining`` is the
        admission-checked max residency, so the extension stays within
        each slot's worst-case cap.
        """
        alloc = self.alloc
        changed = False
        order = sorted((s for s in range(self.n)
                        if self.active[s] is not None),
                       key=lambda s: self.admit_seq[s])
        for slot in order:
            if self.active[slot] is None:
                continue                 # evicted as a victim below
            while True:
                try:
                    changed |= alloc.ensure(slot, self.pos[slot] + 1)
                    break
                except paging.PoolExhausted:
                    victim = self._preempt_victim()
                    changed = True       # victim's table row went null
                    if victim == slot:
                        break            # requester evicted itself
        k = max(1, horizon)
        if k > 1:
            live = [s for s in order if self.active[s] is not None]

            def extra(steps: int) -> int:
                return sum(
                    max(0, alloc.pages_for(
                        self.pos[s] + min(steps, self.remaining[s]))
                        - len(alloc.slot_pages[s]))
                    for s in live)

            while k > 1 and extra(k) > alloc.free_pages:
                k -= 1
            for s in live:
                changed |= alloc.ensure(
                    s, self.pos[s] + min(k, self.remaining[s]))
        if changed:
            self.caches = paging.sync_block_tables(self.caches, alloc.table)
        return k

    def _record_live(self) -> None:
        """Live-token peak is layout-agnostic (the dense layout used to
        report 0, skewing the paged-vs-dense residency comparison);
        called once per committed decode row so chunked serving sees the
        same per-step peaks the stepwise cadence did."""
        live = sum(self.pos[s] + 1 for s in range(self.n)
                   if self.active[s] is not None)
        self.stats["peak_live_tokens"] = max(
            self.stats["peak_live_tokens"], live)
        if self.paged and self.alloc.pages_in_use >= self.alloc.high_water:
            self.stats["frag_at_high_water"] = 1.0 - live / max(
                self.alloc.pages_in_use * self.geom.page_size, 1)

    def step(self, max_steps: int = 1) -> int:
        """Run up to ``max_steps`` decode steps; returns how many ran.

        Chunked cadence (DESIGN.md §7.1): each iteration admits from the
        queue, sweeps deadlines, grows/preempts pages out to the chunk
        horizon, then launches ONE fused on-device dispatch
        (``device_loop.build_fused_decode``) that runs up to
        ``decode_chunk`` decode+sample steps before syncing back — the
        returned ``(k, n_slots)`` token block is committed host-side
        row by row with exactly the stepwise per-slot semantics
        (per-request decode fault sites, EOS/budget completion, page
        release).  Admission-only iterations (heads rejected / timed out
        / finished at prefill) don't count against ``max_steps``.

        A replica-tier fault (see class docstring) raises out of this
        method with the session state intact for ``inflight()``
        harvesting; an armed replica fault *inside* the upcoming chunk
        splits the chunk at the fault step, so the tokens before it are
        committed (a partially-committed chunk migrates) and the fault
        fires at exactly the stepwise decode-step index.
        """
        cfg = self.cfg
        ran = 0
        while ran < max_steps and (
                self.queue or any(a is not None for a in self.active)):
            if self.kv_integrity:
                # commit-boundary verify BEFORE admission: corruption
                # detected here frees/quarantines pages and re-enqueues
                # its victims at the head, so recovery re-prefills in
                # this very iteration
                self._verify_integrity()
            with self.trace.span("admit", self.track):
                self._admit()
            if all(a is None for a in self.active):
                if self.queue:
                    continue     # heads were rejected/timed out — refill
                break            # the fill loop drained the queue
            self._sweep_deadlines()
            chunk = min(max(1, cfg.decode_chunk), max_steps - ran)
            if self.paged:
                with self.trace.span("ensure_pages", self.track):
                    chunk = self._ensure_pages(chunk)
            self._record_live()  # chunk-boundary peak (pre-dispatch)
            if all(a is None for a in self.active):
                continue         # deadline sweep / self-eviction emptied
            if self.injector is not None:
                # process-tier fault first (exact-match so bare ints can't
                # escalate): the whole process dies — ProcessKilled raises
                # through the router to the crash drill, which restores
                # the latest snapshot.  Then replica tier: the engine dies
                # mid-decode — deliberately NOT per-request isolated,
                # raises out of step() so the router migrates this
                # session's inflight().  An armed step strictly inside the
                # chunk caps it, so the next iteration fires the fault at
                # the stepwise index with the pre-fault rows committed.
                self.injector.check(self.stats["decode_steps"],
                                    site="process", exact=True)
                self.injector.check(self.stats["decode_steps"],
                                    site="replica")
                lo = self.stats["decode_steps"] + 1
                hi = self.stats["decode_steps"] + chunk
                faults = [f for f in (
                    self.injector.next_armed("replica", lo, hi),
                    self.injector.next_armed("process", lo, hi, exact=True))
                    if f is not None]
                if faults:
                    chunk = min(faults) - self.stats["decode_steps"]
                if self.paged:
                    # corruption striking INSIDE the dispatch window:
                    # injected after the boundary verify, caught by the
                    # commit loop's NaN/Inf screen instead
                    idx = self.injector.take("page_nan")
                    if idx is not None:
                        self.caches = paging.corrupt_page(
                            self.caches, idx, nan=True)
            if self.trace.enabled and self.paged:
                self.trace.counter("free_pages", self.track,
                                   free=self.alloc.free_pages)
            with self.trace.span("decode_chunk", self.track,
                                 chunk=int(chunk), active=self.num_active):
                ran += self._run_chunk(chunk)
            if self.injector is not None and self.paged:
                # silent corruption at rest: injected AFTER the boundary
                # fingerprints, so the recorded crc reflects the clean
                # contents and the next iteration's verify flags the page
                idx = self.injector.take("page")
                if idx is not None:
                    self.caches = paging.corrupt_page(self.caches, idx)
        return ran

    def _run_chunk(self, chunk: int) -> int:
        """One fused dispatch of up to ``chunk`` decode steps and the host
        commit of its tokens; returns the decode steps committed.

        Four clock reads split the dispatch's host time into enqueue
        (building the inputs and launching ``_fused_decode``, which
        returns before the device is done), wait (fetching the results:
        the host blocks on the device) and commit (the row-by-row commit
        below), summed into the ``decode_enqueue_s`` / ``decode_wait_s``
        / ``decode_commit_s`` counters; a dispatch the watchdog flags
        logs its split in ``straggler_log``."""
        cfg = self.cfg
        t0 = self.clock()
        with self.trace.span("dispatch", self.track):
            rem_dev = jnp.asarray(
                [self.remaining[s] if self.active[s] is not None else 0
                 for s in range(self.n)], jnp.int32)
            act_dev = jnp.asarray(
                [a is not None for a in self.active], bool)
            block, steps_ran, tok, key, self.caches, logit_ok = \
                self.engine._fused_decode(
                    self.engine.params, self.caches, self.cur_tok,
                    rem_dev, act_dev, self.engine._key,
                    jnp.asarray(chunk, jnp.int32))
        t1 = self.clock()
        with self.trace.span("fetch", self.track):
            block, steps, ok_block = jax.device_get(
                (block, steps_ran, logit_ok))
        t2 = self.clock()
        steps = int(steps)
        block, ok_block = np.asarray(block), np.asarray(ok_block)
        self.cur_tok = tok
        self.engine._key = key
        self.stats["decode_dispatches"] += 1
        step0 = self.stats["decode_steps"]
        # normalize wall time by steps actually fused into this
        # dispatch — a k-step chunk must not read as a k× straggler
        flagged = self.watchdog.observe(step0, (t2 - t0) / max(steps, 1))
        ran = 0
        with self.trace.span("commit_tokens", self.track, steps=steps):
            for i in range(steps):
                if all(a is None for a in self.active):
                    break        # decode faults emptied the batch early
                if i > 0:
                    self._record_live()
                self.stats["decode_steps"] += 1
                ran += 1
                for slot in range(self.n):
                    req = self.active[slot]
                    if req is None:
                        continue
                    if self.injector is not None:
                        try:
                            # per-request decode site: "this request
                            # committing its len(out)-th generated token"
                            self.injector.check(len(req.out), site="decode")
                        except Exception as e:  # noqa: BLE001 — isolate
                            if self.strict:
                                raise
                            self._finish_bad(req, "failed", repr(e),
                                             slot=slot)
                            continue
                    if self.kv_integrity and not ok_block[i, slot]:
                        # poisoned logits: this slot's pages were
                        # corrupted inside the dispatch window.  The
                        # tainted token is never committed, so ``out``
                        # holds only clean tokens — quarantine the bad
                        # page(s) and recompute-preempt just this slot
                        self.stats["nonfinite_logits"] += 1
                        self._quarantine_slot_pages(slot)
                        self._preempt_slot(slot)
                        continue
                    tok_i = int(block[i, slot])
                    req.out.append(tok_i)
                    self.pos[slot] += 1
                    self.remaining[slot] -= 1
                    if self.remaining[slot] <= 0 or tok_i == cfg.eos_id:
                        self._finish_ok(req)
                        self.trace.end("request",
                                       (self.label, f"slot{slot}"),
                                       status=req.status)
                        self.active[slot] = None
                        if self.paged:
                            self.alloc.release(slot)
            if self.kv_integrity:
                self._record_checksums()
        t3 = self.clock()
        split = (t1 - t0, t2 - t1, t3 - t2)
        for name, sec in zip(("decode_enqueue_s", "decode_wait_s",
                             "decode_commit_s"), split):
            self.stats[name] += sec
        if flagged:
            self.straggler_log.append((step0,) + split)
            self.trace.instant("straggler_flagged", self.track, step=step0,
                               enqueue_s=split[0], wait_s=split[1],
                               commit_s=split[2])
            log.warning("straggler: dispatch at step %d: enqueue %.3fs, "
                        "wait %.3fs, commit %.3fs", step0, *split)
        return ran

    def drain(self) -> None:
        """Run to quiescence: every submitted request reaches a terminal
        status.  New ``submit()``s after drain() returns start it again."""
        while not self.idle:
            self.step(max_steps=1 << 30)

    # ------------------------------------------------- snapshot / restore
    def snapshot(self) -> Dict:
        """Crash-consistent session state as a JSON-serializable dict
        (DESIGN.md §7.6).

        Captures the *host* truth only — undone requests in ``inflight()``
        order (prompt tokens, generated prefix, budgets, deadline ages),
        counters, the engine PRNG key, and the allocator's quarantine/
        accounting state.  Raw KV tensors are deliberately NOT serialized:
        :meth:`restore` re-enqueues each request with its prefix in
        ``out``, so re-admission re-prefills prompt+prefix through the
        recompute path and the resumed stream is token-identical to the
        ``generate()`` oracle.  Deadlines are stored as ages
        (``now - arrival_t``) and rebased on the restoring session's
        clock, so a half-spent deadline stays half-spent across the
        restart."""
        now = self.clock()
        reqs = [request_to_state(req, now) for req in self.inflight()]
        snap: Dict = {
            "version": 1,
            "kv_layout": self.cfg.kv_layout,
            "n_slots": self.n,
            "requests": reqs,
            "stats": dict(self.stats),
            # latency/queue/prefill histogram states ride the snapshot so
            # restored percentiles cover the pre-crash population too
            "request_timing": {name: h.state()
                               for name, h in self.hists.items()},
            "prng_key": np.asarray(
                jax.device_get(self.engine._key)).tolist(),
        }
        self.trace.instant("snapshot", self.track, requests=len(reqs))
        if self.paged:
            snap["alloc"] = {
                "quarantined": sorted(self.alloc.quarantined
                                      | self.alloc._pending_quarantine),
                "double_release": self.alloc.double_release,
                "evictions": self.alloc.evictions,
                "pages_evicted": self.alloc.pages_evicted,
                "page_high_water": self.alloc.high_water,
            }
        return snap

    def restore(self, snap: Dict) -> List[Request]:
        """Load a :meth:`snapshot` into this (idle, freshly-built)
        session: counters resume, the PRNG key is reinstated, quarantined
        pages stay out of circulation across the restart, and every
        snapshotted request is re-enqueued FIFO with its generated prefix
        — the next ``step()``/``drain()`` re-prefills and resumes each
        stream exactly where the dead process left it.  Returns the new
        :class:`Request` objects in queue order (the handles the caller
        watches; re-prefilled prompt+prefix tokens are counted in
        ``restore_recompute_tokens``)."""
        if not self.idle:
            raise RuntimeError("restore() needs an idle session — it "
                               "rebuilds the queue from the snapshot")
        if snap.get("kv_layout") != self.cfg.kv_layout:
            raise ValueError(
                f"snapshot was taken under kv_layout="
                f"{snap.get('kv_layout')!r} but this session runs "
                f"{self.cfg.kv_layout!r}")
        now = self.clock()
        self.engine._key = jnp.asarray(
            np.asarray(snap["prng_key"], np.uint32))
        for key, val in snap.get("stats", {}).items():
            if key in self.stats:
                self.stats[key] = val
        for name, state in snap.get("request_timing", {}).items():
            if name in self.hists:
                self.hists[name].load(state)
        self.stats["restores"] += 1
        if self.paged and "alloc" in snap:
            a = snap["alloc"]
            # replay quarantines with the allocator's tracer off: the
            # process that found the corruption already traced these
            # pages, and the restored pages_quarantined counter must
            # keep matching the trace's page_quarantine event count
            saved_tracer = self.alloc.tracer
            self.alloc.tracer = None
            try:
                for page in a.get("quarantined", ()):
                    self.alloc.quarantine(page)
            finally:
                self.alloc.tracer = saved_tracer
            self.alloc.double_release = a.get("double_release", 0)
            self.alloc.evictions = a.get("evictions", 0)
            self.alloc.pages_evicted = a.get("pages_evicted", 0)
            self.alloc.high_water = max(self.alloc.high_water,
                                        a.get("page_high_water", 0))
        restored: List[Request] = []
        for rs in snap.get("requests", []):
            req = request_from_state(rs, now)
            if req.out:
                # the whole prompt+prefix must re-prefill — the KV pages
                # died with the process
                self.stats["restore_recompute_tokens"] += \
                    len(req.tokens) + len(req.out)
            # bypass submit(): the snapshotted stats already counted
            # these requests once
            self.queue.append(req)
            restored.append(req)
        self.trace.instant("restore", self.track,
                           requests=len(restored))
        return restored

    def stats_snapshot(self) -> Dict:
        """Current counters in the ``Engine.paging_stats`` shape; callable
        at any point in the session (the router snapshots mid-flight)."""
        stats = dict(self.stats)
        stats["straggler_decode_steps"] = len(self.watchdog.events)
        stats["request_timing"] = {name: h.state()
                                   for name, h in self.hists.items()}
        stats["latency_percentiles"] = obs_metrics.timing_percentiles(
            stats["request_timing"])
        if self.paged:
            stats.update(self.alloc.stats())
            stats["kv_layout"] = "paged"
            # dense-equivalent residency: what (n_slots, S_max) slabs pin
            stats["dense_equiv_tokens"] = self.n * self.cfg.max_seq
            stats["paged_peak_tokens"] = stats["page_high_water"] \
                * self.geom.page_size
        else:
            stats["kv_layout"] = "dense"
        return stats
