#!/usr/bin/env python3
"""Prove on a TPU that the system's main paths run and give right answers.

Usage, from the repository root on a machine with a TPU:

  python3 chip_smoke.py             # one chip: SpMV, SpMM, granite-3-2b serving
  python3 chip_smoke.py --chips 4   # sharded SpMV over a 4-device mesh only

The phases run in one process, always in the same order:

1. device check — prints platform, device kind and count, and exits
   non-zero before any phase unless JAX reports a TPU;
2. ``spmv`` — a 27-point stencil on a 64³ grid (262,144 rows, 6,859,000
   nonzeros, ≈64 MiB as RgCSR at G=128, so the kernel streams from HBM),
   built as CSR without a dense array, through ``repro.core.spmv`` with
   ``impl="kernel"`` (RgCSR, block ordering) and the Hybrid (ELL+COO)
   format, each compared with a float64 host CSR product;
3. ``spmm`` — ``repro.core.spmm(impl="kernel")`` of an (8192 × 2048)
   weight pruned to density 0.25 (granite-3-2b's FFN shape) times a
   (2048 × 128) activation, against the dense product at
   ``precision="highest"``, in float32 and bfloat16;
4. ``serve`` — granite-3-2b at published widths and random weights through
   ``Engine.serve`` (paged KV, fused decode loop): 8 requests of 16 prompt
   tokens and 16 new tokens on 4 slots, ``max_seq`` 128, held to a float32
   reference and to ``Engine.generate()`` batch-1 (see ``LOGIT_TOL``).

With ``--chips 4`` only the sharded phase runs: split and replicated
sharded SpMV over a 4-device ``model`` axis, compared with the one-chip
kernel result and the host reference, and a check that every device holds
its own shard of the plan.

A failed check raises, so the script exits non-zero.  The last line of
standard output is one JSON object::

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The persistent compilation cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

# Served tokens must be near-greedy under a float32 reference forward:
# max(ref logits) - ref logit of the served token <= LOGIT_TOL.  Logits of
# the random-weight model are ~N(0, 1) by construction (tied 1/sqrt(d)
# embedding after RMS norm), so the top logit is ≈4.3 and the mean top-1 to
# top-2 spacing ≈0.2-0.4.  Serving runs in bfloat16: at full width on the
# CPU the bf16 logits drift from the fp32 forward by at most 0.042 (2
# layers) and 0.048 (8 layers), so a bf16-greedy token sits within ~0.1 of
# the fp32 max.  0.25 keeps that margin; a broken cache or position index
# picks tokens ~4 below the max.  Batched paged serving and batch-1 dense
# generate() can flip argmax on such near-ties, so token identity with
# generate() is reported but not required; both streams are held to the
# same reference.
LOGIT_TOL = 0.25
SPMV_TOL = 1e-5          # relative L2, float32 against a float64 product
SPMM_TOL = {"float32": 1e-5, "bfloat16": 1e-2}   # bf16 output rounding ≈1e-3


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_l2(y, ref) -> float:
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.linalg.norm(y - ref) / max(np.linalg.norm(ref), 1e-300))


def host_csr_matvec(values, columns, row_ptr, x):
    """float64 reference y = A @ x from a host CSR triplet."""
    rows = np.repeat(np.arange(len(row_ptr) - 1), np.diff(row_ptr))
    prods = np.asarray(values, np.float64) * np.asarray(x, np.float64)[
        np.asarray(columns)]
    return np.bincount(rows, weights=prods, minlength=len(row_ptr) - 1)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how many XLA
    compilations ran, from ``jax.monitoring`` events."""

    _instance = None

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileClock":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event.endswith("backend_compile_duration"):
                self.compiles += 1


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def peak_bytes(device) -> str:
    stats = device.memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return f"{stats['peak_bytes_in_use']} B"


def _kernel_in_program(mat, x) -> bool:
    """Whether the kernel launch behind ``spmv``/``spmm(mat, x,
    impl="kernel")`` — the cached plan, the same arguments — lowers to a
    TPU custom call."""
    from repro.core.formats import RgCSR
    from repro.kernels import ops as kops
    from repro.kernels.ell_spmv import ell_spmv_pallas
    from repro.kernels.rgcsr_spmm import rgcsr_spmm_pallas
    from repro.kernels.rgcsr_spmv import rgcsr_spmv_pallas
    if not isinstance(mat, RgCSR):
        plan = kops.make_ell_plan(mat)
        lowered = ell_spmv_pallas.lower(plan.values2d, plan.columns2d, x,
                                        interpret=False)
    else:
        plan = kops.get_plan(mat)
        args = (plan.step_group, plan.step_first, plan.values2d,
                plan.columns2d, x)
        layout = dict(n_groups=plan.n_groups, group_size=plan.group_size,
                      chunks_per_step=plan.chunks_per_step, interpret=False)
        if x.ndim == 1:
            lowered = rgcsr_spmv_pallas.lower(
                *args, plan.diag_start, plan.diag_shift,
                diag_steps=plan.diag_steps, x_pad=plan.x_pad, **layout)
        else:
            lowered = rgcsr_spmm_pallas.lower(*args, **layout)
    return "tpu_custom_call" in lowered.as_text()


# ---------------------------------------------------------------- phases


def spmv_phase(grid=64, *, seed: int = 0, on_tpu: bool = True) -> dict:
    """RgCSR kernel and Hybrid SpMV on a 27-point stencil, against float64."""
    import jax.numpy as jnp
    from repro.core import spmv
    from repro.core.formats import HybridEllCoo, RgCSR
    from repro.core.suite import stencil27_csr

    values, columns, row_ptr, shape = stencil27_csr(grid, seed)
    t0 = time.perf_counter()
    a = RgCSR.from_csr(values, columns, row_ptr, shape)
    hyb = HybridEllCoo.from_csr(values, columns, row_ptr, shape)
    print(f"spmv: 27-point stencil on {grid} grid: {shape[0]} rows, "
          f"{len(values)} nnz; RgCSR {a.storage_bytes() / 2**20:.1f} MiB at "
          f"G={a.group_size}, Hybrid k1={hyb.k1} + {hyb.coo_values.shape[0]}"
          f" COO; built in {time.perf_counter() - t0:.2f} s", flush=True)
    x = np.random.default_rng(seed + 1).standard_normal(shape[1]).astype(
        np.float32)
    ref = host_csr_matvec(values, columns, row_ptr, x)
    xd = jnp.asarray(x)
    out = {}
    for name, mat in (("rgcsr", a), ("hybrid", hyb)):
        y = np.asarray(spmv(mat, xd, impl="kernel"))
        err = rel_l2(y, ref)
        print(f"spmv: {name} kernel relative L2 error {err:.3e} "
              f"(limit {SPMV_TOL:g})", flush=True)
        check(np.isfinite(y).all() and err <= SPMV_TOL,
              f"{name} SpMV error {err:.3e} above {SPMV_TOL:g}")
        if on_tpu:
            found = _kernel_in_program(mat, xd)
            print(f"spmv: {name} tpu_custom_call in lowered program: "
                  f"{found}", flush=True)
            check(found, f"{name} SpMV program has no tpu_custom_call")
        out[name] = err
    return out


def spmm_phase(d_out: int = 8192, d_in: int = 2048, d: int = 128,
               density: float = 0.25, *, seed: int = 0,
               on_tpu: bool = True) -> dict:
    """RgCSR SpMM kernel at granite-3-2b's FFN shape, against dense."""
    import jax
    import jax.numpy as jnp
    from repro.core import spmm
    from repro.core.formats import RgCSR

    rng = np.random.default_rng(seed)
    w = rng.standard_normal((d_out, d_in)).astype(np.float32)
    w[np.abs(w) < np.quantile(np.abs(w), 1.0 - density)] = 0.0   # prune
    x = rng.standard_normal((d_in, d)).astype(np.float32)
    out = {}
    for dtype in ("float32", "bfloat16"):
        wd = w.astype(jnp.dtype(dtype))
        a = RgCSR.from_dense(wd)
        xd = jnp.asarray(x, dtype)
        y = np.asarray(spmm(a, xd, impl="kernel").astype(jnp.float32))
        ref = np.asarray(jnp.dot(jnp.asarray(wd, jnp.float32),
                                 xd.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST))
        err = rel_l2(y, ref)
        print(f"spmm: {dtype} ({d_out}x{d_in}, density {density}) x "
              f"({d_in}x{d}): {a.nnz} nnz, relative L2 error vs dense "
              f"{err:.3e} (limit {SPMM_TOL[dtype]:g})", flush=True)
        check(np.isfinite(y).all() and err <= SPMM_TOL[dtype],
              f"{dtype} SpMM error {err:.3e} above {SPMM_TOL[dtype]:g}")
        if on_tpu:
            found = _kernel_in_program(a, xd)
            print(f"spmm: {dtype} tpu_custom_call in lowered program: "
                  f"{found}", flush=True)
            check(found, f"{dtype} SpMM program has no tpu_custom_call")
        out[dtype] = err
    return out


def _reference_gaps(forward, vocab, params, prompts, streams):
    """max(ref) - ref[token] at every generated position, teacher-forced
    through the float32 reference ``forward`` on each stream's own prefix."""
    import jax
    import jax.numpy as jnp
    p_len = prompts.shape[1]
    tokens = np.concatenate([prompts, streams[:, :-1]], axis=1)
    with jax.default_matmul_precision("highest"):
        logits = forward(params, jnp.asarray(tokens, jnp.int32))
    ref = np.asarray(logits, np.float32)[:, p_len - 1:, :vocab]
    check(np.isfinite(ref).all(), "reference logits are not finite")
    picked = np.take_along_axis(ref, streams[..., None], axis=-1)[..., 0]
    return ref.max(axis=-1) - picked


def serve_phase(cfg, *, n_requests: int = 8, prompt_len: int = 16,
                max_new: int = 16, n_slots: int = 4, max_seq: int = 128,
                seed: int = 0) -> dict:
    """granite-3-2b through Engine.serve: paged KV, fused decode loop."""
    import jax
    from repro.models import LanguageModel
    from repro.serve import Engine, Request, ServeConfig

    clock = CompileClock.get()
    device = jax.devices()[0]
    c0, t0 = clock.seconds, time.perf_counter()
    engine = Engine(cfg, ServeConfig(max_seq=max_seq, n_slots=n_slots,
                                     kv_layout="paged", seed=seed))
    jax.block_until_ready(engine.params)
    print(f"serve: {cfg.name}: {engine.model.n_params()} params "
          f"({cfg.param_dtype} weights, {cfg.dtype} compute), init "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (n_requests, prompt_len)).astype(np.int32)

    # warm-up: one request compiles the prefill and the fused decode loop
    t0 = time.perf_counter()
    engine.serve([Request(tokens=prompts[0], max_new_tokens=max_new)])
    print(f"serve: warm-up (1 request) {time.perf_counter() - t0:.1f} s; "
          f"compile so far {clock.seconds - c0:.1f} s", flush=True)

    # observe the fused loop's own per-step logit finiteness screen
    fused, finite = engine._fused_decode, []

    def watched(*args):
        res = fused(*args)
        active, steps = np.asarray(args[4]), int(res[1])
        finite.append(bool(np.asarray(res[5])[:steps][:, active].all()))
        return res

    engine._fused_decode = watched
    reqs = [Request(tokens=p, max_new_tokens=max_new) for p in prompts]
    n0, t0 = clock.compiles, time.perf_counter()
    engine.serve(reqs)
    serve_s = time.perf_counter() - t0
    compiles_in_window = clock.compiles - n0
    engine._fused_decode = fused
    statuses = Counter(r.status for r in reqs)
    n_tokens = sum(len(r.out or []) for r in reqs)
    print(f"serve: {n_requests} requests x {max_new} new tokens on "
          f"{n_slots} slots: status {dict(statuses)}, "
          f"{engine.paging_stats['decode_dispatches']} fused dispatches, "
          f"{compiles_in_window} compilations in the timed window", flush=True)
    print(f"serve: smoke figure, not a metric: {n_tokens / serve_s:.1f} "
          f"served tokens/s ({serve_s:.2f} s wall)", flush=True)
    check(statuses == Counter({"ok": n_requests}),
          f"not every request ok: {dict(statuses)}")
    streams = np.array([r.out for r in reqs], np.int64)
    check(streams.shape == (n_requests, max_new), "short output")
    check(((streams >= 0) & (streams < cfg.vocab)).all(),
          "served token outside the vocabulary")
    check(finite and all(finite), "non-finite logits in a fused dispatch")

    gen = np.stack([engine.generate(p[None], max_new)[0] for p in prompts]
                   ).astype(np.int64)
    same = int((gen == streams).all(axis=1).sum())
    ref_model = LanguageModel(dataclasses.replace(cfg, dtype="float32"))
    forward = jax.jit(lambda p, t: ref_model.forward(p, {"tokens": t})[0])
    gap_serve = _reference_gaps(forward, cfg.vocab, engine.params, prompts,
                                streams)
    gap_gen = _reference_gaps(forward, cfg.vocab, engine.params, prompts,
                              gen)
    print(f"serve: token-identical to generate() batch-1 in {same}/"
          f"{n_requests} requests; max logit gap to the fp32 reference: "
          f"served {gap_serve.max():.4f}, generate() {gap_gen.max():.4f} "
          f"(limit {LOGIT_TOL})", flush=True)
    check(gap_serve.max() <= LOGIT_TOL,
          f"served tokens {gap_serve.max():.4f} below the reference max")
    check(gap_gen.max() <= LOGIT_TOL,
          f"generate() tokens {gap_gen.max():.4f} below the reference max")
    print(f"serve: compile {clock.seconds - c0:.1f} s in this phase; "
          f"peak_bytes_in_use {peak_bytes(device)}", flush=True)
    return {"statuses": dict(statuses), "same_as_generate": same,
            "max_gap_served": float(gap_serve.max()),
            "max_gap_generate": float(gap_gen.max()),
            "compiles_in_window": compiles_in_window}


def sharded_phase(grid=(16, 32, 32), n_devices: int = 4, *, seed: int = 0,
                  ) -> dict:
    """Split and replicated sharded SpMV over a ``model`` mesh axis."""
    import jax
    import jax.numpy as jnp
    from repro.core import spmv
    from repro.core.formats import RgCSR, ShardedRgCSR
    from repro.core.suite import stencil27_csr
    from repro.kernels import ops as kops
    from repro.launch.mesh import make_mesh

    devices = jax.devices()[:n_devices]
    check(len(devices) == n_devices,
          f"need {n_devices} devices, found {len(jax.devices())}")
    values, columns, row_ptr, shape = stencil27_csr(grid, seed)
    x = np.random.default_rng(seed + 1).standard_normal(shape[1]).astype(
        np.float32)
    ref = host_csr_matvec(values, columns, row_ptr, x)
    xd = jnp.asarray(x)
    y1 = np.asarray(spmv(RgCSR.from_csr(values, columns, row_ptr, shape),
                         xd, impl="kernel"))
    err1 = rel_l2(y1, ref)
    print(f"sharded: 27-point stencil on {grid} grid: {shape[0]} rows, "
          f"{len(values)} nnz; one-chip kernel relative L2 error "
          f"{err1:.3e}", flush=True)
    check(err1 <= SPMV_TOL, f"one-chip SpMV error {err1:.3e}")
    sm = ShardedRgCSR.from_csr(values, columns, row_ptr, shape,
                               n_shards=n_devices)
    mesh = make_mesh((n_devices,), ("model",), devices=devices)
    out = {"one_chip": err1}
    for x_mode in ("replicated", "split"):
        y = np.asarray(spmv(sm, xd, mesh=mesh, mesh_axis="model",
                            x_mode=x_mode))
        err, vs_one = rel_l2(y, ref), rel_l2(y, y1)
        plan = kops.get_sharded_plan(sm, x_mode=x_mode)
        placed = kops.sharded_plan_placement(plan, mesh=mesh, axis="model")
        owners = []
        for arr in placed:
            shards = arr.addressable_shards
            owners.append(sorted(s.device.id for s in shards))
            check({s.device for s in shards} == set(devices)
                  and all(s.data.shape[0] == 1 for s in shards),
                  f"{x_mode}: a plan array is not split one shard per "
                  f"device: {[(s.device.id, s.data.shape) for s in shards]}")
        print(f"sharded: {x_mode}: relative L2 error {err:.3e} vs host, "
              f"{vs_one:.3e} vs one chip; {len(placed)} plan arrays, each "
              f"one shard per device on devices {owners[0]}; remote cols "
              f"per shard {list(plan.shard_remote_cols)}", flush=True)
        check(err <= SPMV_TOL and vs_one <= SPMV_TOL,
              f"{x_mode} sharded SpMV error {err:.3e} / {vs_one:.3e}")
        out[x_mode] = err
    return out


# ------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded SpMV phase on 4 devices")
    args = ap.parse_args(argv)

    import jax
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} (jax {jax.__version__})", flush=True)
    if info["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(n_devices=4)
    else:
        spmv_phase()
        spmm_phase()
        serve_phase(get_config("granite-3-2b"))
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
