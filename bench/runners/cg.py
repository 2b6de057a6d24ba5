"""HPCG-style conjugate gradients through ``repro.core.spmv``.

Set-up builds HPCG's operator as a host CSR matrix, converts it to the
format the traffic file names, and warms the SpMV and the solver update.
The window then runs CG from x = 0 in sets of ``iterations_per_set``
iterations (HPCG's own set length), restarting until the window's seconds
are spent, with one iteration in flight while the host waits for the one
before.  ``cg_gflops`` is the CG operations of the iterations completed
over the window's seconds.

Correctness: every residual norm the window produced, and the iterates
that close the first and the last set, are compared with a float64 host
CG (``configs/hpcg.reference.py``) of the same number of iterations.
"""
from __future__ import annotations

import time

import numpy as np

import harness
import work

# Programs of the benchmark's own; all other device time in the window is
# the SpMV call's.
OWN_PROGRAMS = ("jit_bench_cg_update", "jit_bench_cg_start")


def hpcg_csr(m: int):
    """HPCG's 27-point operator on an m³ grid as a host CSR triplet
    (float32 values, int32 columns ascending within each row)."""
    n = m ** 3
    idx = np.arange(n, dtype=np.int32)
    z, y, x = idx // (m * m), (idx // m) % m, idx % m
    offsets = [(dz, dy, dx) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
               for dx in (-1, 0, 1)]
    valid = np.empty((n, 27), bool)
    for i, (dz, dy, dx) in enumerate(offsets):
        valid[:, i] = ((z + dz >= 0) & (z + dz < m) & (y + dy >= 0)
                       & (y + dy < m) & (x + dx >= 0) & (x + dx < m))
    shift = np.array([(dz * m + dy) * m + dx for dz, dy, dx in offsets],
                     np.int32)
    columns = (idx[:, None] + shift[None, :])[valid]
    lens = valid.sum(axis=1)
    values = np.where(columns == np.repeat(idx, lens), np.float32(26.0),
                      np.float32(-1.0))
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=row_ptr[1:])
    return values, columns, row_ptr, (n, n)


def _programs():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_cg_start(b):
        return jnp.zeros_like(b), jnp.vdot(b, b)

    @jax.jit
    def bench_cg_update(x, r, p, ap, rr):
        alpha = rr / jnp.vdot(p, ap)
        x = x + alpha * p
        r = r - alpha * ap
        rr_new = jnp.vdot(r, r)
        p = r + (rr_new / rr) * p
        return x, r, p, rr_new

    return bench_cg_start, bench_cg_update


def run(ctx) -> harness.RunOutput:
    import jax
    import jax.numpy as jnp
    import repro.core as core
    import devtrace

    cfg, traffic, ph = ctx.cell.config, ctx.cell.traffic, ctx.phases
    m = int(cfg["local_grid"])
    per_set = int(traffic["iterations_per_set"])
    spmv_kw = dict(traffic["spmv"])

    values, columns, row_ptr, shape = hpcg_csr(m)
    n, nnz = shape[0], len(values)
    b_host = np.add.reduceat(values, row_ptr[:-1])      # b = A·1
    ph.mark("host matrix build")
    fmt = getattr(core, traffic["format"])
    a = fmt.from_csr(values, columns, row_ptr, shape)
    jax.block_until_ready(jax.tree_util.tree_leaves(a))
    del values, columns, row_ptr
    ph.mark("format conversion and transfer")
    b = jnp.asarray(b_host)
    start, update = _programs()
    c0 = ctx.compiles.seconds
    t0 = time.perf_counter()
    y = core.spmv(a, b, **spmv_kw)
    y.block_until_ready()
    first = time.perf_counter() - t0
    compile_s = ctx.compiles.seconds - c0
    ph.items.append(("kernel plan", first - compile_s))
    ph.items.append(("SpMV compile or cache load", compile_s))
    ph.t_last = time.perf_counter()
    x0, rr0 = start(b)
    x, r, p, rr = update(x0, b, b, y, rr0)
    jax.block_until_ready(rr)
    del y
    ph.mark("solver compile and warm-up")

    flops_it = work.cg_flops_per_iteration(n, nnz)
    capture = devtrace.Capture() if ctx.trace else None
    trace_until = float(traffic["trace_seconds"])
    sets, snaps = [], {}            # per set: [rr device scalars]
    reduced, traced_its = None, 0
    n_compiles = ctx.compiles.count
    setup_s = ph.mark("window opens")
    if capture is not None:
        capture.start()
    t0 = time.perf_counter()
    done, prev, hist, marks = 0, None, None, [t0]
    while True:
        if hist is None or len(hist) == per_set:
            if hist is not None:
                snaps.setdefault(0, (len(hist), x))
            hist = []
            sets.append(hist)
            x, r, p, rr = x0, b, b, rr0
        with jax.profiler.TraceAnnotation("bench.spmv"):
            ap = core.spmv(a, p, **spmv_kw)
        with jax.profiler.TraceAnnotation("bench.cg_update"):
            x, r, p, rr = update(x, r, p, ap, rr)
        hist.append(rr)
        if prev is not None:
            with jax.profiler.TraceAnnotation("bench.wait"):
                prev.block_until_ready()
            done += 1
            marks.append(time.perf_counter())
        prev = rr
        now = time.perf_counter() - t0
        if capture is not None and reduced is None and now >= trace_until:
            rr.block_until_ready()
            traced_its = done + 1
            reduced = capture.stop()
        if now >= ctx.seconds:
            break
    prev.block_until_ready()
    done += 1
    window_s = time.perf_counter() - t0
    compiles_in_window = ctx.compiles.count - n_compiles
    device = harness.device_record()
    snaps.setdefault(0, (len(hist), x))
    snaps[len(sets) - 1] = (len(hist), x)

    # ---------------------------------------------------------- correctness
    ref = ctx.cell.reference()
    t_ref = time.perf_counter()
    resid = [np.sqrt(np.asarray(jax.device_get(h), np.float64))
             for h in sets]
    longest = max(len(h) for h in resid)
    rel, x_err = compare(ref, m, resid, list(snaps.values()))
    lim = traffic["limits"]
    checks = [harness.Check("resid_rel_err", float(np.max(rel)),
                            lim["resid_rel_err"]),
              harness.Check("x_max_err", x_err, lim["x_max_err"])]
    failed = int(np.sum(~(rel <= lim["resid_rel_err"])))
    ref_s = time.perf_counter() - t_ref

    notes = {
        "setup phases": ph.line(),
        "window": f"{done} CG iterations in {len(sets)} sets over "
                  f"{window_s:.3f} s; {compiles_in_window} compilations "
                  f"in the window; seconds between completions: min "
                  f"{np.min(np.diff(marks)):.4f} median "
                  f"{np.median(np.diff(marks)):.4f} max "
                  f"{np.max(np.diff(marks)):.4f}",
        "problem": f"HPCG {m}^3: n={n} nnz={nnz}; "
                   f"{flops_it} FLOP per iteration",
        "reference": f"float64 host CG over {longest} iterations took "
                     f"{ref_s:.1f} s (after the window, not in setup_s)",
    }
    layer = {"flops_per_iteration": flops_it,
             "spmv_bytes": work.spmv_min_bytes(n, n, nnz),
             "spmv_calls_traced": traced_its,
             "own_programs": OWN_PROGRAMS,
             "device_kind": device["kind"],
             "compiles_in_window": compiles_in_window}
    return harness.RunOutput(
        end_to_end={"cg_gflops": done * flops_it / window_s / 1e9,
                    "setup_s": setup_s},
        attempted=done, failed=failed, checks=checks, layer=layer,
        device=device, trace=reduced, notes=notes)


def compare(ref, m: int, resid_sets, snaps):
    """(relative residual gaps, largest iterate error) of CG runs against
    the float64 reference.  The iterate error is the largest entry's gap
    over the largest entry, so one wrong row shows.  ``resid_sets``: per set, ‖r_k‖;
    ``snaps``: (iteration count, iterate) pairs."""
    longest = max(len(h) for h in resid_sets)
    ref_resid, ref_x = ref.cg(m, longest, keep={k for k, _ in snaps})
    rel = np.concatenate([np.abs(h - ref_resid[:len(h)]) / ref_resid[:len(h)]
                          for h in resid_sets])
    x_err = max(float(np.max(np.abs(np.asarray(xv, np.float64) - ref_x[k]))
                      / np.max(np.abs(ref_x[k]))) for k, xv in snaps)
    return rel, x_err


def control(cell, iterations: int, dtype_name: str = "bfloat16") -> dict:
    """The reference itself, computed on the device in the next precision
    below the configuration's float32, put in the program's place and
    held to the same comparison."""
    import jax.numpy as jnp
    ref = cell.reference()
    m = int(cell.config["local_grid"])
    dtype = jnp.dtype(dtype_name)
    resid, xs = ref.cg(m, iterations, keep={iterations}, xp=jnp,
                       dtype=dtype)
    rel, x_err = compare(ref, m, [np.asarray(resid)],
                         [(iterations, xs[iterations])])
    return {"resid_rel_err": float(np.max(rel)), "x_max_err": x_err}


def calibrate(cell, args) -> list:
    """The control's readings (the problem does not depend on the seed, so
    one reading stands for every seed; each is printed per seed)."""
    reading = control(cell, args.iterations)
    return [{"seed": s, "who": "control bfloat16", **reading}
            for s in args.seeds]
