"""Pure-jnp SpMV / SpMM reference implementations for every format.

These are the *oracles*: jit-compatible, vectorized, numerically identical to
``A @ x`` up to floating-point reassociation.  The Pallas kernels in
:mod:`repro.kernels` are validated against these; higher layers (SparseLinear,
the benchmark harness) dispatch here on CPU and to the kernels on TPU.

The CSR path mirrors the paper's "scalar CSR" only in semantics — a data-
parallel segment-sum, since a literal one-thread-per-row walk has no TPU
analogue (DESIGN.md §2).
"""
from __future__ import annotations

import functools
from typing import Union

import jax
import jax.numpy as jnp

from repro.core.formats import (
    COO,
    CSR,
    ELLPACK,
    BlockedCSR,
    HybridEllCoo,
    RgCSR,
    ShardedRgCSR,
    SlicedEllpack,
)

Matrix = Union[CSR, COO, ELLPACK, HybridEllCoo, BlockedCSR, RgCSR,
               SlicedEllpack, ShardedRgCSR]

__all__ = ["spmv", "spmm"]


def _segment_matvec(values, columns, row_ids, x, n_rows):
    """y[r] = sum_{i: row_ids[i]==r} values[i] * x[columns[i]]."""
    prods = values * jnp.take(x, columns, axis=0)
    return jax.ops.segment_sum(prods, row_ids, num_segments=n_rows)


def _segment_matmat(values, columns, row_ids, x, n_rows):
    """Y[r, :] = sum values[i] * X[columns[i], :]."""
    gathered = jnp.take(x, columns, axis=0)            # (nnz, d)
    prods = gathered * values[:, None]
    return jax.ops.segment_sum(prods, row_ids, num_segments=n_rows)


# ---------------------------------------------------------------------------
# per-format spmv
# ---------------------------------------------------------------------------


def spmv_csr(a: CSR, x):
    return _segment_matvec(a.values, a.columns, a.row_ids, x, a.shape[0])


def spmv_coo(a: COO, x):
    return _segment_matvec(a.values, a.columns, a.rows, x, a.shape[0])


def spmv_ellpack(a: ELLPACK, x):
    # slot-major: y = sum_k values[k, :] * x[columns[k, :]]
    gathered = jnp.take(x, a.columns, axis=0)           # (K, N)
    y = jnp.sum(a.values * gathered, axis=0)
    return y[: a.shape[0]]


def spmv_hybrid(a: HybridEllCoo, x):
    gathered = jnp.take(x, a.ell_columns, axis=0)
    y = jnp.sum(a.ell_values * gathered, axis=0)[: a.shape[0]]
    if a.coo_values.shape[0]:
        y = y + _segment_matvec(a.coo_values, a.coo_columns, a.coo_rows, x,
                                a.shape[0])
    return y


def spmv_blocked_csr(a: BlockedCSR, x):
    bs = a.block_size
    n_cols_pad = (-a.shape[1]) % bs
    xp = jnp.pad(x, (0, n_cols_pad))
    xb = xp.reshape(-1, bs)                              # (n_block_cols, bs)
    gathered = jnp.take(xb, a.block_columns, axis=0)     # (n_blocks, bs)
    prods = jnp.einsum("bij,bj->bi", a.values, gathered)  # (n_blocks, bs)
    nbr = a.block_row_pointers.shape[0] - 1
    yb = jax.ops.segment_sum(prods, a.block_row_ids, num_segments=nbr)
    return yb.reshape(-1)[: a.shape[0]]


def spmv_rgcsr(a: RgCSR, x):
    """Slot-major grouped SpMV.  Padding values are exact zeros, so summing
    them is a no-op — semantically identical to the paper's rowLengths
    early-exit (which saves *work*, not correctness).  The Pallas kernel
    realizes the actual work-skip via its chunk table."""
    return _segment_matvec(a.values, a.columns, a.row_of_element, x, a.shape[0])


def spmv_sliced_ellpack(a: SlicedEllpack, x):
    return _segment_matvec(a.values, a.columns, a.row_of_element, x, a.shape[0])


# ---------------------------------------------------------------------------
# per-format spmm (A @ X, X dense (n, d)) — needed by SparseLinear
# ---------------------------------------------------------------------------


def spmm_csr(a: CSR, x):
    return _segment_matmat(a.values, a.columns, a.row_ids, x, a.shape[0])


def spmm_coo(a: COO, x):
    return _segment_matmat(a.values, a.columns, a.rows, x, a.shape[0])


def spmm_ellpack(a: ELLPACK, x):
    gathered = jnp.take(x, a.columns, axis=0)            # (K, N, d)
    y = jnp.sum(a.values[..., None] * gathered, axis=0)
    return y[: a.shape[0]]


def spmm_hybrid(a: HybridEllCoo, x):
    gathered = jnp.take(x, a.ell_columns, axis=0)
    y = jnp.sum(a.ell_values[..., None] * gathered, axis=0)[: a.shape[0]]
    if a.coo_values.shape[0]:
        y = y + _segment_matmat(a.coo_values, a.coo_columns, a.coo_rows, x,
                                a.shape[0])
    return y


def spmm_blocked_csr(a: BlockedCSR, x):
    bs = a.block_size
    d = x.shape[1]
    n_cols_pad = (-a.shape[1]) % bs
    xp = jnp.pad(x, ((0, n_cols_pad), (0, 0)))
    xb = xp.reshape(-1, bs, d)
    gathered = jnp.take(xb, a.block_columns, axis=0)     # (n_blocks, bs, d)
    prods = jnp.einsum("bij,bjd->bid", a.values, gathered)
    nbr = a.block_row_pointers.shape[0] - 1
    yb = jax.ops.segment_sum(prods, a.block_row_ids, num_segments=nbr)
    return yb.reshape(-1, d)[: a.shape[0]]


def spmm_rgcsr(a: RgCSR, x):
    return _segment_matmat(a.values, a.columns, a.row_of_element, x, a.shape[0])


def spmm_sliced_ellpack(a: SlicedEllpack, x):
    return _segment_matmat(a.values, a.columns, a.row_of_element, x, a.shape[0])


_SPMV = {
    CSR: spmv_csr,
    COO: spmv_coo,
    ELLPACK: spmv_ellpack,
    HybridEllCoo: spmv_hybrid,
    BlockedCSR: spmv_blocked_csr,
    RgCSR: spmv_rgcsr,
    SlicedEllpack: spmv_sliced_ellpack,
}

_SPMM = {
    CSR: spmm_csr,
    COO: spmm_coo,
    ELLPACK: spmm_ellpack,
    HybridEllCoo: spmm_hybrid,
    BlockedCSR: spmm_blocked_csr,
    RgCSR: spmm_rgcsr,
    SlicedEllpack: spmm_sliced_ellpack,
}


@functools.partial(jax.jit, static_argnames=())
def _identity(x):
    return x


def _use_kernel(a, impl: str, kernel_formats=(RgCSR,)) -> bool:
    """Kernel dispatch policy.

    ``impl='ref'`` — always the jnp oracle.  ``impl='kernel'`` — the Pallas
    kernel (interpret mode off the TPU); a matrix no kernel can run raises
    instead of silently answering from the oracle: a format without a
    kernel (``kernel_formats``), or a matrix traced under ``jit`` (kernel
    plans index host metadata, so the matrix must be concrete — close over
    it rather than passing it as a jit argument).  ``impl='auto'`` — the
    kernel on TPU for RgCSR matrices it can run, the oracle elsewhere
    (including under tracing, where XLA shards and fuses the segment-sum).
    """
    if impl not in ("auto", "ref", "kernel"):   # validate unconditionally,
        raise ValueError(                        # even on oracle-only paths
            f"unknown impl {impl!r}; options: auto/ref/kernel")
    if impl == "ref":
        return False
    traced = any(isinstance(leaf, jax.core.Tracer)
                 for leaf in jax.tree_util.tree_leaves(a))
    if impl == "kernel":
        if not isinstance(a, kernel_formats):
            raise ValueError(
                f"impl='kernel': no Pallas kernel for {type(a).__name__} "
                f"here (kernels: "
                f"{', '.join(f.__name__ for f in kernel_formats)})")
        if traced:
            raise ValueError(
                "impl='kernel' needs a concrete matrix: its kernel plan is "
                "built on the host, so the matrix cannot be a jit argument "
                "(close over it, or use impl='auto'/'ref')")
        return True      # explicit request: let make_plan raise if unrunnable
    # auto: only matrices the TPU kernel can actually run (group_size a
    # multiple of 128 lanes, slots sublane-packed); others — e.g. the small
    # modeled group sizes the format tests sweep — stay on the oracle
    # instead of crashing in make_plan.
    return (isinstance(a, RgCSR) and not traced
            and jax.default_backend() == "tpu"
            and a.group_size % 128 == 0 and a.slot_pad % 8 == 0)


def _sharded_dispatch(a: ShardedRgCSR, mesh, mesh_axis,
                      chunks_per_step, ordering, spill_threshold, x_mode,
                      shard_configs=None):
    """Resolve the sharded plan + mesh axis for a ShardedRgCSR call."""
    from repro.kernels import ops as kops
    if mesh is None:
        raise ValueError(
            "ShardedRgCSR spmv/spmm needs mesh= (and usually mesh_axis=): "
            "the row shards execute under shard_map over a 1-D mesh axis "
            "(DESIGN.md §11)")
    if mesh_axis is None:
        from repro.sharding import resolve_spmv_shard_axis
        mesh_axis = resolve_spmv_shard_axis(mesh)
    plan = kops.get_sharded_plan(a, chunks_per_step=chunks_per_step,
                                 ordering=ordering,
                                 spill_threshold=spill_threshold,
                                 x_mode=x_mode, shard_configs=shard_configs)
    return plan, mesh_axis


def spmv(a: Matrix, x, *, impl: str = "auto", chunks_per_step: int = 1,
         ordering: str = "block", spill_threshold: int = 0,
         mesh=None, mesh_axis: str | None = None,
         x_mode: str = "replicated", shard_configs=None):
    """``y = A @ x`` for any of the paper's formats.

    RgCSR matrices can dispatch to the Pallas kernel through the process-wide
    :data:`repro.kernels.ops.PLAN_CACHE` (see ``impl`` in :func:`_use_kernel`)
    so repeated SpMV on the same matrix — the serving / iterative-solver
    pattern — builds its host-side execution plan exactly once.  ELLPACK
    and Hybrid matrices run the ELL kernel under ``impl='kernel'`` (plus
    the Hybrid's COO tail as a segment-sum).

    ``ordering='adaptive'`` selects the length-aware regrouped plan (and,
    with ``spill_threshold > 0``, the pathological-row COO spill); results
    are identical up to fp reassociation — the plan's fused inverse gather
    restores the original row order.  Oracle paths ignore both knobs.

    :class:`ShardedRgCSR` matrices run the multi-device shard_map path
    (DESIGN.md §11/§12): ``mesh`` is required, ``mesh_axis`` defaults to
    the partitioner's ``sparse_rows`` rule, ``x_mode`` picks replicated-x
    vs the local/remote split with its plan-driven sparse exchange, and
    ``shard_configs`` (one ``(chunks_per_step, ordering, spill_threshold)``
    per shard — e.g. the per-shard autotune winners) overrides the global
    schedule knobs shard-by-shard.
    """
    if isinstance(a, ShardedRgCSR):
        from repro.kernels import ops as kops
        plan, axis = _sharded_dispatch(a, mesh, mesh_axis, chunks_per_step,
                                       ordering, spill_threshold, x_mode,
                                       shard_configs)
        return kops.sharded_rgcsr_spmv(plan, x, mesh=mesh, axis=axis)
    if _use_kernel(a, impl, (RgCSR, ELLPACK, HybridEllCoo)):
        from repro.kernels import ops as kops
        if not isinstance(a, RgCSR):
            return kops.hybrid_spmv(a, x)
        plan = kops.get_plan(a, chunks_per_step=chunks_per_step,
                             ordering=ordering,
                             spill_threshold=spill_threshold)
        return kops.rgcsr_spmv(plan, x)
    return _SPMV[type(a)](a, x)


def spmm(a: Matrix, x, *, impl: str = "auto", chunks_per_step: int = 1,
         ordering: str = "block", spill_threshold: int = 0,
         mesh=None, mesh_axis: str | None = None,
         x_mode: str = "replicated", shard_configs=None):
    """``Y = A @ X`` (X dense ``(n, d)``) for any of the paper's formats.

    Same PlanCache-backed kernel dispatch (and adaptive-plan / sharded
    knobs, including per-shard ``shard_configs``) as :func:`spmv`.
    """
    if isinstance(a, ShardedRgCSR):
        from repro.kernels import ops as kops
        plan, axis = _sharded_dispatch(a, mesh, mesh_axis, chunks_per_step,
                                       ordering, spill_threshold, x_mode,
                                       shard_configs)
        return kops.sharded_rgcsr_spmm(plan, x, mesh=mesh, axis=axis)
    if _use_kernel(a, impl):
        from repro.kernels import ops as kops
        plan = kops.get_plan(a, chunks_per_step=chunks_per_step,
                             ordering=ordering,
                             spill_threshold=spill_threshold)
        return kops.rgcsr_spmm(plan, x)
    return _SPMM[type(a)](a, x)
