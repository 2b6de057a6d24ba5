"""RgCSR SpMM (sparse A × dense X) Pallas TPU kernel.

This is the kernel the LM framework actually uses (SparseLinear: pruned
weight matrix in RgCSR × activation batch).  Extending the paper's SpMV
schedule to SpMM multiplies arithmetic intensity by ``d`` (the dense width)
per stored matrix element — which is why weight sparsity can pay on TPU
despite SpMV itself being hopelessly memory-bound (paper §1: intensity ≤ 1).

Schedule: grid ``(d_tiles, num_steps)`` — step dim innermost so the output
block ``(group, d_tile)`` is revisited consecutively; the matrix streams
once per d-tile.

**The row gather runs in XLA, before the kernel** (as in the SpMV kernel:
the TPU compiler has no general in-kernel gather).  ``X[columns2d]`` is
formed as an ``(S, d, G)`` stream — slot-major like the value tile, with
the dense width on sublanes and the group's rows on lanes — so slot ``k``
of a step is the leading-dim slice ``xg[k]: (DT, G)`` and its weights
``values[k]: (1, G)`` broadcast over sublanes with no relayout.  The kernel
accumulates ``Yᵀ`` of each group in a float32 ``(DT, G)`` block; the
wrapper transposes back.  The gathered stream is ``d`` times the matrix
size in HBM bytes: the cost of running without an in-kernel gather.

**Chunk coarsening** (DESIGN.md §3): one grid step processes
``chunks_per_step`` 8-slot chunks of one group — the same step table and
group-padded ``(S, G)`` storage as the SpMV kernel, so one
:class:`repro.kernels.ops.RgCSRPlan` drives both kernels.

Like the SpMV kernel, the output index map is the step table alone, so
adaptive (length-regrouped) plans run unchanged: ``y`` rows come back in
the permuted row space and ``ops.rgcsr_spmm`` fuses the inverse gather +
COO spill tail on the way out (DESIGN.md §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128

__all__ = ["rgcsr_spmm_kernel", "rgcsr_spmm_pallas"]


def rgcsr_spmm_kernel(step_group_ref, step_first_ref,
                      values_ref, xg_ref, y_ref):
    """Blocks: values (R, G), R = 8·chunks_per_step; gathered X
    (R, DT, G); y (DT, G) float32 — the group's rows on lanes."""
    s = pl.program_id(1)

    @pl.when(step_first_ref[s] == 1)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    acc = y_ref[...]
    for k in range(values_ref.shape[0]):        # static unroll: R FMA waves
        w = values_ref[pl.ds(k, 1), :].astype(jnp.float32)      # (1, G)
        acc = acc + xg_ref[k].astype(jnp.float32) * w
    y_ref[...] = acc


@functools.partial(
    jax.jit,
    static_argnames=("n_groups", "group_size", "d_tile", "chunks_per_step",
                     "interpret"))
def rgcsr_spmm_pallas(step_group, step_first, values2d, columns2d, x,
                      *, n_groups: int, group_size: int, d_tile: int = LANES,
                      chunks_per_step: int = 1, interpret: bool = True):
    """Launch RgCSR SpMM.  ``x``: (n_cols, d); returns (n_groups·G, d) in
    the result dtype of ``values2d`` and ``x``."""
    g = group_size
    rows_per_step = chunks_per_step * SUBLANES
    d = x.shape[1]
    d_pad = -(-max(d, 1) // d_tile) * d_tile
    out_dtype = jnp.result_type(values2d.dtype, x.dtype)
    x_pad = jnp.pad(x, ((0, 0), (0, d_pad - d)))
    xg = jnp.take(x_pad.T, columns2d, axis=1)            # (d_pad, S, G)
    xg = jnp.transpose(xg, (1, 0, 2))                    # (S, d_pad, G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(d_pad // d_tile, step_group.shape[0]),
        in_specs=[
            pl.BlockSpec((rows_per_step, g), lambda t, s, sg, sf: (s, 0)),
            pl.BlockSpec((rows_per_step, d_tile, g),
                         lambda t, s, sg, sf: (s, t, 0)),
        ],
        out_specs=pl.BlockSpec((None, d_tile, g),
                               lambda t, s, sg, sf: (sg[s], t, 0)),
    )
    yt = pl.pallas_call(
        rgcsr_spmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, d_pad, g), jnp.float32),
        interpret=interpret,
        name="rgcsr_spmm",
    )(step_group, step_first, values2d, xg)
    y = jnp.transpose(yt, (0, 2, 1)).reshape(n_groups * g, d_pad)
    return y[:, :d].astype(out_dtype)
