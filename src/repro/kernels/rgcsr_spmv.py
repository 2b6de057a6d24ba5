"""RgCSR SpMV as a Pallas TPU kernel — the paper's CUDA kernel, TPU-native.

Mapping from the paper's CUDA kernel (§3.4) to TPU (DESIGN.md §2):

* CUDA: one *thread* per row; a thread-block of 128 threads = one group;
  per slot step, the 128 threads read 128 consecutive values/columns
  (coalesced 128-byte segments).
* TPU:  one *lane* per row; a group of ``G`` rows (G a multiple of 128) is a
  dense ``(K_g, G)`` tile in VMEM — slot ``k`` of all rows is one (or a few)
  full 128-lane vectors.  Reading slot-major tiles from HBM is the TPU
  equivalent of coalescing: contiguous, layout-aligned DMA.

The ragged group structure (K_g varies per group — the whole point of RgCSR
vs ELLPACK) is handled with a **step table** built at plan time
(DESIGN.md §3):

* the flat grouped storage is reshaped to ``values2d/columns2d: (S, G)``
  where ``S = Σ_g K_g`` (each K_g padded to ``8 · chunks_per_step``
  sublanes);
* grid step ``s`` covers slot rows ``[R·s, R·(s+1))`` with
  ``R = 8 · chunks_per_step`` and belongs to exactly one group
  ``step_group[s]`` (K_g % R == 0 guarantees no step straddles a group);
* the grid is ``(num_steps,)`` — *no* grid step is spent on nonexistent
  slots of short groups.  This realizes the paper's "skip meaningless
  arithmetic via rowLengths" at DMA granularity, which is what matters on a
  memory-bound op (the VPU flops on padding are free; the HBM bytes and
  grid steps are not).

**Chunk coarsening** (``chunks_per_step`` ∈ {1, 2, 4, 8}): one grid step
processes ``chunks_per_step`` 8-slot chunks of the same group, accumulating
across the coarsened tile in-kernel.  Fewer grid steps → less per-step
launch/DMA-descriptor overhead and a larger contiguous matrix DMA per step;
the cost is padding short groups up to the coarsened tile (masked by exact
zeros placed at plan time via the chunk table).  The autotuner
(:mod:`repro.kernels.autotune`) measures this trade per matrix.

**The x gather runs in XLA, before the kernel.**  The TPU compiler has no
general in-kernel gather (Mosaic accepts only 2-D gathers within a tile),
so ``x[columns2d]`` is formed by one XLA gather into an ``(S, G)`` stream
laid out exactly like the value tile, and the kernel multiplies the two
``(R, G)`` tiles and reduces over slots.  x itself is never staged into
VMEM, so its width is not bounded by VMEM.  The price is one extra
``(S, G)`` stream of HBM bytes (written by the gather, read by the kernel).

Scalar-prefetch carries ``step_group`` (output index map) and ``step_first``
(accumulator init).  The output is ``(n_groups, 1, G)`` float32 — a
``(1, G)`` block whose last two dims equal the array's, which the TPU
tiling rule accepts — and the same output block is revisited only by
consecutive grid steps (steps of a group are contiguous), which is the
Pallas TPU requirement for read-modify-write output accumulation.

**Permuted row space** (adaptive plans, DESIGN.md §5): the kernel is
deliberately agnostic to *which* rows a group holds — the step table is the
only output index map, and the accumulator init (``step_first``) fires on
each group's first step regardless of row identity.  An adaptive plan
exploits this: its groups hold length-sorted rows, so ``y_ref`` rows are in
the permuted space and the wrapper's fused epilogue
(:func:`repro.kernels.ops._adaptive_finish_spmv`) gathers them back to
original row order and adds the COO spill tail.  No kernel change needed —
the permutation lives entirely in plan metadata.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128

# Candidate coarsening factors: how many 8-slot chunks one grid step covers.
CHUNKS_PER_STEP_CHOICES = (1, 2, 4, 8)

__all__ = ["rgcsr_spmv_kernel", "rgcsr_spmv_pallas",
           "CHUNKS_PER_STEP_CHOICES", "SUBLANES", "LANES"]


def rgcsr_spmv_kernel(step_group_ref, step_first_ref,
                      values_ref, xg_ref, y_ref):
    """Kernel body.

    Blocks: values and gathered x ``(R, G)`` with ``R = 8·chunks_per_step``;
    y ``(1, G)`` float32 accumulator of the step's group.
    """
    s = pl.program_id(0)

    @pl.when(step_first_ref[s] == 1)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    prods = (values_ref[...].astype(jnp.float32)
             * xg_ref[...].astype(jnp.float32))          # (R, G)
    y_ref[...] += jnp.sum(prods, axis=0, keepdims=True)


@functools.partial(
    jax.jit,
    static_argnames=("n_groups", "group_size", "chunks_per_step",
                     "interpret"))
def rgcsr_spmv_pallas(step_group, step_first, values2d, columns2d, x,
                      *, n_groups: int, group_size: int,
                      chunks_per_step: int = 1, interpret: bool = True):
    """Launch the RgCSR SpMV kernel.

    Args:
      step_group:   (num_steps,) int32 — group id of each coarsened step.
      step_first:   (num_steps,) int32 — 1 iff first step of its group.
      values2d:     (S, G) slot-major values (S = total padded slots; every
                    group's slot count is a multiple of 8·chunks_per_step).
      columns2d:    (S, G) int32 column indices (ghost index 0 on padding).
      x:            (n_cols,) the dense vector.
      n_groups, group_size, chunks_per_step: static layout parameters.
      interpret:    run in interpret mode (CPU validation) or compile for TPU.

    Returns:
      (n_groups · G,) per-group result rows in the result dtype of
      ``values2d`` and ``x``; the caller unpads.
    """
    g = group_size
    rows_per_step = chunks_per_step * SUBLANES
    out_dtype = jnp.result_type(values2d.dtype, x.dtype)
    xg = jnp.take(x, columns2d, axis=0)                   # (S, G) XLA gather

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(step_group.shape[0],),
        in_specs=[
            pl.BlockSpec((rows_per_step, g), lambda s, sg, sf: (s, 0)),
            pl.BlockSpec((rows_per_step, g), lambda s, sg, sf: (s, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, g),
                               lambda s, sg, sf: (sg[s], 0, 0)),
    )
    y = pl.pallas_call(
        rgcsr_spmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, 1, g), jnp.float32),
        interpret=interpret,
        name="rgcsr_spmv",
    )(step_group, step_first, values2d, xg)
    return y.reshape(-1).astype(out_dtype)
