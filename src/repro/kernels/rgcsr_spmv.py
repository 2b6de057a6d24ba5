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

**The x stream is built in XLA, before the kernel, in one of two ways.**
The TPU compiler has no general in-kernel gather (Mosaic accepts only 2-D
gathers within a tile), so x reaches the kernel as an ``(S, G)`` stream laid
out like the value tile:

* *Diagonal slot rows* (block plans of matrices whose rows share column
  offsets, e.g. stencils — DESIGN.md §3.1) hold, in lane ``l`` of group
  ``g``, the entry at column ``g·G + l + d`` for one offset ``d`` per row,
  or an exact 0.  Their x is the contiguous slice
  ``x_pad[start : start + G]`` of a zero-padded x (``start = g·G + d +
  pad``).  XLA fetches the two aligned ``G``-wide rows of ``x_pad`` that the
  slice spans (a row gather, DMA-friendly), and
  :func:`rgcsr_diag_spmv_kernel` rotates them into place per slot row with
  ``pltpu.roll`` by the row's ``start % G``, scalar-prefetched per step
  from SMEM.  The plan (``ops._offset_slots``) picks, per group, offsets of
  its longest row ranked by how many lanes share them, as many as keep the
  group's padded slot count smallest (ties go to more diagonal rows), pads
  x by its largest overhang past 0 or n, and puts diagonal rows first, in
  steps ``[0, diag_steps)``.
* *Gathered slot rows* (everything else) take ``x[columns2d]`` from one
  scalar XLA gather, and :func:`rgcsr_spmv_kernel` multiplies the two
  ``(R, G)`` tiles and reduces over slots.

x itself is never staged into VMEM, so its width is not bounded by VMEM.
The price is extra ``(S, G)``-sized streams of HBM bytes (two for diagonal
rows, one for gathered rows), written by XLA and read by the kernel.  When a
plan has both kinds, each kernel writes the groups its steps visit, and the
two outputs are combined per group.

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
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128

# Candidate coarsening factors: how many 8-slot chunks one grid step covers.
CHUNKS_PER_STEP_CHOICES = (1, 2, 4, 8)

__all__ = ["rgcsr_spmv_kernel", "rgcsr_diag_spmv_kernel",
           "rgcsr_spmv_pallas", "merge_group_parts",
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


def rgcsr_diag_spmv_kernel(step_group_ref, step_first_ref, shift_ref,
                           values_ref, xa_ref, xb_ref, y_ref):
    """Kernel body for diagonal slot rows.

    Blocks: values and the two aligned x rows ``xa``/``xb`` ``(R, G)``;
    ``shift`` ``(1, R)`` int32 in SMEM, the lane at which each row's slice
    starts inside ``xa``; y ``(1, G)`` float32 accumulator.  Slot row ``i``
    multiplies its values with ``concat(xa[i], xb[i])[shift : shift + G]``.
    """
    s = pl.program_id(0)

    @pl.when(step_first_ref[s] == 1)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    rows, g = values_ref.shape
    lane = lax.broadcasted_iota(jnp.int32, (1, g), 1)
    row = lambda ref, i: ref[pl.ds(i, 1), :].astype(jnp.float32)  # noqa
    acc = jnp.zeros((1, g), jnp.float32)
    for i in range(rows):
        k = shift_ref[0, i]
        # lanes >= k come from xa, the rest from xb; rolling left by k
        # then puts lane k + l of the pair at lane l
        x = jnp.where(lane >= k, row(xa_ref, i), row(xb_ref, i))
        acc = acc + row(values_ref, i) * pltpu.roll(x, lax.rem(g - k, g), 1)
    y_ref[...] += acc


def merge_group_parts(parts, n_groups: int):
    """Sum per-group outputs of kernels that each visit only some groups.

    ``parts``: ``(y, step_group)`` pairs, ``y`` with leading axis
    ``n_groups``; a group no step of a part visits holds whatever the output
    buffer held, so it is masked out of that part.
    """
    if len(parts) == 1:
        return parts[0][0]
    total = None
    for y, groups in parts:
        seen = jnp.zeros((n_groups,), bool).at[groups].set(True)
        y = jnp.where(seen.reshape((n_groups,) + (1,) * (y.ndim - 1)), y,
                      jnp.zeros((), y.dtype))
        total = y if total is None else total + y
    return total


def _diag_x(x, diag_start, *, group_size: int, x_pad: int):
    """The two aligned ``G``-wide rows of the padded x that each diagonal
    row's slice spans."""
    g = group_size
    n = x.shape[0]
    n_rows = -(-(n + 2 * x_pad) // g) + 1      # room for row q + 1
    x2 = jnp.pad(x, (x_pad, n_rows * g - n - x_pad)).reshape(n_rows, g)
    q = diag_start // g
    xa = jnp.take(x2, q, axis=0, mode="clip")
    xb = jnp.take(x2, q + 1, axis=0, mode="clip")
    return xa, xb


@functools.partial(
    jax.jit,
    static_argnames=("n_groups", "group_size", "chunks_per_step",
                     "diag_steps", "x_pad", "interpret"))
def rgcsr_spmv_pallas(step_group, step_first, values2d, columns2d, x,
                      diag_start=None, diag_shift=None, *, n_groups: int,
                      group_size: int, chunks_per_step: int = 1,
                      diag_steps: int = 0, x_pad: int = 0,
                      interpret: bool = True):
    """Launch the RgCSR SpMV kernels.

    Args:
      step_group:   (num_steps,) int32 — group id of each coarsened step.
      step_first:   (num_steps,) int32 — 1 iff first step of its group.
      values2d:     (S, G) slot-major values (S = total padded slots; every
                    group's slot count is a multiple of 8·chunks_per_step).
      columns2d:    (S, G) int32 column indices (ghost index 0 on padding).
      x:            (n_cols,) the dense vector.
      diag_start:   (diag_steps · R,) int32 — where each diagonal slot row's
                    x slice starts in x padded by ``x_pad`` zeros on both
                    sides; None when the plan has no diagonal rows.
      diag_shift:   (diag_steps, 1, R) int32 — ``diag_start % G`` per step.
      n_groups, group_size, chunks_per_step: static layout parameters.
      diag_steps:   steps ``[0, diag_steps)`` hold diagonal rows, the rest
                    gathered rows.
      x_pad:        zeros on each side of x that keep every slice in range.
      interpret:    run in interpret mode (CPU validation) or compile for TPU.

    Returns:
      (n_groups · G,) per-group result rows in the result dtype of
      ``values2d`` and ``x``; the caller unpads.
    """
    g = group_size
    rows_per_step = chunks_per_step * SUBLANES
    out_dtype = jnp.result_type(values2d.dtype, x.dtype)
    n_steps = step_group.shape[0]

    def tile(first_step):
        return pl.BlockSpec((rows_per_step, g),
                            lambda s, sg, sf: (s + first_step, 0))

    out_spec = pl.BlockSpec((None, 1, g), lambda s, sg, sf: (sg[s], 0, 0))
    out_shape = jax.ShapeDtypeStruct((n_groups, 1, g), jnp.float32)
    parts = []
    if diag_steps:
        with jax.named_scope("rgcsr_diag_x"):
            xa, xb = _diag_x(x, diag_start, group_size=g, x_pad=x_pad)
        shift_spec = pl.BlockSpec((None, 1, rows_per_step),
                                  lambda s, sg, sf: (s, 0, 0),
                                  memory_space=pltpu.SMEM)
        sg = step_group[:diag_steps]
        y = pl.pallas_call(
            rgcsr_diag_spmv_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(diag_steps,),
                in_specs=[shift_spec, tile(0), tile(0), tile(0)],
                out_specs=out_spec),
            out_shape=out_shape,
            interpret=interpret,
            name="rgcsr_diag_spmv",
        )(sg, step_first[:diag_steps], diag_shift, values2d, xa, xb)
        parts.append((y, sg))
    if diag_steps < n_steps:
        cols = columns2d[diag_steps * rows_per_step:] if diag_steps \
            else columns2d
        xg = jnp.take(x, cols, axis=0)                # (S_g, G) XLA gather
        sg = step_group[diag_steps:]
        y = pl.pallas_call(
            rgcsr_spmv_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(n_steps - diag_steps,),
                in_specs=[tile(diag_steps), tile(0)],
                out_specs=out_spec),
            out_shape=out_shape,
            interpret=interpret,
            name="rgcsr_spmv",
        )(sg, step_first[diag_steps:], values2d, xg)
        parts.append((y, sg))
    y = merge_group_parts(parts, n_groups)
    return y.reshape(-1).astype(out_dtype)
