"""ELLPACK SpMV Pallas TPU kernel (the paper's comparison format, Fig. 3).

ELLPACK is the degenerate RgCSR with a single group = the whole matrix, so
the kernel is the same slot-major FMA without any chunk table: grid
``(row_tiles, slot_tiles)`` with the slot dim innermost so each output tile
accumulates consecutively.  As in the RgCSR kernel, ``x[columns]`` is
gathered by XLA before the kernel (the TPU compiler has no general
in-kernel gather).  Used by the Hybrid format's ELL part; the COO spill
runs as a jnp segment-sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SUBLANES = 8
LANES = 128

__all__ = ["ell_spmv_kernel", "ell_spmv_pallas"]


def ell_spmv_kernel(values_ref, xg_ref, y_ref):
    """Blocks: values and gathered x (8, row_tile); y (1, row_tile) f32."""
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    prods = (values_ref[...].astype(jnp.float32)
             * xg_ref[...].astype(jnp.float32))
    y_ref[...] += jnp.sum(prods, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def ell_spmv_pallas(values2d, columns2d, x, *, row_tile: int = LANES,
                    interpret: bool = True):
    """values2d/columns2d: (K_pad, N_pad) slot-major; x: (n_cols,).
    Returns (N_pad,) in the result dtype of ``values2d`` and ``x``."""
    k_pad, n_rows_pad = values2d.shape
    out_dtype = jnp.result_type(values2d.dtype, x.dtype)
    xg = jnp.take(x, columns2d, axis=0)                   # XLA gather
    y = pl.pallas_call(
        ell_spmv_kernel,
        grid=(n_rows_pad // row_tile, k_pad // SUBLANES),
        in_specs=[
            pl.BlockSpec((SUBLANES, row_tile), lambda r, k: (k, r)),
            pl.BlockSpec((SUBLANES, row_tile), lambda r, k: (k, r)),
        ],
        out_specs=pl.BlockSpec((1, row_tile), lambda r, k: (0, r)),
        out_shape=jax.ShapeDtypeStruct((1, n_rows_pad), jnp.float32),
        interpret=interpret,
        name="ell_spmv",
    )(values2d, xg)
    return y[0].astype(out_dtype)
