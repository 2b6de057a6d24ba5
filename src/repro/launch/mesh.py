"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never initializes jax devices — required because the dry-run
must set ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before*
any jax device query (see launch/dryrun.py lines 1–2).

Meshes:
* single pod : (16, 16)            axes ("data", "model")   = 256 chips
* multi-pod  : (2, 16, 16)         axes ("pod", "data", "model") = 512 chips

The ``model`` axis maps onto the ICI torus dimension with the densest links
(TP traffic is per-layer); ``pod`` is the outermost axis — cross-pod (DCN)
traffic is only the gradient all-reduce / no serving traffic at all.
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["make_production_mesh", "make_mesh", "HW"]


# TPU v5e target constants (system-prompt values; used by roofline + tests)
class HW:
    PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
    HBM_BW = 819e9               # bytes/s per chip
    ICI_BW = 50e9                # bytes/s per link (~per axis direction)
    HBM_BYTES = 16 * 2 ** 30     # v5e HBM capacity
    VMEM_BYTES = 128 * 2 ** 20   # physical; Mosaic's default scoped limit
                                 # per kernel is 16 MiB (analyze.TPU_V5E)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Build a mesh over the first prod(shape) of ``devices`` (default: all
    available devices).

    Every axis is ``AxisType.Auto``: the installed JAX makes
    ``jax.make_mesh`` axes Explicit by default, and gathers on operands
    sharded over Explicit axes (the embedding lookup, slicing a sharded
    SpMV result) then refuse to infer their output sharding.
    """
    from jax.sharding import AxisType
    n = int(np.prod(shape))
    devices = jax.devices() if devices is None else list(devices)
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} exist; "
            f"on the CPU backend set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=<n> before "
            f"jax initializes its devices")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))
