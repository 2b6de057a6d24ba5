"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU compiler installed with JAX compiles for a chip that is described
rather than attached, and refuses what the chip's compiler would refuse:
block shapes off the (8, 128) tiling, in-kernel gathers it cannot lower,
more VMEM or HBM than the chip has.  Interpret-mode tests cannot see any of
that.  Nothing runs here, so these tests say nothing about results or
times.

Every test of this file shares one topology, described in a module fixture
(never at import time: only one process may load the TPU library, and the
test workers all import this file).  The persistent compilation cache is
off around the compiles — an entry compiled for a described chip cannot be
read back without one.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.ell_spmv import ell_spmv_pallas
from repro.kernels.rgcsr_spmm import rgcsr_spmm_pallas
from repro.kernels.rgcsr_spmv import rgcsr_spmv_pallas
from repro.models import LanguageModel
from repro.serve import ServeConfig, device_loop, paging

V5E_HBM_BYTES = 16 * 2 ** 30

# 27-point stencil on a 64³ grid as RgCSR at G=128: 262,144 rows in 2048
# groups, 27 nonzeros in interior rows → 32 slots per group at slot_pad 8;
# its diagonal rows' slices overhang by at most 64² + 64 + 1
STENCIL = dict(n=262_144, n_groups=2048, slots=32, x_pad=4161)
# granite-3-2b FFN weight (8192 × 2048) at density 0.25: 64 groups; the
# longest of 128 rows with ~Binomial(2048, 0.25) nonzeros is ≈570 → 576
FFN = dict(d_out=8192, d_in=2048, d=128, n_groups=64, slots=576)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topology = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("dtype,cps", [(jnp.float32, 1), (jnp.float32, 4),
                                       (jnp.bfloat16, 8)])
def test_rgcsr_spmv_compiles_for_v5e(one_chip, dtype, cps):
    _compile_spmv(one_chip, dtype, cps, diag_steps=0)


@pytest.mark.parametrize("dtype,cps,part", [
    (jnp.float32, 4, "all"),       # the stencil's plan: every row diagonal
    (jnp.bfloat16, 1, "half")])    # both kernels, outputs merged per group
def test_rgcsr_diag_spmv_compiles_for_v5e(one_chip, dtype, cps, part):
    steps = STENCIL["n_groups"] * STENCIL["slots"] // (8 * cps)
    _compile_spmv(one_chip, dtype, cps,
                  diag_steps=steps if part == "all" else steps // 2)


def _compile_spmv(one_chip, dtype, cps, *, diag_steps):
    s = STENCIL["n_groups"] * STENCIL["slots"]
    steps = s // (8 * cps)
    compiled = rgcsr_spmv_pallas.lower(
        _sds(one_chip, (steps,), jnp.int32),
        _sds(one_chip, (steps,), jnp.int32),
        _sds(one_chip, (s, 128), dtype),
        _sds(one_chip, (s, 128), jnp.int32),
        _sds(one_chip, (STENCIL["n"],), dtype),
        *((_sds(one_chip, (diag_steps * 8 * cps,), jnp.int32),
           _sds(one_chip, (diag_steps, 1, 8 * cps), jnp.int32))
          if diag_steps else ()),
        n_groups=STENCIL["n_groups"], group_size=128, chunks_per_step=cps,
        diag_steps=diag_steps, x_pad=STENCIL["x_pad"] if diag_steps else 0,
        interpret=False).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("rgcsr_diag_spmv" in text) == bool(diag_steps)


@pytest.mark.parametrize("cps", [1, 4])
def test_rgcsr_spmm_compiles_for_v5e(one_chip, cps):
    s = FFN["n_groups"] * FFN["slots"]
    steps = s // (8 * cps)
    compiled = rgcsr_spmm_pallas.lower(
        _sds(one_chip, (steps,), jnp.int32),
        _sds(one_chip, (steps,), jnp.int32),
        _sds(one_chip, (s, 128), jnp.bfloat16),
        _sds(one_chip, (s, 128), jnp.int32),
        _sds(one_chip, (FFN["d_in"], FFN["d"]), jnp.bfloat16),
        n_groups=FFN["n_groups"], group_size=128, chunks_per_step=cps,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ell_spmv_compiles_for_v5e(one_chip):
    """The Hybrid format's ELL part of the same stencil (k1 = 27 → 32)."""
    compiled = ell_spmv_pallas.lower(
        _sds(one_chip, (32, STENCIL["n"]), jnp.float32),
        _sds(one_chip, (32, STENCIL["n"]), jnp.int32),
        _sds(one_chip, (STENCIL["n"],), jnp.float32),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_granite_init_writes_each_leaf_once(one_chip):
    """Published-width init is one program whose only buffers are the
    parameters: no initializer temporaries on top of 10 GB of weights."""
    model = LanguageModel(get_config("granite-3-2b"))
    key = _sds(one_chip, (2,), jnp.uint32)
    mem = jax.jit(model.init, out_shardings=one_chip).lower(
        key).compile().memory_analysis()
    # fp32 weights, up to the chip's layout padding of small leaves
    assert mem.output_size_in_bytes == pytest.approx(model.n_params() * 4,
                                                     rel=1e-6)
    assert mem.temp_size_in_bytes < 2 ** 20


def test_granite_fused_decode_fits_v5e_hbm(one_chip):
    """The fused decode loop at published widths: weights, paged caches and
    the program's temporaries fit one chip's HBM together."""
    cfg = get_config("granite-3-2b")
    model = LanguageModel(cfg)
    sc = ServeConfig(max_seq=128, n_slots=4)

    def place(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    geom = paging.geometry(sc.max_seq, sc.page_size, sc.n_slots, sc.n_pages)
    params = place(model.abstract_params())
    caches = place(jax.eval_shape(
        lambda: model.init_cache(sc.n_slots, sc.max_seq, paging=geom)))
    n = sc.n_slots
    mem = device_loop.build_fused_decode(model, sc).lower(
        params, caches, _sds(one_chip, (n, 1), jnp.int32),
        _sds(one_chip, (n,), jnp.int32), _sds(one_chip, (n,), jnp.bool_),
        _sds(one_chip, (2,), jnp.uint32), _sds(one_chip, (), jnp.int32),
    ).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert np.isfinite(total) and total < V5E_HBM_BYTES
