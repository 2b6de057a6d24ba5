"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracle.

All kernels run in interpret mode (CPU container); the sweep covers group
sizes, ragged shapes, rectangular matrices, empty rows, bf16/fp32.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import from_dense
from repro.kernels import (ell_spmv, make_ell_plan, make_plan, rgcsr_spmm,
                           rgcsr_spmv)
from repro.kernels.ref import spmv_ref, spmm_ref


def _rand(seed, n, m, density):
    rng = np.random.default_rng(seed)
    a = (rng.uniform(size=(n, m)) < density).astype(np.float32)
    a *= rng.uniform(0.5, 1.5, size=(n, m)).astype(np.float32)
    return a


@pytest.mark.parametrize("n,m,density,g", [
    (64, 64, 0.1, 128),        # fewer rows than one group
    (128, 128, 0.05, 128),     # exactly one group
    (300, 257, 0.08, 128),     # ragged rows+cols
    (513, 300, 0.02, 256),     # larger group
    (130, 1000, 0.01, 128),    # wide
    (40, 40, 0.5, 128),        # dense-ish
])
def test_rgcsr_spmv_shapes(n, m, density, g):
    a = _rand(0, n, m, density)
    mat = from_dense(a, "rgcsr", group_size=g)
    plan = make_plan(mat)
    x = np.random.default_rng(1).standard_normal(m).astype(np.float32)
    got = np.asarray(rgcsr_spmv(plan, jnp.asarray(x), interpret=True))
    ref = np.asarray(spmv_ref(mat, jnp.asarray(x)))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got, a @ x, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 3e-2)])
def test_rgcsr_spmv_dtypes(dtype, rtol):
    a = _rand(2, 200, 200, 0.05)
    mat = from_dense(a, "rgcsr", group_size=128)
    plan = make_plan(mat)
    plan = dataclasses.replace(plan, values2d=plan.values2d.astype(dtype))
    x = jnp.asarray(np.random.default_rng(3).standard_normal(200), dtype)
    got = np.asarray(rgcsr_spmv(plan, x, interpret=True)).astype(np.float32)
    ref = a @ np.asarray(x, np.float32)
    np.testing.assert_allclose(got, ref, rtol=rtol, atol=rtol * 10)


@pytest.mark.parametrize("d", [1, 7, 64, 129])
def test_rgcsr_spmm_widths(d):
    a = _rand(4, 150, 140, 0.07)
    mat = from_dense(a, "rgcsr", group_size=128)
    plan = make_plan(mat)
    x = np.random.default_rng(5).standard_normal((140, d)).astype(np.float32)
    got = np.asarray(rgcsr_spmm(plan, jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(got, a @ x, rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), n=st.integers(8, 200),
       m=st.integers(8, 200))
def test_rgcsr_spmv_property(seed, n, m):
    a = _rand(seed, n, m, 0.08)
    mat = from_dense(a, "rgcsr", group_size=128)
    plan = make_plan(mat)
    x = np.random.default_rng(seed).standard_normal(m).astype(np.float32)
    got = np.asarray(rgcsr_spmv(plan, jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(got, a @ x, rtol=1e-4, atol=1e-4)


def test_rgcsr_empty_rows_and_ghost_index():
    a = np.zeros((140, 90), np.float32)
    a[0, 3] = 2.0
    a[139, 89] = -1.0            # only two nonzeros; many empty rows
    mat = from_dense(a, "rgcsr", group_size=128)
    plan = make_plan(mat)
    x = np.random.default_rng(0).standard_normal(90).astype(np.float32)
    got = np.asarray(rgcsr_spmv(plan, jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(got, a @ x, rtol=1e-5, atol=1e-6)


def test_plan_rejects_non_tpu_group_size():
    a = _rand(6, 64, 64, 0.1)
    mat = from_dense(a, "rgcsr", group_size=32, slot_pad=4)
    with pytest.raises(ValueError):
        make_plan(mat)


@pytest.mark.parametrize("n,m", [(64, 64), (200, 130), (257, 511)])
def test_ell_spmv(n, m):
    a = _rand(7, n, m, 0.06)
    mat = from_dense(a, "ellpack")
    plan = make_ell_plan(mat)
    x = np.random.default_rng(8).standard_normal(m).astype(np.float32)
    got = np.asarray(ell_spmv(plan, jnp.asarray(x), interpret=True))
    np.testing.assert_allclose(got, a @ x, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# Diagonal (offset-aligned) slot rows in block plans
# --------------------------------------------------------------------------

def _stencil_rgcsr(grid, group_size=128):
    from repro.core.formats import RgCSR
    from repro.core.suite import stencil27_csr
    values, columns, row_ptr, shape = stencil27_csr(grid)
    return RgCSR.from_csr(values, columns, row_ptr, shape,
                          group_size=group_size)


def _band_mix(rows_per_step):
    """512×512 with a band of ``rows_per_step`` offsets in groups 0, 1 and 3
    and random far entries in groups 0 and 2: group 0 gets diagonal and
    gathered rows, 1 and 3 diagonal rows only, 2 gathered rows only; the
    band overhangs column 0 in group 0 and column n in group 3."""
    n, rng = 512, np.random.default_rng(11)
    a = np.zeros((n, n), np.float32)
    band = np.arange(-(rows_per_step // 2), rows_per_step - rows_per_step // 2)
    for r in [*range(0, 256), *range(384, 512)]:
        cols = r + band
        cols = cols[(cols >= 0) & (cols < n)]
        a[r, cols] = rng.uniform(0.5, 1.5, size=len(cols))
    for r in range(0, 128):
        cols = rng.choice(np.arange(256, 512), size=1 + r % 3, replace=False)
        a[r, cols] = rng.uniform(0.5, 1.5, size=len(cols))
    for r in range(256, 384):
        cols = rng.choice(n, size=4, replace=False)
        a[r, cols] = rng.uniform(0.5, 1.5, size=len(cols))
    return from_dense(a, "rgcsr", group_size=128), a


def _steps_by_part(plan):
    sg = np.asarray(plan.step_group)
    return set(sg[: plan.diag_steps]), set(sg[plan.diag_steps:])


@pytest.mark.parametrize("cps", [1, 4])
@pytest.mark.parametrize("case", ["hpcg12", "hpcg16", "hpcg16_g256",
                                  "band_mix"])
def test_diagonal_slot_rows_match_reference(case, cps, monkeypatch):
    from repro.kernels import ops
    from repro.kernels.ops import _make_block_plan
    if case == "band_mix":
        mat, dense = _band_mix(8 * cps)
    else:
        grid = int(case[4:6])
        mat = _stencil_rgcsr(grid, 256 if case.endswith("g256") else 128)
        dense = None
    plan = make_plan(mat, chunks_per_step=cps)
    # re-slotted in runs of groups (one run per group here, some with no
    # diagonal row): the same plan
    monkeypatch.setattr(ops, "_SLOT_ROWS_PER_PART", 1)
    split = make_plan(mat, chunks_per_step=cps)
    for field in ("values2d", "columns2d", "step_group", "step_first",
                  "diag_start", "diag_shift"):
        np.testing.assert_array_equal(np.asarray(getattr(split, field)),
                                      np.asarray(getattr(plan, field)))
    assert (split.diag_steps, split.x_pad) == (plan.diag_steps, plan.x_pad)
    csr_plan = _make_block_plan(mat, chunks_per_step=cps, offset_slots=False)
    assert plan.stored_slots <= csr_plan.stored_slots      # never grows
    assert plan.x_pad > 0         # slices overhang column 0 or column n
    diag, gathered = _steps_by_part(plan)
    if case == "band_mix":
        assert 0 < plan.diag_slot_fraction < 1
        assert {0, 1, 3} <= diag and {0, 2} <= gathered
        assert 1 not in gathered and 3 not in gathered and 2 not in diag
    else:                                 # every group all diagonal
        assert plan.diag_slot_fraction == 1.0
        assert plan.diag_steps == plan.num_steps and not gathered
        assert diag == set(range(plan.n_groups))
    # columns2d stays valid: in range, and the true column under a value
    cols = np.asarray(plan.columns2d)
    assert cols.min() >= 0 and cols.max() < mat.shape[1]
    x = np.random.default_rng(5).standard_normal(mat.shape[1]).astype(
        np.float32)
    got = np.asarray(rgcsr_spmv(plan, jnp.asarray(x), interpret=True))
    ref = np.asarray(spmv_ref(mat, jnp.asarray(x)))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    if dense is not None:
        np.testing.assert_allclose(got, dense @ x, rtol=1e-4, atol=1e-4)
        xs = np.random.default_rng(6).standard_normal(
            (mat.shape[1], 3)).astype(np.float32)
        got2 = np.asarray(rgcsr_spmm(plan, jnp.asarray(xs), interpret=True))
        np.testing.assert_allclose(got2, dense @ xs, rtol=1e-4, atol=1e-4)


def test_rgcsr_spmm_on_diagonal_plan():
    mat = _stencil_rgcsr(12)
    plan = make_plan(mat, chunks_per_step=4)
    assert plan.diag_slot_fraction == 1.0
    xs = np.random.default_rng(7).standard_normal(
        (mat.shape[1], 5)).astype(np.float32)
    got = np.asarray(rgcsr_spmm(plan, jnp.asarray(xs), interpret=True))
    np.testing.assert_allclose(got, np.asarray(spmm_ref(mat, jnp.asarray(xs))),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("cps", [1, 4])
@pytest.mark.parametrize("kind,n", [("random", 300), ("random", 2048),
                                    ("powerlaw", 512), ("powerlaw", 2048)])
def test_unshared_offsets_keep_csr_plan(kind, n, cps):
    """Rows that share no offsets (random and power-law rows without a unit
    diagonal) keep today's plan, array for array."""
    from repro.core import suite
    from repro.kernels.ops import _make_block_plan
    if kind == "random":
        a = _rand(n, n, n, 8.0 / n)
    else:
        a = suite.generate("powerlaw", n, seed=1)
        np.fill_diagonal(a, 0.0)
    mat = from_dense(a, "rgcsr", group_size=128)
    plan = make_plan(mat, chunks_per_step=cps)
    csr_plan = _make_block_plan(mat, chunks_per_step=cps, offset_slots=False)
    assert plan.diag_steps == 0 and plan.diag_start is None
    assert plan.diag_slot_fraction == 0.0
    for field in ("values2d", "columns2d", "step_group", "step_first"):
        want = np.asarray(getattr(csr_plan, field))
        got = np.asarray(getattr(plan, field))
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes(), field
