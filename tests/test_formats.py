"""Property + unit tests for the sparse formats (the paper's core)."""
import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import FORMATS, from_dense, spmm, spmv
from repro.core.analyze import GTX280, peak_model_gflops, row_stats
from repro.core.formats import RgCSR, _hybrid_split_k
from repro.core.ordering import ORDERINGS, descending_ordering, permute_rows
from repro.core.suite import generate, paper_twins, small_corpus

FMT_KWARGS = {
    "rgcsr": dict(group_size=32, slot_pad=4),
    "sliced_ellpack": dict(group_size=32, slot_pad=4),
}


def _rand_sparse(seed, n, m, density):
    rng = np.random.default_rng(seed)
    a = (rng.uniform(size=(n, m)) < density).astype(np.float32)
    a *= rng.uniform(0.5, 1.5, size=(n, m)).astype(np.float32)
    return a


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2 ** 16), n=st.integers(1, 96),
       m=st.integers(1, 96), density=st.floats(0.0, 0.3),
       fmt=st.sampled_from(sorted(FORMATS)))
def test_roundtrip_and_spmv(seed, n, m, density, fmt):
    a = _rand_sparse(seed, n, m, density)
    mat = from_dense(a, fmt, **FMT_KWARGS.get(fmt, {}))
    np.testing.assert_allclose(mat.to_dense(), a, atol=1e-6)
    x = np.random.default_rng(seed + 1).standard_normal(m).astype(np.float32)
    y = np.asarray(spmv(mat, jnp.asarray(x)))
    np.testing.assert_allclose(y, a @ x, rtol=2e-4, atol=2e-4)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16), fmt=st.sampled_from(sorted(FORMATS)))
def test_spmm(seed, fmt):
    a = _rand_sparse(seed, 48, 40, 0.1)
    x = np.random.default_rng(seed).standard_normal((40, 7)).astype(np.float32)
    mat = from_dense(a, fmt, **FMT_KWARGS.get(fmt, {}))
    np.testing.assert_allclose(np.asarray(spmm(mat, jnp.asarray(x))), a @ x,
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16), g=st.sampled_from([4, 8, 32]))
def test_rgcsr_fill_nonnegative_and_counts(seed, g):
    a = _rand_sparse(seed, 50, 50, 0.08)
    mat = from_dense(a, "rgcsr", group_size=g, slot_pad=4)
    assert mat.nnz == int((a != 0).sum())
    assert mat.stored_elements >= mat.nnz
    assert mat.fill_ratio() >= 0.0
    # group pointers are monotone and multiples of group size
    gp = np.asarray(mat.group_pointers)
    assert (np.diff(gp) >= 0).all()
    assert (np.diff(gp) % g == 0).all()


def test_rgcsr_storage_vs_sliced_ellpack():
    """RgCSR = sliced ELLPACK + rowLengths (the paper's exact delta)."""
    a = _rand_sparse(3, 64, 64, 0.1)
    rg = from_dense(a, "rgcsr", group_size=32, slot_pad=4)
    se = from_dense(a, "sliced_ellpack", group_size=32, slot_pad=4)
    assert rg.storage_bytes() - se.storage_bytes() == 4 * a.shape[0]


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_descending_ordering_minimizes_fill(seed, ):
    """Paper §4.4.2: descending row-length ordering is optimal for fill."""
    a = _rand_sparse(seed, 60, 60, 0.07)
    base = from_dense(a, "rgcsr", group_size=16, slot_pad=1)
    desc = from_dense(permute_rows(a, descending_ordering(a)), "rgcsr",
                      group_size=16, slot_pad=1)
    assert desc.stored_elements <= base.stored_elements


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2 ** 16),
       oname=st.sampled_from(sorted(ORDERINGS)))
def test_ordering_preserves_spmv_up_to_permutation(seed, oname):
    a = _rand_sparse(seed, 40, 40, 0.1)
    perm = ORDERINGS[oname](a)
    x = np.random.default_rng(seed).standard_normal(40).astype(np.float32)
    y_base = np.asarray(spmv(from_dense(a, "rgcsr", group_size=8,
                                        slot_pad=1), jnp.asarray(x)))
    y_perm = np.asarray(spmv(from_dense(permute_rows(a, perm), "rgcsr",
                                        group_size=8, slot_pad=1),
                             jnp.asarray(x)))
    np.testing.assert_allclose(y_perm, y_base[perm], rtol=2e-4, atol=2e-4)


def test_hybrid_split_heuristic():
    # uniform rows → K1 ≈ row length; one dense row → spills to COO
    lens = np.full(5000, 6)
    lens[0] = 4000
    k1 = _hybrid_split_k(lens)
    assert 1 <= k1 <= 10


def test_peak_model_matches_paper_table1():
    assert abs(peak_model_gflops(GTX280, 4, False) - 23.5) < 0.5
    assert abs(peak_model_gflops(GTX280, 8, False) - 14.1) < 0.1
    assert abs(peak_model_gflops(GTX280, 4, True) - 35.25) < 0.1
    assert abs(peak_model_gflops(GTX280, 8, True) - 23.5) < 0.1


def test_paper_twins_signatures():
    twins = paper_twins(scale=64)
    st4 = row_stats(twins["trans4_twin"])
    st_fd = row_stats(twins["fd18_twin"])
    # the pathology: max row ≫ mean (trans4) vs max ≈ mean (fd18)
    assert st4["row_nnz_max"] > 50 * st4["row_nnz_mean"]
    assert st_fd["row_nnz_max"] < 3 * st_fd["row_nnz_mean"]


@pytest.mark.parametrize("family", ["stencil", "fem2d", "powerlaw",
                                    "uniform", "circuit", "blockrand",
                                    "banded"])
def test_suite_families_deterministic(family):
    a = generate(family, 64, seed=5)
    b = generate(family, 64, seed=5)
    np.testing.assert_array_equal(a, b)
    assert (a != 0).sum() > 0


# ------------------------------------------------ CSR construction path


def _csr_of(dense):
    rows, cols = np.nonzero(dense)
    row_ptr = np.concatenate([[0], np.cumsum(
        np.bincount(rows, minlength=dense.shape[0]))])
    return dense[rows, cols], cols.astype(np.int32), row_ptr


def _per_row_rgcsr(dense, g, slot_pad):
    """Independent per-row reference for the grouped layout: row r's
    nonzeros fill slots 0..len-1 of lane r % g in its group's tile."""
    n = dense.shape[0]
    n_groups = max(1, -(-n // g))
    lens = (dense != 0).sum(axis=1)
    slots = []
    for gi in range(n_groups):
        k = int(lens[gi * g:(gi + 1) * g].max()) if gi * g < n else 0
        slots.append(-(-max(k, 1) // slot_pad) * slot_pad)
    values = [np.zeros((k, g), dense.dtype) for k in slots]
    columns = [np.zeros((k, g), np.int32) for k in slots]
    for r in range(n):
        cols_r = np.nonzero(dense[r])[0]
        values[r // g][: len(cols_r), r % g] = dense[r, cols_r]
        columns[r // g][: len(cols_r), r % g] = cols_r
    return (np.concatenate([v.reshape(-1) for v in values]),
            np.concatenate([c.reshape(-1) for c in columns]),
            np.asarray(slots, np.int32), lens.astype(np.int32))


@pytest.mark.parametrize("spec", small_corpus(), ids=lambda s: s.name)
def test_rgcsr_from_csr_equals_from_dense(spec):
    dense = spec.build()
    a = RgCSR.from_dense(dense)
    b = RgCSR.from_csr(*_csr_of(dense), dense.shape)
    for name in RgCSR._array_fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)), name)
    values, columns, slots, lens = _per_row_rgcsr(dense, 128, 8)
    np.testing.assert_array_equal(np.asarray(b.values), values)
    np.testing.assert_array_equal(np.asarray(b.columns), columns)
    np.testing.assert_array_equal(np.asarray(b.slots_per_group), slots)
    np.testing.assert_array_equal(np.asarray(b.row_lengths), lens)
    np.testing.assert_array_equal(b.to_dense(), dense)


@pytest.mark.parametrize("g,slot_pad", [(32, 4), (4, 1), (256, 8)])
def test_rgcsr_from_csr_group_sizes(g, slot_pad):
    dense = _rand_sparse(71, 150, 90, 0.1)
    dense[7] = 0.0                                  # an empty row
    b = RgCSR.from_csr(*_csr_of(dense), dense.shape, group_size=g,
                       slot_pad=slot_pad)
    values, columns, slots, _ = _per_row_rgcsr(dense, g, slot_pad)
    np.testing.assert_array_equal(np.asarray(b.values), values)
    np.testing.assert_array_equal(np.asarray(b.columns), columns)
    np.testing.assert_array_equal(np.asarray(b.slots_per_group), slots)
    x = np.random.default_rng(72).standard_normal(90).astype(np.float32)
    np.testing.assert_allclose(np.asarray(spmv(b, jnp.asarray(x))),
                               dense @ x, rtol=1e-4, atol=1e-4)


def test_hybrid_and_sharded_from_csr_match_dense():
    from repro.core.formats import HybridEllCoo, ShardedRgCSR
    dense = generate("circuit", 300, seed=3)
    csr = _csr_of(dense)
    h = HybridEllCoo.from_csr(*csr, dense.shape)
    k1 = h.k1
    assert k1 == _hybrid_split_k((dense != 0).sum(axis=1))
    # per-row reference: the first k1 nonzeros of row i fill ELL column i,
    # the rest go to the COO tail in row-major order
    ell = np.zeros((k1, dense.shape[0]), np.float32)
    coo = []
    for i in range(dense.shape[0]):
        cols_i = np.nonzero(dense[i])[0]
        ell[: len(cols_i[:k1]), i] = dense[i, cols_i[:k1]]
        coo += [(i, c, dense[i, c]) for c in cols_i[k1:]]
    assert len(coo) > 0                             # dense rows spill
    np.testing.assert_array_equal(np.asarray(h.ell_values), ell)
    np.testing.assert_array_equal(
        np.stack([np.asarray(h.coo_rows), np.asarray(h.coo_columns)], 1),
        np.array([(r, c) for r, c, _ in coo]))
    np.testing.assert_array_equal(np.asarray(h.coo_values),
                                  np.array([v for _, _, v in coo]))
    np.testing.assert_array_equal(h.to_dense(), dense)
    sm = ShardedRgCSR.from_csr(*csr, dense.shape, n_shards=8)
    assert sm.rows_per_shard == 38
    np.testing.assert_array_equal(sm.to_dense(), dense)


def test_stencil27_csr_structure():
    from repro.core.suite import stencil27_csr
    values, columns, row_ptr, shape = stencil27_csr((3, 4, 5), seed=1)
    assert shape == (60, 60)
    lens = np.diff(row_ptr)
    assert lens.max() == 27 and lens.min() == 8     # interior and corners
    assert len(values) == (3 * 3 - 2) * (3 * 4 - 2) * (3 * 5 - 2)
    for r in range(shape[0]):                       # ascending, in-grid
        cols = columns[row_ptr[r]: row_ptr[r + 1]]
        assert (np.diff(cols) > 0).all() and r in cols
    a = RgCSR.from_csr(values, columns, row_ptr, shape)
    dense = a.to_dense()
    np.testing.assert_array_equal(dense != 0, dense.T != 0)   # symmetric
