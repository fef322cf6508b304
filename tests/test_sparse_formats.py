"""Property + unit tests for the sparse substrate (formats, converters,
reference SpMV, suite generator, sparsity features)."""

import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core.features import (
    FEATURE_NAMES,
    extract_features,
    features_from_assignment_histogram,
    features_from_csr_indptr,
)
from repro.sparse import FORMAT_NAMES, from_dense, spmv, to_dense
from repro.sparse.formats import CSR, SELL
from repro.sparse.generate import (
    MATRIX_NAMES,
    PATTERN_NAMES,
    SUITE,
    generate_by_name,
    random_matrix,
)


def _rand_dense(n_rows, n_cols, density, seed):
    rng = np.random.default_rng(seed)
    d = rng.normal(size=(n_rows, n_cols)).astype(np.float32)
    mask = rng.random((n_rows, n_cols)) < density
    return np.where(mask, d, 0.0).astype(np.float32)


dense_strategy = st.builds(
    _rand_dense,
    n_rows=st.integers(1, 120),
    n_cols=st.integers(1, 120),
    density=st.floats(0.0, 0.4),
    seed=st.integers(0, 2**31 - 1),
)


@pytest.mark.parametrize("fmt", FORMAT_NAMES)
@given(dense=dense_strategy)
def test_roundtrip(fmt, dense):
    mat = from_dense(dense, fmt)
    np.testing.assert_allclose(to_dense(mat), dense, rtol=0, atol=0)


@pytest.mark.parametrize("fmt", FORMAT_NAMES)
@given(dense=dense_strategy, seed=st.integers(0, 2**31 - 1))
def test_spmv_matches_dense(fmt, dense, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=dense.shape[1]).astype(np.float32)
    ref = dense @ x
    y = np.asarray(spmv(from_dense(dense, fmt), x))
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


@given(dense=dense_strategy)
def test_csr_structure(dense):
    mat = from_dense(dense, "csr")
    assert isinstance(mat, CSR)
    indptr = np.asarray(mat.indptr)
    assert indptr[0] == 0 and indptr[-1] == mat.nnz
    assert (np.diff(indptr) >= 0).all()
    # row_ids companion consistent with indptr
    counts = np.diff(indptr)
    np.testing.assert_array_equal(
        np.asarray(mat.row_ids), np.repeat(np.arange(dense.shape[0]), counts)
    )


@given(dense=dense_strategy)
def test_sell_storage_invariants(dense):
    mat = from_dense(dense, "sell")
    assert isinstance(mat, SELL)
    sp = np.asarray(mat.slice_ptr)
    sw = np.asarray(mat.slice_width)
    assert (np.diff(sp) == sw * mat.C).all()
    assert (sw % 128 == 0).all()  # lane-quantum padding
    assert mat.data.shape == (sp[-1] // mat.C, mat.C)  # stacked slice planes


@given(dense=dense_strategy)
def test_feature_invariants(dense):
    f = extract_features(dense)
    counts = (dense != 0).sum(axis=1)
    assert f.n == dense.shape[0]
    assert f.nnz == counts.sum()
    assert abs(f.avg_nnz * f.n - f.nnz) < 1e-6 * max(f.nnz, 1)
    assert 0.0 <= f.ell_ratio <= 1.0 + 1e-9
    assert abs(f.std_nnz**2 - f.var_nnz) < 1e-6 * max(f.var_nnz, 1.0)
    assert f.median <= counts.max(initial=0)
    vec = f.vector()
    assert vec.shape == (len(FEATURE_NAMES),)
    assert np.isfinite(vec).all()
    assert np.isfinite(f.log_vector()).all()


def test_features_from_indptr_matches_dense():
    dense = _rand_dense(64, 80, 0.1, 3)
    mat = from_dense(dense, "csr")
    f1 = extract_features(dense)
    f2 = features_from_csr_indptr(np.asarray(mat.indptr))
    np.testing.assert_allclose(f1.vector(), f2.vector())


def test_assignment_histogram_features():
    f = features_from_assignment_histogram(np.array([5, 0, 3, 8]))
    assert f.n == 4 and f.nnz == 16 and f.avg_nnz == 4.0


def test_suite_has_30_named_matrices():
    assert len(MATRIX_NAMES) == 30
    # paper §6.1 ranges
    ns = [SUITE[m].n for m in MATRIX_NAMES]
    nnzs = [SUITE[m].nnz for m in MATRIX_NAMES]
    assert min(ns) == 14_340 and max(ns) == 1_489_752
    assert min(nnzs) == 800_800 and max(nnzs) == 19_235_140


@pytest.mark.parametrize("name", MATRIX_NAMES[::6])
def test_suite_generation_scaled(name):
    d = generate_by_name(name, scale=0.003)
    assert d.shape[0] >= 64
    f = extract_features(d)
    assert f.nnz > 0


@pytest.mark.parametrize("pattern", PATTERN_NAMES)
def test_patterns_generate(pattern):
    d = random_matrix(128, 6.0, pattern, seed=1)
    assert (d != 0).sum() > 0


def test_pattern_diversity():
    """The generator must reproduce Fig. 7's spread: ELL ratio and std_nnz
    must differ strongly across pattern families."""
    f_fem = extract_features(random_matrix(512, 16.0, "fem", seed=2))
    f_pow = extract_features(random_matrix(512, 16.0, "powerlaw", seed=2))
    assert f_fem.ell_ratio > 3 * f_pow.ell_ratio
    assert f_pow.std_nnz > 3 * f_fem.std_nnz
