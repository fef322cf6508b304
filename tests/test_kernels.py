"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode).

Every Pallas kernel is swept over shapes, dtypes, sparsity patterns and
schedules and checked against ref.py. bf16 accumulation uses a loose
tolerance (long-reduction precision, see kernel taxonomy Part E)."""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import (
    DEFAULT_SCHEDULE,
    InfeasibleConfig,
    KernelSchedule,
    compile_spmv,
    prepare,
    spmm_pallas,
    spmv_pallas,
)
from repro.kernels.ref import spmm_dense, spmv_dense
from repro.sparse import FORMAT_NAMES
from repro.sparse.generate import random_matrix

FORMATS = list(FORMAT_NAMES)

SCHEDULES = [
    DEFAULT_SCHEDULE,
    KernelSchedule(rows_per_block=8, nnz_tile=128, unroll=1),
    KernelSchedule(rows_per_block=32, nnz_tile=256, unroll=2),
    KernelSchedule(rows_per_block=128, nnz_tile=512, unroll=4),
    KernelSchedule(rows_per_block=16, nnz_tile=128, unroll=1, accum_dtype="bfloat16"),
    KernelSchedule(rows_per_block=64, nnz_tile=128, dimension_semantics="parallel"),
]


def _check(dense, fmt, sched, x=None, tol=None):
    rng = np.random.default_rng(0)
    x = rng.normal(size=dense.shape[1]).astype(np.float32) if x is None else x
    ref = np.asarray(spmv_dense(dense, x))
    mat = prepare(dense, fmt, sched)
    y = np.asarray(spmv_pallas(mat, x, sched))
    assert y.shape == (dense.shape[0],)
    tol = tol or (3e-2 if sched.accum_dtype == "bfloat16" else 1e-4)
    scale = np.abs(ref).max() + 1e-9
    np.testing.assert_allclose(y / scale, ref / scale, atol=tol, rtol=tol)


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("sched_i", range(len(SCHEDULES)))
def test_schedule_sweep(fmt, sched_i):
    dense = random_matrix(250, 11.0, "fem", seed=42).astype(np.float32)
    _check(dense, fmt, SCHEDULES[sched_i])


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("pattern", ["fem", "powerlaw", "block", "banded", "denserows"])
def test_pattern_sweep(fmt, pattern):
    dense = random_matrix(200, 8.0, pattern, seed=9).astype(np.float32)
    _check(dense, fmt, DEFAULT_SCHEDULE)


@pytest.mark.parametrize("fmt", FORMATS)
@settings(max_examples=8)
@given(
    n=st.integers(8, 300),
    avg=st.floats(1.0, 24.0),
    seed=st.integers(0, 10_000),
)
def test_random_shapes(fmt, n, avg, seed):
    dense = random_matrix(n, avg, "fem", seed=seed).astype(np.float32)
    _check(dense, fmt, KernelSchedule(rows_per_block=8, nnz_tile=128))


@pytest.mark.parametrize("fmt", FORMATS)
def test_empty_rows(fmt):
    """Rows with zero nonzeros must produce exact zeros."""
    dense = np.zeros((64, 64), dtype=np.float32)
    dense[10, 3] = 2.0
    dense[50, 60] = -1.5
    x = np.ones(64, dtype=np.float32)
    mat = prepare(dense, fmt, DEFAULT_SCHEDULE)
    y = np.asarray(spmv_pallas(mat, x, DEFAULT_SCHEDULE))
    ref = dense @ x
    np.testing.assert_allclose(y, ref, atol=1e-6)


@pytest.mark.parametrize("fmt", FORMATS)
def test_input_dtypes(fmt):
    dense = random_matrix(100, 6.0, "fem", seed=5).astype(np.float32)
    rng = np.random.default_rng(2)
    for dt, tol in [(np.float32, 1e-4), (np.float64, 1e-4)]:
        x = rng.normal(size=dense.shape[1]).astype(dt)
        _check(dense.astype(dt), fmt, DEFAULT_SCHEDULE, x=x.astype(np.float32), tol=tol)


def test_spmm_matches_dense():
    dense = random_matrix(96, 7.0, "powerlaw", seed=11).astype(np.float32)
    X = np.random.default_rng(1).normal(size=(dense.shape[1], 16)).astype(np.float32)
    mat = prepare(dense, "ell", DEFAULT_SCHEDULE)
    Y = np.asarray(spmm_pallas(mat, X))
    np.testing.assert_allclose(Y, np.asarray(spmm_dense(dense, X)), rtol=1e-4, atol=1e-4)


def test_misaligned_schedule_rejected():
    dense = random_matrix(100, 6.0, "fem", seed=5).astype(np.float32)
    mat = prepare(dense, "ell", KernelSchedule(nnz_tile=128))
    with pytest.raises(InfeasibleConfig):
        spmv_pallas(mat, np.ones(dense.shape[1], np.float32), KernelSchedule(nnz_tile=512))


def test_sell_nnz_tile_mismatch_rejected():
    dense = random_matrix(100, 6.0, "fem", seed=5).astype(np.float32)
    mat = prepare(dense, "sell", KernelSchedule(nnz_tile=128))
    with pytest.raises(InfeasibleConfig):
        spmv_pallas(mat, np.ones(dense.shape[1], np.float32), KernelSchedule(nnz_tile=256))


def test_compile_spmv_end_to_end():
    dense = random_matrix(128, 9.0, "block", seed=8).astype(np.float32)
    x = np.random.default_rng(3).normal(size=dense.shape[1]).astype(np.float32)
    fn = compile_spmv(dense, "bell", KernelSchedule(rows_per_block=16))
    np.testing.assert_allclose(
        np.asarray(fn(x)), dense @ x, rtol=1e-4, atol=1e-4
    )


def test_schedule_validation():
    with pytest.raises(ValueError):
        KernelSchedule(rows_per_block=10)  # not a sublane multiple
    with pytest.raises(ValueError):
        KernelSchedule(nnz_tile=100)  # not a lane multiple
    with pytest.raises(ValueError):
        KernelSchedule(unroll=3)  # must divide nnz_tile
    with pytest.raises(ValueError):
        KernelSchedule(accum_dtype="float16")


@pytest.mark.parametrize("fmt", ["csr", "ell", "sell"])
def test_the_gather_of_x_is_named_in_the_spmv_program(fmt):
    """The XLA gather sits in a ``spmv.gather`` scope, so a trace names it.
    CSR gathers a float32 x inside its kernel; a bf16 x takes its XLA gather."""
    import jax.numpy as jnp

    from repro.kernels.common import GATHER_SCOPE
    from repro.kernels.ops import _jitted_spmv
    from repro.sparse.registry import spec_for

    dense = random_matrix(120, 6.0, "fem", seed=3).astype(np.float32)
    mat = prepare(dense, fmt, DEFAULT_SCHEDULE)
    x = jnp.ones(dense.shape[1], jnp.bfloat16 if fmt == "csr" else jnp.float32)
    text = _jitted_spmv.lower(spec_for(mat).spmv, mat, x, DEFAULT_SCHEDULE).as_text(
        debug_info=True
    )
    assert GATHER_SCOPE == "spmv.gather"
    assert f"{GATHER_SCOPE}/" in text


def _csr_both_gathers(dense, sched, x):
    """y of the CSR kernel with x gathered inside it and by XLA."""
    import jax

    from repro.kernels.csr import csr_spmv_pallas

    mat = prepare(dense, "csr", sched)
    n_rows = dense.shape[0]

    def run(gather):
        fn = jax.jit(
            lambda x: csr_spmv_pallas(
                mat.data, mat.indices, mat.row_ids, x, n_rows, mat.tiling, sched,
                gather=gather,
            )
        )
        return np.asarray(fn(x))

    return mat, run("vmem"), run("xla")


def _csr_gather_case(n_rows, n_cols, empty_block=False):
    dense = random_matrix(max(n_rows, n_cols), 9.0, "denserows", seed=n_cols)
    dense = dense[:n_rows, :n_cols].astype(np.float32)
    dense[:, -1] = 1.5  # the last column of x, past the last whole row of 128
    if empty_block:
        dense[8:16] = 0.0  # an 8-row block stored as one tile of padding
    return dense


@pytest.mark.parametrize(
    "rpb,nt,n_rows,n_cols,empty_block",
    [
        pytest.param(rpb, nt, 300, 300, False, id=f"rpb{rpb}-nt{nt}")
        for rpb in (8, 64, 512)
        for nt in (128, 1024)
    ]
    + [
        pytest.param(8, 128, 100, 100, False, id="x-in-one-row"),
        pytest.param(64, 1024, 260, 128, False, id="x-exactly-one-row"),
        pytest.param(8, 1024, 200, 300, True, id="tile-of-padding-only"),
    ],
)
def test_csr_gathers_x_in_the_kernel_bit_for_bit_as_xla_does(
    rpb, nt, n_rows, n_cols, empty_block
):
    from repro.kernels.common import GATHER_SCOPE
    from repro.kernels.ops import PreparedSpmv, _jitted_spmv
    from repro.sparse.registry import spec_for

    dense = _csr_gather_case(n_rows, n_cols, empty_block)
    sched = KernelSchedule(rows_per_block=rpb, nnz_tile=nt)
    x = np.random.default_rng(rpb + nt).normal(size=n_cols).astype(np.float32)
    mat, y_vmem, y_xla = _csr_both_gathers(dense, sched, x)
    if empty_block:
        assert (np.asarray(mat.data).reshape(-1, nt) == 0).all(axis=1).any()
    assert y_vmem.dtype == y_xla.dtype and np.array_equal(y_vmem, y_xla)
    np.testing.assert_allclose(y_vmem[:n_rows], dense @ x, rtol=1e-4, atol=1e-4)
    # the served path takes the in-kernel gather and keeps no XLA gather of x
    assert PreparedSpmv(mat, sched).gather == "vmem"
    text = _jitted_spmv.lower(spec_for(mat).spmv, mat, x, sched).as_text(debug_info=True)
    assert f"{GATHER_SCOPE}/" not in text
    np.testing.assert_array_equal(np.asarray(spmv_pallas(mat, x, sched)), y_vmem[:n_rows])


@pytest.mark.parametrize("case", ["bf16-x", "x-past-the-row-bound"])
def test_csr_keeps_the_xla_gather_where_the_kernel_cannot_gather(case):
    """A bf16 x, or an x of more rows of 128 than ``x_gather`` allows, is
    gathered by XLA before the launch, inside the ``spmv.gather`` scope."""
    import jax.numpy as jnp

    from repro.kernels.common import GATHER_SCOPE
    from repro.kernels.csr import VMEM_GATHER_MAX_ROWS, WALK_ROWS, x_gather
    from repro.kernels.ops import PreparedSpmv, _jitted_spmv
    from repro.sparse.registry import spec_for

    sched = KernelSchedule(rows_per_block=8, nnz_tile=128)
    rng = np.random.default_rng(7)
    if case == "bf16-x":
        n_cols, dtype = 300, jnp.bfloat16
    else:
        # a 128-wide tile fills one sublane in 8, so its bound is an eighth,
        # in whole steps of the walk; one column more takes another step
        fit = VMEM_GATHER_MAX_ROWS // 8 // WALK_ROWS * WALK_ROWS * 128
        n_cols, dtype = fit + 1, jnp.float32
        assert x_gather(fit, dtype, 128) == "vmem"
    dense = np.zeros((40, n_cols), np.float32)
    cols = rng.integers(0, n_cols, size=(40, 6))
    dense[np.arange(40)[:, None], cols] = rng.normal(size=cols.shape)
    dense[3, n_cols - 1] = 2.0
    mat = prepare(dense, "csr", sched)
    assert x_gather(n_cols, dtype, 128) == "xla"
    if case != "bf16-x":
        assert PreparedSpmv(mat, sched).gather == "xla"
    x = jnp.asarray(rng.normal(size=n_cols), dtype)
    text = _jitted_spmv.lower(spec_for(mat).spmv, mat, x, sched).as_text(debug_info=True)
    assert f"{GATHER_SCOPE}/" in text
    y = np.asarray(spmv_pallas(mat, x, sched), np.float32)
    ref = dense @ np.asarray(x, np.float32)
    np.testing.assert_allclose(y, ref, rtol=2e-2, atol=2e-2)
