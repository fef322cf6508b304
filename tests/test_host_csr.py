"""The host CSR input path: HPCG's stencil from ``repro.sparse.generate``,
its conversion, fingerprint and features, the plan's storage guard, and CG
on it through the session, at small sizes on the CPU."""

import numpy as np
import pytest

from repro.core.autotuner import AutoSpMV
from repro.core.features import extract_features, row_nnz_counts
from repro.core.session import AutoSpmvSession
from repro.kernels.common import (
    NNZ_TILE_CHOICES,
    ROWS_PER_BLOCK_CHOICES,
    InfeasibleConfig,
    KernelSchedule,
)
from repro.kernels.ops import (
    clear_kernel_memo,
    compile_spmspv,
    kernel_memo_stats,
    matrix_fingerprint,
    prepare,
    spmv_pallas,
)
from repro.obs.trace import get_tracer
from repro.solvers import IterativeSolver, cg, power_iteration
from repro.sparse.formats import csr_tiled, csr_tiled_from_host, tiled_stored
from repro.sparse.generate import hpcg_stencil, random_matrix
from repro.sparse.host import HostCSR
from repro.sparse.registry import MAX_STORAGE_BYTES, MatrixStats, csr_storable

PLAN = KernelSchedule(rows_per_block=8, nnz_tile=1024, unroll=8, accum_dtype="bfloat16")
USAGE = {"user_s", "sys_s", "minflt", "majflt", "nvcsw", "nivcsw"}


def _dense(mat: HostCSR) -> np.ndarray:
    """The container scattered dense: small test sizes only."""
    out = np.zeros(mat.shape, dtype=np.float64)
    np.add.at(out, (mat.row_ids(), mat.indices), mat.data)
    return out


def _host(dense: np.ndarray) -> HostCSR:
    rows, cols = np.nonzero(dense)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(rows, minlength=dense.shape[0]))])
    return HostCSR(indptr, cols.astype(np.int32), dense[rows, cols].astype(np.float32),
                   dense.shape)


class _Predictor:
    """Plans ``schedule``; rates wider tiles and taller blocks better."""

    def __init__(self, schedule=PLAN):
        self.schedule = schedule

    def predict_format(self, feats, objective):
        return "csr"

    def predict_schedule(self, feats, objective):
        return self.schedule

    def estimate_objective(self, feats, config, objective):
        s = config.schedule
        return 1.0 / s.nnz_tile + 1e-6 / s.rows_per_block


class _Overhead:
    def total_overhead(self, feats, fmt):
        return 1e6

    def predict_c(self, feats, fmt):
        return 1.0


def _session(schedule=PLAN) -> AutoSpmvSession:
    return AutoSpmvSession(AutoSpMV(_Predictor(schedule), _Overhead()))


# ------------------------------------------------------------------ stencil
def test_stencil_matches_a_dense_construction():
    nx, ny, nz = 6, 5, 4
    want = np.zeros((nx * ny * nz,) * 2)
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                row = ix + nx * (iy + ny * iz)
                for dz in (-1, 0, 1):
                    for dy in (-1, 0, 1):
                        for dx in (-1, 0, 1):
                            jx, jy, jz = ix + dx, iy + dy, iz + dz
                            if 0 <= jx < nx and 0 <= jy < ny and 0 <= jz < nz:
                                col = jx + nx * (jy + ny * jz)
                                want[row, col] = 26.0 if col == row else -1.0
    mat = hpcg_stencil(nx, ny, nz)
    assert mat.data.dtype == np.float32 and mat.indices.dtype == np.int32
    assert np.array_equal(_dense(mat), want)
    assert mat.nnz == np.count_nonzero(want) == (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)
    for r in range(mat.shape[0]):  # columns ascending in every row
        cols = mat.indices[mat.indptr[r]:mat.indptr[r + 1]]
        assert np.all(np.diff(cols) > 0)


@pytest.mark.parametrize("side", [2, 3, 7])
def test_stencil_on_a_cube_is_symmetric_with_27_point_rows(side):
    mat = hpcg_stencil(side, side, side)
    dense = _dense(mat)
    assert mat.shape == (side**3, side**3)
    assert mat.nnz == (3 * side - 2) ** 3
    assert np.array_equal(dense, dense.T)
    assert np.all(np.diag(dense) == 26.0)
    assert mat.row_counts().max() == (27 if side > 2 else 8)
    assert mat.row_counts().min() == 8  # a corner: itself and 7 neighbours
    # diagonally dominant with a strictly dominant corner: positive-definite
    assert np.linalg.eigvalsh(dense).min() > 0


# ------------------------------------------------------------------ the type
@pytest.mark.parametrize("args, match", [
    (([1, 2], [0], [1.0], (1, 1)), "from 0"),
    (([0, 2, 1], [0, 0], [1.0, 1.0], (2, 2)), "decrease"),
    (([0, 2], [0], [1.0], (1, 1)), "entries"),
    (([0, 1], [3], [1.0], (1, 2)), r"\[0, 2\)"),
])
def test_a_malformed_host_csr_is_refused(args, match):
    indptr, indices, data, shape = args
    with pytest.raises(ValueError, match=match):
        HostCSR(np.array(indptr), np.array(indices, np.int32), np.array(data, np.float32),
                shape)


def test_a_host_csr_is_never_made_dense():
    mat = hpcg_stencil(3, 3, 3)
    with pytest.raises(TypeError, match="never made dense"):
        np.asarray(mat)
    assert mat.astype(np.float32) is mat
    assert mat.astype(np.float64).data.dtype == np.float64


# -------------------------------------------------------------- conversion
@pytest.mark.parametrize("rpb, nt", [(8, 128), (8, 1024), (64, 256), (512, 128)])
def test_tiled_stream_from_host_equals_the_dense_one(rpb, nt):
    dense = random_matrix(300, 9.0, pattern="fem", seed=3)
    a, b = csr_tiled_from_host(_host(dense), rpb, nt), csr_tiled(dense, rpb, nt)
    assert a.tiling == b.tiling == (rpb, nt)
    for field in ("data", "indices", "indptr", "row_ids"):
        assert np.array_equal(np.asarray(getattr(a, field)), np.asarray(getattr(b, field)))
    assert tiled_stored(_host(dense).row_counts(), rpb, nt) == a.data.shape[0]


def _wide_banded(seed: int) -> np.ndarray:
    """40 rows over 30,000 columns, each row's entries in its own band of
    600 columns, with rows 16-23 empty: a stencil-like x of 235 rows of 128
    that no tile spans."""
    rng = np.random.default_rng(seed)
    dense = np.zeros((40, 30_000), np.float32)
    for r in (*range(16), *range(24, 40)):
        cols = r * 740 + rng.choice(600, size=30, replace=False)
        dense[r, cols] = rng.normal(size=30)
    return dense


def _tile_windows(dense: np.ndarray, rpb: int, nt: int):
    """Brute force: each tile's (first, last) row of 128 of x over its real
    entries, None for a tile of padding only, laid out block by block."""
    out = []
    for b in range(-(-dense.shape[0] // rpb)):
        rows, cols = np.nonzero(dense[b * rpb : (b + 1) * rpb])
        chunks = [cols[i : i + nt] >> 7 for i in range(0, cols.size, nt)] or [None]
        out += [None if c is None else (c.min(), c.max()) for c in chunks]
    return out


@pytest.mark.parametrize("source", ["dense", "host"])
@pytest.mark.parametrize("rpb, nt", [(8, 128), (8, 256), (16, 1024)])
def test_the_tiled_stream_carries_each_tiles_window_of_x(source, rpb, nt):
    """The window ``_tiled`` computes is the brute-force per-tile min over
    the real entries, aligned down to 8 and pulled back to end inside x;
    the padding columns widen no window and change no value, row or y."""
    from repro.kernels.csr import WALK_ROWS, x_rows
    from repro.sparse.formats import csr_to_dense

    dense = _wide_banded(rpb + nt)
    mat = csr_tiled(dense, rpb, nt) if source == "dense" else csr_tiled_from_host(
        _host(dense), rpb, nt)
    windows = _tile_windows(dense, rpb, nt)
    real = [w for w in windows if w is not None]
    widest = max(last - (first - first % 8) + 1 for first, last in real)
    rows = x_rows(dense.shape[1])
    assert mat.x_walk == -(-widest // WALK_ROWS) * WALK_ROWS < rows
    starts = np.asarray(mat.x_start)
    assert starts.shape == (len(windows),)
    for start, w in zip(starts, windows):
        if w is not None:
            assert start == min(w[0] - w[0] % 8, rows - mat.x_walk)
            assert start <= w[0] and w[1] < start + mat.x_walk
    # only padding columns moved: every real entry, row and y are as before
    stream, cols = np.asarray(mat.data), np.asarray(mat.indices)
    real_rows, real_cols = np.nonzero(dense)
    assert stream.size == tiled_stored((dense != 0).sum(axis=1), rpb, nt)
    assert np.array_equal(stream[stream != 0], dense[real_rows, real_cols])
    assert np.array_equal(cols[stream != 0], real_cols)
    assert np.array_equal(np.asarray(mat.row_ids)[stream != 0], real_rows)
    assert np.array_equal(csr_to_dense(mat), dense)
    sched = KernelSchedule(rows_per_block=rpb, nnz_tile=nt)
    x = np.random.default_rng(nt).normal(size=dense.shape[1]).astype(np.float32)
    y = np.asarray(spmv_pallas(mat, x, sched))
    np.testing.assert_allclose(y, dense.astype(np.float64) @ x, rtol=1e-6, atol=1e-6)


def test_a_stored_zero_of_the_host_csr_is_kept():
    mat = HostCSR(np.array([0, 2, 3]), np.array([0, 1, 1], np.int32),
                  np.array([1.0, 0.0, 2.0], np.float32), (2, 2))
    stream = csr_tiled_from_host(mat, 8, 128)
    assert np.array_equal(np.asarray(stream.indptr), [0, 2, 128])
    assert extract_features(mat).nnz == 3


def test_row_counts_of_a_host_csr_match_the_dense_ones():
    dense = random_matrix(400, 12.0, pattern="geometric", seed=5)
    assert np.array_equal(row_nnz_counts(_host(dense)), MatrixStats(dense).row_counts)


@pytest.mark.parametrize("fmt", ["ell", "bell", "sell"])
def test_formats_without_a_host_csr_prepare_refuse_it(fmt):
    with pytest.raises(InfeasibleConfig, match="converts only from a dense matrix"):
        prepare(hpcg_stencil(3, 3, 3), fmt, PLAN)


def test_the_spmspv_path_refuses_a_host_csr():
    with pytest.raises(TypeError, match="never made dense"):
        compile_spmspv(hpcg_stencil(3, 3, 3))


def test_storage_guard_counts_the_padded_stream():
    counts = np.full(350_000, 1)  # one entry a row
    refused = KernelSchedule(rows_per_block=8, nnz_tile=1024)
    assert tiled_stored(counts, 8, 1024) * 12 > MAX_STORAGE_BYTES
    assert csr_storable(counts, refused) is None
    # 8-row blocks of 8 entries each pad to a whole 1,024 tile; 512-row
    # blocks hold 512 entries, 4 tiles of 128, and the last 304 pad to 384
    assert tiled_stored(counts, 8, 1024) == 43_750 * 1024
    assert csr_storable(counts, refused.replace(rows_per_block=512, nnz_tile=128)) == (
        683 * 512 + 384)


# ------------------------------------------------------ fingerprint, features
def test_a_dense_fingerprint_is_unchanged():
    dense = np.arange(12, dtype=np.float32).reshape(3, 4)
    assert matrix_fingerprint(dense) == "580bf8e4d803ed474d4db957e0c2dd8f"


def test_a_host_fingerprint_follows_its_content_and_never_a_dense_one():
    mat = hpcg_stencil(4, 3, 2)
    assert matrix_fingerprint(mat) == matrix_fingerprint(hpcg_stencil(4, 3, 2))
    assert matrix_fingerprint(mat) != matrix_fingerprint(_dense(mat).astype(np.float32))
    changed = HostCSR(mat.indptr, mat.indices, mat.data * 2, mat.shape)
    assert matrix_fingerprint(changed) != matrix_fingerprint(mat)
    assert matrix_fingerprint(mat.astype(np.float64)) != matrix_fingerprint(mat)


def test_features_of_a_host_csr_equal_the_dense_ones():
    mat = hpcg_stencil(5, 4, 3)
    assert extract_features(mat) == extract_features(_dense(mat))


# ---------------------------------------------------------- the plan's guard
def _diagonal(n: int) -> HostCSR:
    """An n x n diagonal: its dense form is far past the storage guard."""
    return HostCSR(np.arange(n + 1), np.arange(n, dtype=np.int32),
                   np.ones(n, np.float32), (n, n))


def test_serve_optimize_plans_csr_from_a_host_csr_and_never_densifies():
    clear_kernel_memo()
    mat = _diagonal(30_000)
    assert mat.shape[0] * mat.shape[1] * 4 > MAX_STORAGE_BYTES
    session = _session()
    plan = session.serve_optimize(mat)
    assert plan.fmt == "csr"
    assert plan.schedule == PLAN  # storable: the plan is served as planned
    assert plan.kernel.mat.tiling == (8, 1024)
    assert plan.kernel.mat.shape == mat.shape
    assert session.stats.feature_extractions == 1
    assert plan.features.nnz == 30_000 and plan.features.n == 30_000


def test_a_refused_plan_is_replaced_by_the_best_rated_storable_tiling():
    clear_kernel_memo()
    mat = _diagonal(350_000)
    session = _session()
    plan = session.serve_optimize(mat)  # must not raise
    counts = mat.row_counts()
    assert csr_storable(counts, PLAN) is None
    pairs = [(rpb, nt) for rpb in ROWS_PER_BLOCK_CHOICES for nt in NNZ_TILE_CHOICES
             if csr_storable(counts, PLAN.replace(rows_per_block=rpb, nnz_tile=nt))]
    best = min(pairs, key=lambda p: 1.0 / p[1] + 1e-6 / p[0])
    assert (plan.schedule.rows_per_block, plan.schedule.nnz_tile) == best
    assert plan.schedule.replace(rows_per_block=8, nnz_tile=1024) == PLAN
    assert plan.kernel.mat.tiling == best
    assert plan.kernel.mat.data.shape[0] == tiled_stored(counts, *best)
    # the next request serves the same storable plan from the memos
    before = kernel_memo_stats()["compiles"]
    again = session.serve_optimize(mat)
    assert again.schedule == plan.schedule and again.kernel is plan.kernel
    assert kernel_memo_stats()["compiles"] == before


def test_a_dense_matrix_keeps_its_storable_plan():
    clear_kernel_memo()
    dense = random_matrix(200, 6.0, pattern="fem", seed=2)
    plan = _session().serve_optimize(dense)
    assert plan.schedule == PLAN and plan.kernel.mat.tiling == (8, 1024)


# ------------------------------------------------------------------ solvers
def _cg64(dense: np.ndarray, b: np.ndarray, iters: int) -> np.ndarray:
    """Textbook CG in float64 on the dense matrix, x0 = 0."""
    x, r = np.zeros_like(b), b.copy()
    p, rr = r.copy(), r @ r
    for _ in range(iters):
        Ap = dense @ p
        alpha = rr / (p @ Ap)
        x, r = x + alpha * p, r - alpha * Ap
        rr, rr_old = r @ r, rr
        p = r + rr / rr_old * p
    return x


def test_cg_on_a_host_csr_agrees_with_float64_cg_and_with_cg_on_the_dense_matrix():
    clear_kernel_memo()
    mat = hpcg_stencil(6, 5, 4)
    dense = _dense(mat)
    b = dense @ (1.0 + 0.1 * np.random.default_rng(11).standard_normal(mat.shape[0]))
    iters = 12  # the residual stays far above float32 round-off
    got = cg(_session(), mat, b, tol=0.0, max_iters=iters)
    want = _cg64(dense, b, iters)
    assert got.iterations == iters and got.fmt == "csr"
    # float32 products and sums in the SpMV, float64 recurrences: a relative
    # gap of a few float32 ulps, amplified little in 12 iterations
    assert np.abs(got.value - want).max() / np.abs(want).max() < 1e-5
    held = cg(_session(), dense.astype(np.float32), b, tol=0.0, max_iters=iters)
    # the same entries in the same order make the same tiled stream and the
    # same kernel: the two inputs give the same answer, to float32 ulps
    np.testing.assert_allclose(got.value, held.value, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got.residuals, held.residuals, rtol=1e-5)


def test_iterative_solver_counts_entries_from_indptr():
    mat = HostCSR(np.array([0, 2, 3]), np.array([0, 1, 1], np.int32),
                  np.array([1.0, 0.0, 2.0], np.float32), (2, 2))
    solver = IterativeSolver(_session(), mat)
    solver.setup()
    assert solver.nnz == 3  # the stored zero counts, as indptr[-1] says


def test_the_host_path_spans_carry_their_attributes():
    clear_kernel_memo()
    tracer = get_tracer()
    tracer.clear()
    mat = hpcg_stencil(4, 4, 3)
    b = np.random.default_rng(3).standard_normal(mat.shape[0])
    cg(_session(), mat, b, tol=0.0, max_iters=3)
    spans = tracer.spans()
    by_id = {s["id"]: s for s in spans}
    (fp,) = [s for s in spans if s["name"] == "session.fingerprint"]
    assert fp["attrs"]["kind"] == "csr" and fp["attrs"]["bytes"] == mat.nbytes
    converts = [s for s in spans if s["name"] == "sparse.convert"]
    assert converts and all(by_id[s["parent"]]["name"] == "kernel.compile" for s in converts)
    for s in converts:
        assert s["attrs"]["nnz"] == mat.nnz
        assert s["attrs"]["stored"] == tiled_stored(mat.row_counts(), 8, 1024)
    vector = [s for s in spans if s["name"] == "solver.vector"]
    assert len(vector) == 3
    for s in vector:
        assert by_id[s["parent"]]["name"] == "solver.iterate"
        assert USAGE <= set(s["attrs"])
    tracer.clear()
    power_iteration(_session(), _dense(mat).astype(np.float32), tol=0.0, max_iters=2)
    (fp,) = [s for s in tracer.spans() if s["name"] == "session.fingerprint"]
    assert fp["attrs"]["kind"] == "dense"
    assert not [s for s in tracer.spans() if s["name"] in ("solver.vector", "sparse.convert")]
