"""A cell that is not a power iteration on a Table-7 matrix, run by the
harness as it stands: the driver contract is open.

This file is itself the toy cell's driver module: its configuration is a
7-point stencil on a small 3-D grid, built sparse (never as a dense n x n
array); its program answers one SpMV a request with a jitted segment sum;
and its ``check`` returns numbers of its own, ``y_rel_err`` and
``answered_short``. The test registers the module under
``chipbench.drivers`` for the time of a test, so that the cell finds it by
the name in its traffic, as a real cell finds its driver's file.
"""

from __future__ import annotations

import json
import sys
import time
from dataclasses import dataclass

import ml_dtypes
import numpy as np
import pytest
import scipy.sparse

from chipbench import drivers, readings, run

SEED = 2**33 + 5
DRIVER = "toy_stencil"

# ------------------------------------------------------------ the toy driver
CHECKS = ("y_rel_err", "answered_short")


def inputs(config: dict, seed: int, scale: float = 1.0) -> scipy.sparse.csr_matrix:
    """The 7-point stencil of ``config["grid"]``, values drawn from ``seed``."""
    nx, ny, nz = (max(2, round(g * scale)) for g in config["grid"])
    eye = scipy.sparse.identity

    def path(n):
        return scipy.sparse.diags([np.ones(n - 1), np.ones(n - 1)], [-1, 1])

    kron = scipy.sparse.kron
    mat = (kron(kron(path(nx), eye(ny)), eye(nz)) + kron(kron(eye(nx), path(ny)), eye(nz))
           + kron(kron(eye(nx), eye(ny)), path(nz)) + eye(nx * ny * nz)).tocsr()
    mat.eliminate_zeros()  # kron's blocks store zeros
    mat.sort_indices()
    values = np.random.default_rng([seed % 2**64, 1])
    mat.data = values.uniform(0.1, 1.0, size=mat.nnz).astype(np.float32)
    return mat


def x_of(n: int, seed: int, i: int) -> np.ndarray:
    return np.random.default_rng([seed % 2**64, 2, i]).standard_normal(n).astype(np.float32)


@dataclass
class Answer:
    x: np.ndarray
    y: np.ndarray
    spmvs: int = 1


def build_tuner(traffic: dict):
    return None


class Program:
    def __init__(self, config: dict, traffic: dict, seed: int, tuner, scale: float = 1.0):
        import jax
        import jax.numpy as jnp

        self.mat, self.seed = inputs(config, seed, scale), seed
        self.n_rows, self.n_cols = self.mat.shape
        self.nnz = self.mat.nnz
        t0 = time.perf_counter()
        coo = self.mat.tocoo()
        self.device = tuple(jnp.asarray(a) for a in (coo.data, coo.row, coo.col))
        self._spmv = jax.jit(lambda v, r, c, x: jax.ops.segment_sum(
            v * x[c], r, num_segments=self.n_rows))
        self.metrics = {"tune_s": time.perf_counter() - t0}
        self.about = f"toy stencil: n={self.n_rows} nnz={self.nnz}"
        self._spmv(*self.device, np.zeros(self.n_cols, np.float32)).block_until_ready()

    def request(self, i: int) -> Answer:
        x = x_of(self.n_cols, self.seed, i)
        return Answer(x, np.asarray(self._spmv(*self.device, x)))

    def release(self) -> scipy.sparse.csr_matrix:
        self.device = None
        return self.mat


def check(mat, answers: list[Answer], traffic: dict) -> list[dict]:
    ref = mat.astype(np.float64)
    out = []
    for a in answers:
        want = ref @ a.x.astype(np.float64)
        got = np.asarray(a.y, np.float64)
        short = float(want.size - got.size)
        err = float(np.abs(got - want).max() / np.abs(want).max()) if not short else np.inf
        out.append({"y_rel_err": err, "answered_short": short})
    return out


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def control(mat, config: dict, traffic: dict, seed: int, count: int) -> list[Answer]:
    """The reference with values and x in bfloat16, the step below float32."""
    low = mat.copy()
    low.data = _bf16(low.data)
    return [Answer(x, low @ _bf16(x)) for x in (x_of(mat.shape[1], seed, i) for i in range(count))]


# ------------------------------------------------------------------ the cell
@pytest.fixture
def cell(monkeypatch):
    monkeypatch.setitem(sys.modules, f"chipbench.drivers.{DRIVER}", sys.modules[__name__])
    spec = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    end_to_end = [m for m in spec["end_to_end"] if "workloads" not in m]
    return run.Cell(
        name="toy7pt.spmv", chips=1,
        config={"name": "toy7pt", "grid": [16, 12, 10], "dtype": "float32"},
        traffic={"driver": DRIVER},
        limits={"y_rel_err": 1e-5, "answered_short": 0.0},
        end_to_end=end_to_end, per_layer=[], cpu_scale=0.5)


def test_the_toy_cell_keeps_the_driver_contract(cell):
    driver = sys.modules[__name__]
    assert drivers.contract_faults(driver, cell.limits) == []
    assert cell.driver is driver
    assert set(cell.limits) == set(driver.CHECKS)
    assert 0 < cell.cpu_scale <= 1


def test_the_toy_configuration_is_sparse_and_seeded():
    config = {"grid": [4, 3, 2]}
    a, b = inputs(config, SEED), inputs(config, SEED + 1)
    assert scipy.sparse.issparse(a) and a.shape == (24, 24)
    # 7-point pattern: each of the 3 axes couples (n_axis - 1) pairs, both ways
    assert a.nnz == 24 + 2 * (3 * 2 * 3 + 4 * 2 * 2 + 4 * 3 * 1)
    assert (a != 0).toarray().tolist() == (b != 0).toarray().tolist()
    assert not np.array_equal(a.data, b.data)
    assert np.array_equal(a.data, inputs(config, SEED).data)


def _over(res):
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


def test_the_toy_cell_runs_through_run_cell(cell):
    res = run.run_cell(cell.name, SEED, 0.3, False, require_chip=False,
                       scale=cell.cpu_scale, cell=cell)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(CHECKS)
    assert not _over(res)
    assert set(res["metrics"]) == {"spmv_ms", "tune_s", "setup_s"}


def test_an_altered_toy_answer_is_not_correct(cell, monkeypatch):
    original = Program.request

    def request(self, i):
        a = original(self, i)
        a.y = np.concatenate([a.y[:1] * 1.5, a.y[1:]])
        return a

    monkeypatch.setattr(Program, "request", request)
    res = run.run_cell(cell.name, SEED, 0.3, False, require_chip=False,
                       scale=cell.cpu_scale, cell=cell)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"] >= 1
    assert _over(res) == {"y_rel_err"}


def test_the_toy_control_readings_are_not_correct(cell):
    got = readings.control_readings(cell, SEED, scale=cell.cpu_scale)
    assert got["correct"] is False
    assert got["y_rel_err"] > cell.limits["y_rel_err"]
    assert got["answered_short"] == 0.0
