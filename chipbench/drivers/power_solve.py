"""Driver of power-iteration traffic: one caller's solves, back to back.

A traffic file that names this driver (``"driver": "power_solve"``) sets:

* ``max_iters``, ``tol``: the iterations and stopping residual of a solve;
* ``warmup_iters``: the iterations of the one solve set-up runs;
* ``tuner``: ``scale`` and ``train_matrices`` of the program's
  ``build_tuner``.

Set-up builds the session as ``python -m repro.launch.solve`` does
(compile-time plans, no bandit, no SpMSpV policy), generates the matrix
from the seed, times the first ``serve_optimize`` (``tune_s``) and warms up
with one short solve. A request is one solve through
``repro.solvers.power_iteration`` from a dense start vector drawn from
(seed, request index); it answers one SpMV per iteration.

The check runs the plain power iteration of ``chipbench.reference`` from
the same matrix and start vector for the iterations asked, and compares the
final unit vector, the final Rayleigh quotient, the residual of every
iteration and the count of iterations answered.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass

import numpy as np

from chipbench import matrices, reference

CHECKS = ("vector_rel_err", "eigenvalue_rel_err", "residual_gap", "iterations_short")
inputs = matrices.inputs  # the configuration's Table-7 matrix, dense


@dataclass
class Solve:
    """One answered request."""

    x0: np.ndarray
    vector: np.ndarray
    eigenvalue: float
    iterations: int
    residuals: list

    @property
    def spmvs(self) -> int:
        return self.iterations


def build_tuner(traffic: dict):
    from repro.core.session import build_tuner as _build
    from repro.sparse.generate import MATRIX_NAMES

    t = traffic["tuner"]
    return _build(scale=t["scale"], names=MATRIX_NAMES[: t["train_matrices"]])


class Program:
    """The program objects set-up builds once and the window drives."""

    def __init__(self, config: dict, traffic: dict, seed: int, tuner, scale: float = 1.0):
        import jax

        from repro.core.session import AutoSpmvSession

        self.traffic, self.objective, self.seed = traffic, config["objective"], seed
        self.dense = inputs(config, seed, scale)
        self.n_rows, self.n_cols = self.dense.shape
        self.nnz = int(np.count_nonzero(self.dense))
        self.session = AutoSpmvSession(tuner)
        t0 = time.perf_counter()
        plan = self.session.serve_optimize(self.dense, self.objective)
        jax.block_until_ready(plan.kernel.mat)
        self.metrics = {"tune_s": time.perf_counter() - t0}
        self.about = (f"matrix {config['name']}: n={self.n_rows} nnz={self.nnz}; "
                      f"plan {plan.fmt} {plan.schedule.as_dict()}")
        self._solve(matrices.start_vector(self.n_rows, seed, 0, stream=2), traffic["warmup_iters"])

    def _solve(self, x0: np.ndarray, iterations: int) -> Solve:
        from repro.solvers import power_iteration

        res = power_iteration(self.session, self.dense, tol=self.traffic["tol"],
                              max_iters=iterations, x0=x0, objective=self.objective)
        return Solve(x0, res.value, float(res.extras["eigenvalue"]), res.iterations,
                     list(res.residuals))

    def request(self, i: int) -> Solve:
        x0 = matrices.start_vector(self.n_rows, self.seed, i)
        return self._solve(x0, self.traffic["max_iters"])

    def release(self) -> np.ndarray:
        """Free the program's state; the matrix stays for the check."""
        from repro.kernels.ops import clear_kernel_memo

        self.session = None
        clear_kernel_memo()
        gc.collect()
        return self.dense


def check(dense: np.ndarray, answers: list[Solve], traffic: dict) -> list[dict]:
    """Each answered solve's numbers against the float64 reference."""
    ref = reference.PowerReference(dense)
    return [reference.compare(s.x0, s.vector, s.eigenvalue, s.residuals, s.iterations,
                              traffic["max_iters"], ref) for s in answers]


def control(dense: np.ndarray, config: dict, traffic: dict, seed: int, count: int) -> list[Solve]:
    """The first ``count`` requests answered by the reference at the
    precision below the one the configuration states."""
    ref = reference.PowerReference(dense, precision=reference.BELOW[config["dtype"]])
    iters = traffic["max_iters"]
    out = []
    for i in range(count):
        x0 = matrices.start_vector(dense.shape[0], seed, i)
        vector, eigenvalue, residuals = ref.solve(x0, iters)
        out.append(Solve(x0, vector, eigenvalue, iters, residuals))
    return out
