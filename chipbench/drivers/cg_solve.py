"""Driver of CG traffic: one caller's solves of ``A x = b``, back to back.

A traffic file that names this driver (``"driver": "cg_solve"``) sets:

* ``max_iters``, ``tol``: the iterations and stopping residual of a solve;
* ``noise``: the spread of each solve's chosen solution around 1;
* ``warmup_iters``: the iterations of the one solve set-up runs;
* ``tuner``: ``scale`` and ``train_matrices`` of the program's
  ``build_tuner``.

The configuration gives the ``grid`` of HPCG's 27-point operator, which the
benchmark builds (``chipbench.stencil``) as a CSR, never dense, and hands
to the program as a ``repro.sparse.host.HostCSR``. Set-up builds the
session as ``python -m repro.launch.solve`` does (compile-time plans, no
bandit, no SpMSpV policy), times the first ``serve_optimize`` (``tune_s``)
and warms up with one short solve. Request ``i`` is one solve through
``repro.solvers.cg`` from x0 = 0 of ``b = A x*``, where ``x* = 1 + noise
z`` and ``z`` is a normal vector drawn from (seed, i) (HPCG's own ``b`` is
``A 1``); the benchmark makes ``b`` in float64 inside the request. It
answers one SpMV per iteration.

The check runs the plain float64 CG of ``chipbench.cg_reference`` from the
same ``b`` for the iterations asked, and compares the final iterate, the
residual of every iteration, the residual the final iterate really has
against the last one reported, and the count of iterations answered.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass

import numpy as np

from chipbench import cg_reference, stencil
from chipbench.matrices import seed_words

CHECKS = ("solution_rel_err", "residual_gap", "true_residual_gap", "iterations_short")


def inputs(config: dict, seed: int, scale: float = 1.0) -> stencil.Csr:
    """The configuration's operator; ``scale`` below 1 shrinks the grid.
    HPCG's values are fixed, so the seed draws only the right-hand sides."""
    return stencil.generate(*stencil.grid(config["grid"], scale))


def rhs(exact, n: int, seed: int, i: int, noise: float, stream: int = 1) -> np.ndarray:
    """``b = A x*`` of solve ``i`` in float64; ``stream`` keeps the warm-up's
    apart from the window's."""
    z = np.random.default_rng([seed_words(seed), stream, i]).standard_normal(n)
    return exact @ (1.0 + noise * z)


@dataclass
class Solve:
    """One answered request."""

    b: np.ndarray
    x: np.ndarray
    iterations: int
    residuals: list

    @property
    def spmvs(self) -> int:
        return self.iterations


def build_tuner(traffic: dict):
    # a program without a host CSR input cannot run this cell: say so before
    # the tuner is built
    from repro.sparse.host import HostCSR  # noqa: F401
    from repro.core.session import build_tuner as _build
    from repro.sparse.generate import MATRIX_NAMES

    t = traffic["tuner"]
    return _build(scale=t["scale"], names=MATRIX_NAMES[: t["train_matrices"]])


class Program:
    """The program objects set-up builds once and the window drives."""

    def __init__(self, config: dict, traffic: dict, seed: int, tuner, scale: float = 1.0):
        import jax

        from repro.core.session import AutoSpmvSession
        from repro.sparse.host import HostCSR

        self.traffic, self.objective, self.seed = traffic, config["objective"], seed
        self.mat = inputs(config, seed, scale)
        self.n_rows, self.n_cols = self.mat.shape
        self.nnz = self.mat.nnz
        self.exact = cg_reference.operator(self.mat)  # makes each b
        self.host = HostCSR(self.mat.indptr, self.mat.indices, self.mat.data, self.mat.shape)
        self.session = AutoSpmvSession(tuner)
        t0 = time.perf_counter()
        plan = self.session.serve_optimize(self.host, self.objective)
        jax.block_until_ready(plan.kernel.mat)
        self.metrics = {"tune_s": time.perf_counter() - t0}
        stored = int(plan.kernel.mat.data.shape[0])
        self.about = (f"matrix {config['name']}: grid {stencil.grid(config['grid'], scale)} "
                      f"n={self.n_rows} nnz={self.nnz}; plan {plan.fmt} {plan.schedule.as_dict()}; "
                      f"stored {stored} = {stored / self.nnz:.4f} x nnz")
        self._solve(self._rhs(0, stream=2), traffic["warmup_iters"])

    def _rhs(self, i: int, stream: int = 1) -> np.ndarray:
        return rhs(self.exact, self.n_rows, self.seed, i, self.traffic["noise"], stream)

    def _solve(self, b: np.ndarray, iterations: int) -> Solve:
        from repro.solvers import cg

        res = cg(self.session, self.host, b, tol=self.traffic["tol"], max_iters=iterations,
                 objective=self.objective)
        return Solve(b, res.value, res.iterations, list(res.residuals))

    def request(self, i: int) -> Solve:
        return self._solve(self._rhs(i), self.traffic["max_iters"])

    def release(self) -> stencil.Csr:
        """Free the program's state; the matrix stays for the check."""
        from repro.kernels.ops import clear_kernel_memo

        self.session = self.host = None
        clear_kernel_memo()
        gc.collect()
        return self.mat


def check(mat: stencil.Csr, answers: list[Solve], traffic: dict) -> list[dict]:
    """Each answered solve's numbers against the float64 reference."""
    ref = cg_reference.CgReference(mat)
    return [cg_reference.compare(s.b, s.x, s.residuals, s.iterations, traffic["max_iters"],
                                 ref, ref.mat) for s in answers]


def control(mat: stencil.Csr, config: dict, traffic: dict, seed: int, count: int) -> list[Solve]:
    """The first ``count`` requests answered by the reference at the
    precision below the one the configuration states."""
    from chipbench.reference import BELOW

    low = cg_reference.CgReference(mat, precision=BELOW[config["dtype"]])
    exact = cg_reference.operator(mat)
    iters = traffic["max_iters"]
    out = []
    for i in range(count):
        b = rhs(exact, mat.shape[0], seed, i, traffic["noise"])
        x, residuals = low.solve(b, iters)
        out.append(Solve(b, x, iters, residuals))
    return out
