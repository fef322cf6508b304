"""Plain reference of the CG solve, and the comparison.

The reference imports nothing of the program. It runs textbook conjugate
gradients (Hestenes-Stiefel) from x0 = 0, as the solver states: one
``A p`` an iteration, ``alpha = r.r / p.Ap``, ``x += alpha p``, ``r -=
alpha Ap``, ``p = r + (r'.r' / r.r) p``, and the relative residual
``||r|| / ||b||`` of the recurrence at each iteration, in float64 on a
scipy CSR copy of the matrix the program was handed.

``precision="bfloat16"`` is the control: the same iteration with the
matrix values, ``p`` as it is multiplied and the iterates ``x``, ``r`` and
``p`` rounded to bfloat16, and the products summed in float32, the step
below the float32 that the configuration states.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse

from chipbench.reference import PRECISIONS, RESIDUAL_FLOOR, _bf16, residual_gap


def operator(mat, dtype=np.float64) -> scipy.sparse.csr_matrix:
    """The benchmark's CSR (``chipbench.stencil.Csr``) as a scipy matrix."""
    return scipy.sparse.csr_matrix(
        (np.asarray(mat.data, dtype), mat.indices, mat.indptr), shape=mat.shape)


class CgReference:
    """CG on one matrix, in float64 or in the bf16 control."""

    def __init__(self, mat, precision: str = "float64"):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}; expected one of {PRECISIONS}")
        self.precision = precision
        if precision == "float64":
            self.mat = operator(mat)
        else:
            self.mat = operator(mat, np.float32)
            self.mat.data = _bf16(self.mat.data)

    def _round(self, v: np.ndarray) -> np.ndarray:
        return v if self.precision == "float64" else _bf16(v).astype(np.float64)

    def matvec(self, x: np.ndarray) -> np.ndarray:
        if self.precision == "float64":
            return self.mat @ x
        return (self.mat @ _bf16(x)).astype(np.float64)

    def solve(self, b: np.ndarray, iterations: int) -> tuple[np.ndarray, list[float]]:
        """(x after ``iterations``, the residual of each iteration)."""
        b = np.asarray(b, dtype=np.float64)
        b_norm = float(np.linalg.norm(b)) or 1.0
        x = np.zeros_like(b)
        r = self._round(b.copy())
        p = r.copy()
        rr = float(r @ r)
        residuals = []
        for _ in range(iterations):
            Ap = self.matvec(p)
            pAp = float(p @ Ap)
            if pAp <= 0:  # converged to zero or not SPD: hold the iterate
                residuals.append(float(np.sqrt(rr)) / b_norm)
                continue
            alpha = rr / pAp
            x = self._round(x + alpha * p)
            r = self._round(r - alpha * Ap)
            rr_next = float(r @ r)
            p = self._round(r + (rr_next / rr) * p)
            rr = rr_next
            residuals.append(float(np.sqrt(rr)) / b_norm)
        return x, residuals


def true_residual(mat: scipy.sparse.csr_matrix, b: np.ndarray, x: np.ndarray) -> float:
    """``||b - A x|| / ||b||`` in float64."""
    b = np.asarray(b, dtype=np.float64)
    return float(np.linalg.norm(b - mat @ np.asarray(x, np.float64)) / (np.linalg.norm(b) or 1.0))


def compare(b, x, residuals, iterations: int, max_iters: int, reference: CgReference,
            exact: scipy.sparse.csr_matrix) -> dict:
    """Errors of one answered solve against the float64 reference from the
    same right-hand side, run for the iterations the solve was asked for.
    ``exact`` is the float64 matrix the true residual is taken with."""
    ref_x, ref_res = reference.solve(b, max_iters)
    x = np.asarray(x, dtype=np.float64)
    if residuals:
        true = true_residual(exact, b, x)
        true_gap = abs(true - residuals[-1]) / max(true, RESIDUAL_FLOOR)
    else:
        true_gap = float("inf")
    return {
        "solution_rel_err": float(np.abs(x - ref_x).max() / np.abs(ref_x).max()),
        "residual_gap": residual_gap(residuals, ref_res),
        "true_residual_gap": float(true_gap),
        "iterations_short": float(max_iters - iterations),
    }
