"""Plain reference of the power-iteration solve, and the comparison.

The reference imports nothing of the program. It runs the same normalized
power iteration the solver states (Rayleigh quotient of the unit iterate,
residual ``||A x - λ x|| / |λ|`` of each iteration, entries under
``prune_tol`` dropped after normalization and the iterate renormalized, a
zero product restarted from the uniform vector) in float64 on a CSR copy
of the matrix the program was handed.

``precision="bfloat16"`` is the control: the same iteration with the
matrix values and each iterate rounded to bfloat16 and the products summed
in float32, the step below the float32 that the configurations state.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse

PRECISIONS = ("float64", "bfloat16")
BELOW = {"float32": "bfloat16"}  # the control's precision, by the stated one
# residuals under this are compared as if they were this: below it a float32
# solve's residual is round-off, where the float64 reference's keeps falling
RESIDUAL_FLOOR = 1e-6


def _bf16(a: np.ndarray) -> np.ndarray:
    return np.asarray(a, dtype=np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


class PowerReference:
    """Power iteration on one matrix, in float64 or in the bf16 control."""

    def __init__(self, dense: np.ndarray, precision: str = "float64", prune_tol: float = 1e-7):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}; expected one of {PRECISIONS}")
        csr = scipy.sparse.csr_matrix(dense)
        if precision == "float64":
            self.mat = csr.astype(np.float64)
        else:
            csr.data = _bf16(csr.data)
            self.mat = csr.astype(np.float32)
        self.precision = precision
        self.prune_tol = prune_tol

    def matvec(self, x: np.ndarray) -> np.ndarray:
        if self.precision == "float64":
            return self.mat @ x
        return (self.mat @ _bf16(x)).astype(np.float64)

    def solve(self, x0: np.ndarray, iterations: int) -> tuple[np.ndarray, float, list[float]]:
        """(unit eigenvector estimate, Rayleigh quotient, residual of each
        iteration) after ``iterations``."""
        n = self.mat.shape[0]
        x = np.asarray(x0, dtype=np.float64)
        x = x / (np.linalg.norm(x) or 1.0)
        lam, residuals = 0.0, []
        for _ in range(iterations):
            y = self.matvec(x)
            lam = float(x @ y)
            norm = float(np.linalg.norm(y))
            if norm == 0.0:
                y, norm = np.full(n, 1.0 / np.sqrt(n)), 1.0
            residuals.append(float(np.linalg.norm(y - lam * x)) / (abs(lam) or 1.0))
            x = y / norm
            if self.prune_tol > 0:
                x = np.where(np.abs(x) >= self.prune_tol, x, 0.0)
                x = x / (np.linalg.norm(x) or 1.0)
        return x, lam, residuals


def vector_error(x: np.ndarray, ref: np.ndarray) -> float:
    """Largest entry gap, relative to the reference's largest entry."""
    x = np.asarray(x, dtype=np.float64)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def eigenvalue_error(lam: float, ref: float) -> float:
    return abs(float(lam) - ref) / abs(ref)


def residual_gap(residuals, ref: list[float]) -> float:
    """Widest gap between the solve's residual and the reference's at the
    same iteration, relative to the reference's (or to ``RESIDUAL_FLOOR``,
    where that is larger); infinite where the histories differ in length."""
    if len(residuals) != len(ref) or not ref:
        return float("inf")
    ref = np.asarray(ref, np.float64)
    gap = np.abs(np.asarray(residuals, np.float64) - ref) / np.maximum(ref, RESIDUAL_FLOOR)
    return float(gap.max())


def compare(x0, vector, eigenvalue, residuals, iterations: int, max_iters: int,
            reference: PowerReference) -> dict:
    """Errors of one answered solve against the reference from the same
    start vector, run for the iterations the solve was asked for."""
    ref_vec, ref_lam, ref_res = reference.solve(x0, max_iters)
    return {
        "vector_rel_err": vector_error(vector, ref_vec),
        "eigenvalue_rel_err": eigenvalue_error(eigenvalue, ref_lam),
        "residual_gap": residual_gap(residuals, ref_res),
        "iterations_short": float(max_iters - iterations),
    }
