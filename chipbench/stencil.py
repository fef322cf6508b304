"""The benchmark's own copy of HPCG's 27-point operator, as a plain CSR.

Written apart from the program's ``sparse/generate.py`` (``hpcg_stencil``)
so that a change there cannot move the yardstick: the grid's point numbers
are laid in a 3-D array with a border of -1, and each point's 27 shifted
views name its neighbours, or -1 past the grid's edge. Row ``ix + nx * (iy +
ny * iz)`` holds 26 on the diagonal and -1 for every neighbour inside the
grid, columns ascending, as HPCG's ``GenerateProblem`` writes them. Nothing
here makes the dense n x n array.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True, eq=False)
class Csr:
    """Row pointers, column ids and values of an n x n matrix."""

    indptr: np.ndarray  # int64
    indices: np.ndarray  # int32
    data: np.ndarray  # float32
    shape: tuple[int, int]

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def grid(config_grid, scale: float = 1.0) -> tuple[int, int, int]:
    """The grid a run builds: the configuration's, each side shrunk by the
    cube root of ``scale`` (so ``scale`` is the share of the rows), at
    least 2."""
    return tuple(max(2, round(g * scale ** (1 / 3))) for g in config_grid)


def entries(nx: int, ny: int, nz: int) -> int:
    """Nonzeros of the operator: each axis couples a point with itself and
    its in-grid neighbours, 3 n - 2 pairs along an axis of n points."""
    return (3 * nx - 2) * (3 * ny - 2) * (3 * nz - 2)


def generate(nx: int, ny: int, nz: int) -> Csr:
    """The 27-point operator on an ``nx x ny x nz`` grid, float32."""
    n = nx * ny * nz
    framed = np.full((nz + 2, ny + 2, nx + 2), -1, dtype=np.int64)
    framed[1:-1, 1:-1, 1:-1] = np.arange(n).reshape(nz, ny, nx)
    neighbours = np.stack([
        framed[1 + dz:nz + 1 + dz, 1 + dy:ny + 1 + dy, 1 + dx:nx + 1 + dx].reshape(n)
        for dz in (-1, 0, 1) for dy in (-1, 0, 1) for dx in (-1, 0, 1)
    ], axis=1)  # (n, 27): columns ascending along each row, -1 outside
    inside = neighbours >= 0
    cols = neighbours[inside]
    rows = np.repeat(np.arange(n), inside.sum(axis=1))
    data = np.where(cols == rows, np.float32(26.0), np.float32(-1.0))
    indptr = np.concatenate([[0], np.cumsum(inside.sum(axis=1))]).astype(np.int64)
    return Csr(indptr, cols.astype(np.int32), data, (n, n))
