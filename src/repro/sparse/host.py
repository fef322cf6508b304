"""A CSR matrix held on the host in plain numpy: the sparse input type.

``HostCSR`` is what a caller hands the session, the solvers and
``compile_spmv`` when the matrix is too large to hold dense: row pointers,
column indices and values, and the shape. It holds no device array and
needs nothing beyond numpy. The formats' ``prepare_csr`` converters build
their kernel storage from it directly, and the session hashes and analyses
its three arrays; nothing on that path makes the dense n x n array, and
``np.asarray`` on the container raises, so nothing can by accident.

Rows are stored in order; columns within a row need not be sorted. A
stored zero counts as an entry, as it does in any CSR.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass(frozen=True, eq=False)
class HostCSR:
    indptr: np.ndarray  # (n_rows + 1,) int, indptr[0] == 0
    indices: np.ndarray  # (nnz,) int32 column of each entry
    data: np.ndarray  # (nnz,) value of each entry
    shape: tuple[int, int]

    def __post_init__(self):
        n_rows, n_cols = (int(s) for s in self.shape)
        object.__setattr__(self, "shape", (n_rows, n_cols))
        if self.indptr.shape != (n_rows + 1,) or int(self.indptr[0]) != 0:
            raise ValueError(f"indptr must have {n_rows + 1} entries from 0")
        if np.any(np.diff(self.indptr) < 0):
            raise ValueError("indptr must not decrease")
        nnz = int(self.indptr[-1])
        if self.indices.shape != (nnz,) or self.data.shape != (nnz,):
            raise ValueError(f"indices and data must hold indptr[-1] = {nnz} entries")
        if nnz and (int(self.indices.min()) < 0 or int(self.indices.max()) >= n_cols):
            raise ValueError(f"column indices must lie in [0, {n_cols})")

    def __array__(self, *args, **kwargs):
        raise TypeError("a HostCSR is never made dense; convert it with a format's prepare_csr")

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])

    @property
    def nbytes(self) -> int:
        return int(self.indptr.nbytes + self.indices.nbytes + self.data.nbytes)

    def row_counts(self) -> np.ndarray:
        """Entries per row (int64, length ``n_rows``)."""
        return np.diff(self.indptr.astype(np.int64))

    def row_ids(self) -> np.ndarray:
        """The row of each entry, in storage order (int64)."""
        return np.repeat(np.arange(self.shape[0]), self.row_counts())

    def astype(self, dtype) -> "HostCSR":
        """The same matrix with values of ``dtype`` (itself where they are)."""
        if self.data.dtype == np.dtype(dtype):
            return self
        return HostCSR(self.indptr, self.indices, self.data.astype(dtype), self.shape)
