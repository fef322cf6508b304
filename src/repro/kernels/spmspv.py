"""Pallas TPU kernel: SpMSpV — sparse matrix x SPARSE vector.

Iterative graph workloads (BFS-like frontiers, power iteration from a seed
vertex, personalized PageRank pushes) multiply the same matrix by a vector
whose nonzero count starts tiny and densifies across iterations. A dense
SpMV touches every stored nonzero of A regardless; SpMSpV touches only the
columns the frontier activates (Li et al., "Adaptive SpMV/SpMSpV on GPUs
for Input Vectors of Varied Sparsity", arXiv:2006.16767). This module is
the TPU form of that kernel:

* **Storage** (``CscEll``): column-major ELL — per-column value/row-id
  slices padded to a lane-aligned width ``W`` (the transpose of the ELL
  layout in ``sparse/formats.py``). One extra all-padding column at index
  ``n_cols`` is the *spill column*: frontier padding entries point at it
  and contribute exact zeros.
* **Kernel**: the frontier's column indices (and their x values) ride
  scalar-prefetch SMEM; grid step ``(i, j)`` DMAs width-tile ``j`` of
  column ``active[i]`` via a BlockSpec index map driven by the prefetched
  indices, multiplies by the SMEM-resident ``x[active[i]]``, and
  scatter-adds by row id into the one VMEM-resident ``(n_rows + 1)``
  output vector (spill-slot convention: padding row ids equal ``n_rows``
  and land in the last slot, truncated by the wrapper).

Work is therefore proportional to ``sum(col_nnz[frontier])`` (padded to
tiles), not ``nnz(A)`` — the asymmetry the density-threshold policy in
``repro.solvers.adaptive`` trades on. The frontier length is padded to the
next power of two (min ``SUBLANE``), so a solve whose frontier grows from
1 to n retraces at most ``log2(n)`` distinct kernel shapes.

The in-kernel scatter-add by unsorted row id does not lower on Mosaic, so
the kernel runs only in interpret mode and raises ``NotImplementedError``
on a TPU backend.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    SUBLANE,
    DEFAULT_SCHEDULE,
    InfeasibleConfig,
    KernelSchedule,
    ceil_to,
    compiler_params,
    resolve_interpret,
)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class CscEll:
    """Column-major ELL: padded per-column slices, kernel-ready for SpMSpV.

    ``data[c]`` / ``rows[c]`` hold column ``c``'s nonzero values and row
    ids, zero-/spill-padded to the shared lane-aligned width. Row index
    ``n_rows`` is the spill row (padding slots); column index ``n_cols``
    is the spill column (frontier padding) — all-zero by construction.
    """

    data: jax.Array  # (n_cols + 1, W) values, 0 on padding slots
    rows: jax.Array  # (n_cols + 1, W) int32 row ids, n_rows on padding
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))

    @property
    def width(self) -> int:
        return int(self.data.shape[1])

    @property
    def nbytes(self) -> int:
        return int(
            self.data.size * self.data.dtype.itemsize
            + self.rows.size * self.rows.dtype.itemsize
        )


def csc_from_dense(
    dense: np.ndarray, schedule: KernelSchedule = DEFAULT_SCHEDULE, dtype=np.float32
) -> CscEll:
    """Build the padded column-slice storage from a dense matrix.

    The slice width is the max column nnz rounded up to the schedule's
    ``nnz_tile`` so every column is a whole number of kernel tiles. A
    matrix whose hub column approaches ``n_rows`` pads toward dense
    storage; that blow-up is rejected against the registry's storage
    bound exactly like an infeasible format conversion.
    """
    dense = np.asarray(dense)
    n_rows, n_cols = dense.shape
    col_t = dense.T  # (n_cols, n_rows): nonzeros below are column-major
    c_idx, r_idx = np.nonzero(col_t)
    counts = np.bincount(c_idx, minlength=n_cols)
    W = ceil_to(max(int(counts.max(initial=0)), 1), schedule.nnz_tile)
    from repro.sparse.registry import MAX_STORAGE_BYTES  # lazy: import cycle

    nbytes = (n_cols + 1) * W * (np.dtype(dtype).itemsize + 4)
    if nbytes > MAX_STORAGE_BYTES:
        raise InfeasibleConfig(
            f"CscEll storage {nbytes} B exceeds bound {MAX_STORAGE_BYTES} B "
            f"(width {W} over {n_cols} columns)"
        )
    data = np.zeros((n_cols + 1, W), dtype=dtype)
    rows = np.full((n_cols + 1, W), n_rows, dtype=np.int32)
    # position of each nonzero within its column
    pos = np.arange(c_idx.size) - np.repeat(
        np.concatenate([[0], np.cumsum(counts)[:-1]]), counts
    )
    data[c_idx, pos] = col_t[c_idx, r_idx]
    rows[c_idx, pos] = r_idx
    return CscEll(
        data=jnp.asarray(data), rows=jnp.asarray(rows), shape=(n_rows, n_cols)
    )


def col_nnz(dense: np.ndarray) -> np.ndarray:
    """Per-column nonzero counts — the SpMSpV modeled-work vector."""
    return (np.asarray(dense) != 0).sum(axis=0).astype(np.int64)


def _frontier_pad(k: int) -> int:
    """Padded frontier length: next power of two, at least one sublane."""
    return max(SUBLANE, 1 << (max(k, 1) - 1).bit_length())


def _spmspv_kernel(act_ref, xv_ref, d_ref, r_ref, y_ref, *, unroll, accum_dtype):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    nt = d_ref.shape[1]
    step = nt // unroll
    xval = xv_ref[i].astype(accum_dtype)
    y = y_ref[...].astype(accum_dtype)
    for k in range(unroll):
        sl = slice(k * step, (k + 1) * step)
        y = y.at[r_ref[0, sl]].add(d_ref[0, sl].astype(accum_dtype) * xval)
    y_ref[...] = y.astype(y_ref.dtype)


def csc_spmspv_pallas(
    data: jax.Array,
    rows: jax.Array,
    active: jax.Array,
    xvals: jax.Array,
    n_rows: int,
    schedule: KernelSchedule,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMSpV over padded column slices and a pre-padded frontier.

    ``data/rows: (n_cols + 1, W)`` with ``W % nnz_tile == 0``; ``active:
    (k_pad,)`` int32 column indices (padding entries == n_cols) and
    ``xvals: (k_pad,)`` their x values (padding entries == 0), both riding
    scalar-prefetch SMEM. Returns ``y: (n_rows + 1,)`` (last slot =
    padding spill, truncated by the wrapper).
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "csc_spmspv does not lower on TPU (in-kernel scatter-add by row id)"
        )
    W = data.shape[1]
    nt = schedule.nnz_tile
    if W % nt:
        raise InfeasibleConfig(
            f"CscEll width {W} not aligned to nnz_tile {nt}; re-prepare with "
            "this schedule"
        )
    grid = (int(active.shape[0]), W // nt)
    kernel = functools.partial(
        _spmspv_kernel, unroll=schedule.unroll, accum_dtype=schedule.jnp_accum_dtype
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nt), lambda i, j, act, xv: (act[i], j)),
            pl.BlockSpec((1, nt), lambda i, j, act, xv: (act[i], j)),
        ],
        # whole output vector resident in VMEM across the sequential grid
        out_specs=pl.BlockSpec((n_rows + 1,), lambda i, j, act, xv: (0,)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows + 1,), xvals.dtype),
        compiler_params=compiler_params("arbitrary", "arbitrary"),  # carried y
        interpret=True,
        name="csc_spmspv",
    )(active, xvals, data, rows)


def csc_spmspv(
    mat: CscEll,
    active: np.ndarray,
    xvals: np.ndarray,
    schedule: KernelSchedule = DEFAULT_SCHEDULE,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Frontier-level wrapper: pads, dispatches, truncates the spill slot.

    ``active``/``xvals`` are the frontier's column indices and values at
    their true length ``k`` (any k from 0 to n_cols); an empty frontier
    short-circuits to zeros without a kernel launch.
    """
    n_rows, n_cols = mat.shape
    active = np.asarray(active, dtype=np.int32).reshape(-1)
    xvals = np.asarray(xvals, dtype=np.float32).reshape(-1)
    if active.shape != xvals.shape:
        raise ValueError(
            f"frontier mismatch: {active.shape[0]} indices, {xvals.shape[0]} values"
        )
    k = int(active.size)
    if k == 0:
        return jnp.zeros((n_rows,), dtype=jnp.float32)
    if active.min() < 0 or active.max() >= n_cols:
        raise ValueError("frontier indices out of range")
    k_pad = _frontier_pad(k)
    act = np.full(k_pad, n_cols, dtype=np.int32)  # spill column padding
    xv = np.zeros(k_pad, dtype=np.float32)
    act[:k], xv[:k] = active, xvals
    y = csc_spmspv_pallas(
        mat.data,
        mat.rows,
        jnp.asarray(act),
        jnp.asarray(xv),
        n_rows,
        schedule,
        interpret=interpret,
    )
    return y[:n_rows]
