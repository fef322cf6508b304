"""Sparse matrix storage formats: CSR, ELL, BELL, SELL (paper §2.3).

Each format is a JAX pytree (registered dataclass) whose array fields are
device arrays and whose structural fields (shape, block size, slice height)
are static metadata. Conversion happens on the host in numpy — the paper's
run-time mode explicitly performs conversion on the CPU and *measures* it
(``c_latency``, Table 7), so converters are written to be timeable as-is.

TPU adaptation notes (DESIGN.md §2):

* ``CSR`` carries a ``row_ids`` companion (COO expansion of ``indptr``) —
  the segmented-reduction kernel that replaces GPU scalar/vector-CSR needs
  per-nonzero row ids. ``nbytes_core`` excludes companions so that format
  size comparisons match the textbook definition. Kernel-ready storage
  (``tiling != (0, 0)``) pads each row block's stream with explicit zeros.
* ``BELL`` blocks default to 8×128 (sublane × lane) instead of the paper's
  GPU 2×2, so a stored block times an X segment is an MXU-shaped matmul.
* ``SELL`` keeps true ragged storage (column-major slice planes stacked
  into one ``(total / C, C)`` plane + slice pointers); slice widths are
  padded to the TPU lane quantum (128) rather than 1 — the SELL-C-sigma
  adaptation for 8×128 vector registers.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Union

import jax
import numpy as np

# LANE/SUBLANE live in kernels/common.py (the single source of truth for TPU
# tiling constants); re-exported here for backward compatibility.
from repro.kernels.common import LANE, SUBLANE
from repro.kernels.csr import x_window

# Deprecated: the four *seed* formats. New code should use
# ``repro.sparse.registry.format_names()``, which also covers plugins.
FORMAT_NAMES = ("csr", "ell", "bell", "sell")


def _ceil_to(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


def _nbytes(*arrays) -> int:
    return int(sum(a.size * a.dtype.itemsize for a in arrays))


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class CSR:
    """Compressed Sparse Row. ``row_ids`` is the kernel-facing companion.

    ``tiling`` is the ``(rows_per_block, nnz_tile)`` geometry the stored
    stream is aligned to (``csr_tiled``): each block of ``rows_per_block``
    rows owns a whole number of ``nnz_tile`` tiles, at least one, its tail
    padded with explicit zeros in the block's last row, each at the column
    of the block's last entry. ``(0, 0)`` marks canonical, unpadded storage
    (``csr_from_dense``). A tiled stream also carries its window of x
    (``kernels.csr.x_window``): ``x_start``, each tile's first row of 128 of
    x, and ``x_walk``, the rows of x every tile walks from it; canonical
    storage has none.
    """

    data: jax.Array  # (nnz,) stored values
    indices: jax.Array  # (nnz,) column index per stored value
    indptr: jax.Array  # (n_rows + 1,) row boundaries
    row_ids: jax.Array  # (nnz,) row index per stored value (COO companion)
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    tiling: tuple[int, int] = dataclasses.field(
        default=(0, 0), metadata=dict(static=True)
    )
    x_start: jax.Array | None = None  # (n_tiles,) int32
    x_walk: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    @property
    def nbytes_core(self) -> int:
        return _nbytes(self.data, self.indices, self.indptr)

    @property
    def nbytes(self) -> int:
        return self.nbytes_core + _nbytes(self.row_ids)


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class ELL:
    """ELLPACK: row-major dense (n_rows, max_nnz) value/column planes.

    Padding slots hold value 0 and column 0 — a "safe gather" convention so
    kernels need no masking on the X gather (0 * x[0] == 0).
    """

    data: jax.Array  # (n_rows, width)
    cols: jax.Array  # (n_rows, width) int32
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))

    @property
    def width(self) -> int:
        return int(self.data.shape[1])

    @property
    def nbytes_core(self) -> int:
        return _nbytes(self.data, self.cols)

    nbytes = nbytes_core


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class BELL:
    """Blocked ELL: ELL over (br x bc) dense blocks.

    ``data[i, j]`` is the j-th stored block of block-row i; its block-column
    is ``block_cols[i, j]``. Padding blocks are all-zero with block-column 0.
    """

    data: jax.Array  # (n_block_rows, max_blocks, br, bc)
    block_cols: jax.Array  # (n_block_rows, max_blocks) int32
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    br: int = dataclasses.field(metadata=dict(static=True))
    bc: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_block_rows(self) -> int:
        return int(self.data.shape[0])

    @property
    def max_blocks(self) -> int:
        return int(self.data.shape[1])

    @property
    def nbytes_core(self) -> int:
        return _nbytes(self.data, self.block_cols)

    nbytes = nbytes_core


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class SELL:
    """Sliced ELL (SELL-C-q): slices of C rows, per-slice padded width.

    True ragged storage: ``data``/``cols`` stack the *column-major*
    (width_s, C) slice planes into one ``(total / C, C)`` plane — element
    (row r, k-th stored nonzero) of slice s lives at flat offset
    ``slice_ptr[s] + k * C + r``, i.e. at ``[slice_ptr[s] / C + k, r]``.
    Column-major slices make every width-tile of a slice a contiguous
    ``(nnz_tile, C)`` block, which is what lets the Pallas kernel address
    tiles with a plain BlockSpec (DESIGN.md §2). ``slice_ptr[s]`` is the
    flat element offset of slice s; ``slice_width[s] = (slice_ptr[s+1] -
    slice_ptr[s]) / C``. Widths are padded to the lane quantum ``q``.
    ``row_ids`` is the oracle-facing companion (row per element, == n_rows
    on padding slots).
    """

    data: jax.Array  # (total / C, C)
    cols: jax.Array  # (total / C, C) int32
    slice_ptr: jax.Array  # (n_slices + 1,) int32, element offsets
    slice_width: jax.Array  # (n_slices,) int32
    row_ids: jax.Array  # (total / C, C) int32, == n_rows on padding slots
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    C: int = dataclasses.field(metadata=dict(static=True))
    q: int = dataclasses.field(default=LANE, metadata=dict(static=True))

    @property
    def n_slices(self) -> int:
        return int(self.slice_width.shape[0])

    @property
    def nbytes_core(self) -> int:
        return _nbytes(self.data, self.cols, self.slice_ptr)

    @property
    def nbytes(self) -> int:
        return self.nbytes_core + _nbytes(self.slice_width, self.row_ids)


SparseFormat = Union[CSR, ELL, BELL, SELL]


# ---------------------------------------------------------------------------
# Host-side converters (numpy; timeable as the paper's c_latency)
# ---------------------------------------------------------------------------


def _row_counts(dense: np.ndarray) -> np.ndarray:
    return (dense != 0).sum(axis=1).astype(np.int64)


def csr_from_dense(dense: np.ndarray, dtype=np.float32) -> CSR:
    dense = np.asarray(dense)
    n_rows, n_cols = dense.shape
    rows, cols = np.nonzero(dense)
    data = dense[rows, cols].astype(dtype)
    counts = np.bincount(rows, minlength=n_rows)
    indptr = np.zeros(n_rows + 1, dtype=np.int32)
    np.cumsum(counts, out=indptr[1:])
    return CSR(
        data=jax.numpy.asarray(data),
        indices=jax.numpy.asarray(cols.astype(np.int32)),
        indptr=jax.numpy.asarray(indptr),
        row_ids=jax.numpy.asarray(rows.astype(np.int32)),
        shape=(n_rows, n_cols),
    )


def csr_tiled(
    dense: np.ndarray, rows_per_block: int, nnz_tile: int, dtype=np.float32
) -> CSR:
    """CSR whose stream is aligned to ``(rows_per_block, nnz_tile)`` tiles
    (see ``CSR.tiling``) — the layout the Pallas CSR kernel walks."""
    dense = np.asarray(dense)
    rows, cols = np.nonzero(dense)
    return _tiled(rows, cols, dense[rows, cols], dense.shape, rows_per_block, nnz_tile, dtype)


def csr_tiled_from_host(mat, rows_per_block: int, nnz_tile: int, dtype=np.float32) -> CSR:
    """``csr_tiled`` of a ``HostCSR``, built from its arrays, never dense."""
    return _tiled(mat.row_ids(), mat.indices, mat.data, mat.shape, rows_per_block, nnz_tile, dtype)


def tiled_stored(row_counts: np.ndarray, rows_per_block: int, nnz_tile: int) -> int:
    """Entries ``csr_tiled`` stores for these nonzeros per row: each block of
    ``rows_per_block`` rows padded to whole ``nnz_tile`` tiles, one at least."""
    counts = np.asarray(row_counts, dtype=np.int64)
    per_block = np.add.reduceat(counts, np.arange(0, max(counts.size, 1), rows_per_block))
    return int((np.maximum(-(-per_block // nnz_tile), 1) * nnz_tile).sum())


def _tiled(rows, cols, vals, shape, rpb: int, nt: int, dtype) -> CSR:
    """The tiled stream of the entries ``(rows, cols, vals)``, rows in order."""
    n_rows, n_cols = shape
    n_blocks = max(-(-n_rows // rpb), 1)
    starts = np.searchsorted(rows, np.arange(n_blocks + 1) * rpb)  # each block's first entry
    counts = np.diff(starts)
    stored = np.maximum(-(-counts // nt), 1) * nt  # >= 1 tile per block
    offsets = np.concatenate([[0], np.cumsum(stored)])
    # entry k, in block b, lands at offsets[b] + (k - starts[b])
    pos = np.repeat(offsets[:-1] - starts[:-1], counts)
    pos += np.arange(rows.size)
    last_row = np.minimum((np.arange(n_blocks) + 1) * rpb, max(n_rows, 1)) - 1
    # a padding slot takes the column of the last entry at or before its
    # block's end, so it never widens its tile's window of x
    last_col = cols[np.maximum(starts[1:] - 1, 0)] if rows.size else np.zeros(n_blocks)
    data = np.zeros(int(offsets[-1]), dtype=dtype)
    indices = np.repeat(last_col.astype(np.int32), stored)
    row_ids = np.repeat(last_row.astype(np.int32), stored)
    data[pos] = vals
    indices[pos] = cols
    row_ids[pos] = rows
    indptr = np.zeros(n_rows + 1, dtype=np.int32)
    np.cumsum(np.bincount(row_ids, minlength=n_rows)[:n_rows], out=indptr[1:])
    x_start, x_walk = x_window(indices, nt, n_cols)
    return CSR(
        data=jax.numpy.asarray(data),
        indices=jax.numpy.asarray(indices),
        indptr=jax.numpy.asarray(indptr),
        row_ids=jax.numpy.asarray(row_ids),
        shape=(n_rows, n_cols),
        tiling=(rpb, nt),
        x_start=None if x_start is None else jax.numpy.asarray(x_start),
        x_walk=x_walk,
    )


def ell_from_dense(dense: np.ndarray, dtype=np.float32, min_width: int = 1) -> ELL:
    dense = np.asarray(dense)
    n_rows, n_cols = dense.shape
    counts = _row_counts(dense)
    width = max(int(counts.max(initial=0)), min_width)
    data = np.zeros((n_rows, width), dtype=dtype)
    cols = np.zeros((n_rows, width), dtype=np.int32)
    rows, cc = np.nonzero(dense)
    # position of each nonzero within its row
    pos = np.arange(rows.size) - np.repeat(np.concatenate([[0], np.cumsum(counts)[:-1]]), counts)
    data[rows, pos] = dense[rows, cc]
    cols[rows, pos] = cc
    return ELL(
        data=jax.numpy.asarray(data),
        cols=jax.numpy.asarray(cols),
        shape=(n_rows, n_cols),
    )


def bell_from_dense(
    dense: np.ndarray, br: int = SUBLANE, bc: int = LANE, dtype=np.float32
) -> BELL:
    dense = np.asarray(dense)
    n_rows, n_cols = dense.shape
    pr, pc = _ceil_to(n_rows, br), _ceil_to(n_cols, bc)
    padded = np.zeros((pr, pc), dtype=dtype)
    padded[:n_rows, :n_cols] = dense
    nbr, nbc = pr // br, pc // bc
    blocks = padded.reshape(nbr, br, nbc, bc).transpose(0, 2, 1, 3)  # (nbr, nbc, br, bc)
    occupied = (blocks != 0).any(axis=(2, 3))  # (nbr, nbc)
    max_blocks = max(int(occupied.sum(axis=1).max(initial=0)), 1)
    data = np.zeros((nbr, max_blocks, br, bc), dtype=dtype)
    block_cols = np.zeros((nbr, max_blocks), dtype=np.int32)
    for i in range(nbr):
        js = np.nonzero(occupied[i])[0]
        data[i, : js.size] = blocks[i, js]
        block_cols[i, : js.size] = js
    return BELL(
        data=jax.numpy.asarray(data),
        block_cols=jax.numpy.asarray(block_cols),
        shape=(n_rows, n_cols),
        br=br,
        bc=bc,
    )


def sell_from_dense(
    dense: np.ndarray, C: int = 4 * SUBLANE, q: int = LANE, dtype=np.float32
) -> SELL:
    dense = np.asarray(dense)
    n_rows, n_cols = dense.shape
    counts = _row_counts(dense)
    n_slices = (n_rows + C - 1) // C
    widths = np.zeros(n_slices, dtype=np.int32)
    for s in range(n_slices):
        w = int(counts[s * C : (s + 1) * C].max(initial=0))
        widths[s] = _ceil_to(max(w, 1), q)
    slice_ptr = np.zeros(n_slices + 1, dtype=np.int32)
    np.cumsum(widths.astype(np.int64) * C, out=slice_ptr[1:])
    total = int(slice_ptr[-1])
    data = np.zeros(total, dtype=dtype)
    cols = np.zeros(total, dtype=np.int32)
    row_ids = np.full(total, n_rows, dtype=np.int32)
    for s in range(n_slices):
        w = int(widths[s])
        base = int(slice_ptr[s])
        # build the (C, w) slice plane row-major, then store transposed
        plane_d = np.zeros((C, w), dtype=dtype)
        plane_c = np.zeros((C, w), dtype=np.int32)
        plane_r = np.full((C, w), n_rows, dtype=np.int32)
        for r_local in range(min(C, n_rows - s * C)):
            r = s * C + r_local
            cc = np.nonzero(dense[r])[0]
            plane_d[r_local, : cc.size] = dense[r, cc]
            plane_c[r_local, : cc.size] = cc
            plane_r[r_local, :] = r
        data[base : base + C * w] = plane_d.T.ravel()
        cols[base : base + C * w] = plane_c.T.ravel()
        row_ids[base : base + C * w] = plane_r.T.ravel()
    return SELL(
        data=jax.numpy.asarray(data.reshape(-1, C)),
        cols=jax.numpy.asarray(cols.reshape(-1, C)),
        slice_ptr=jax.numpy.asarray(slice_ptr),
        slice_width=jax.numpy.asarray(widths),
        row_ids=jax.numpy.asarray(row_ids.reshape(-1, C)),
        shape=(n_rows, n_cols),
        C=C,
        q=q,
    )


def _empty_dense(mat) -> np.ndarray:
    return np.zeros(mat.shape, dtype=np.asarray(mat.data).dtype)


def csr_to_dense(mat: CSR) -> np.ndarray:
    out = _empty_dense(mat)
    # add, not assign: tiled storage repeats (row, col) for its explicit zeros
    np.add.at(
        out, (np.asarray(mat.row_ids), np.asarray(mat.indices)), np.asarray(mat.data)
    )
    return out


def ell_to_dense(mat: ELL) -> np.ndarray:
    out = _empty_dense(mat)
    n_rows = mat.shape[0]
    data, cols = np.asarray(mat.data), np.asarray(mat.cols)
    rows = np.repeat(np.arange(n_rows), data.shape[1])
    np.add.at(out, (rows, cols.ravel()), data.ravel())
    return out


def bell_to_dense(mat: BELL) -> np.ndarray:
    out = _empty_dense(mat)
    n_rows, n_cols = mat.shape
    data, bcols = np.asarray(mat.data), np.asarray(mat.block_cols)
    br, bc = mat.br, mat.bc
    for i in range(data.shape[0]):
        for j in range(data.shape[1]):
            r0, c0 = i * br, int(bcols[i, j]) * bc
            blk = data[i, j]
            rr = min(br, n_rows - r0)
            cc = min(bc, n_cols - c0)
            if rr > 0 and cc > 0:
                out[r0 : r0 + rr, c0 : c0 + cc] += blk[:rr, :cc]
    return out


def sell_to_dense(mat: SELL) -> np.ndarray:
    out = _empty_dense(mat)
    n_rows = mat.shape[0]
    rid = np.asarray(mat.row_ids).ravel()
    valid = rid < n_rows
    np.add.at(
        out,
        (rid[valid], np.asarray(mat.cols).ravel()[valid]),
        np.asarray(mat.data).ravel()[valid],
    )
    return out


def from_dense(dense: np.ndarray, fmt: str, **kwargs) -> SparseFormat:
    """Convert a dense matrix to the named (registered) format."""
    from repro.sparse.registry import get_format

    return get_format(fmt).from_dense(dense, **kwargs)


def to_dense(mat: SparseFormat) -> np.ndarray:
    """Densify any registered format (host-side; inverse of the converters)."""
    from repro.sparse.registry import spec_for

    return spec_for(mat).to_dense(mat)


def convert(mat: SparseFormat, fmt: str, **kwargs) -> SparseFormat:
    """Format-to-format conversion (via dense; host-side, timeable)."""
    return from_dense(to_dense(mat), fmt, **kwargs)
