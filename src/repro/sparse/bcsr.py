"""BCSR: blocked compressed-sparse-row — the registry's fifth format.

A CMRS-spirited (Koza et al., arXiv:1203.2946) row-compressed relative of
BELL: storage is a *flat* list of occupied (br x 128) blocks with per-block
block-row / block-column ids, instead of BELL's ELL-style per-block-row
padding to ``max_blocks``. On matrices whose block occupancy is skewed
across block-rows (power-law graphs), BCSR stores only the occupied blocks
— the same padding-elimination argument CSR makes over ELL, one level up.

TPU adaptation mirrors the BELL kernel: ``block_cols`` is a scalar-prefetch
operand whose BlockSpec index map DMAs exactly the 128-wide X panel each
stored block needs, and each grid step is a dense (1, 128) x (br, 128)^T
product on the MXU. Row compression is handled like the CSR kernel handles
tiles: ``block_rows`` (also scalar-prefetched, sorted) drives the output
index map, so consecutive blocks of one block-row accumulate into the same
lane-dense ``(1, br)`` output block. Block-rows without a stored block are
never visited; the output aliases a zero-filled input, so they read zero.
Padding blocks carry ``block_row == n_block_rows`` and land in a spill row
that the wrapper truncates.

This module is deliberately *plugin-shaped*: it touches none of the
dispatch layers (ops / tuning_space / objectives / session / adaptive).
Importing it (or calling ``register()``) is the entire integration — the
format then appears in ``full_space()``, the tuning dataset, classifier
labels, the bandit arm set, and serves through ``SpmvServer``.
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

from repro.kernels.bell import block_matvec, x_panels
from repro.kernels.common import (
    LANE,
    SUBLANE,
    VMEM_BYTES,
    InfeasibleConfig,
    KernelSchedule,
    ceil_to,
    compiler_params,
    first_of_run,
    resolve_interpret,
)
from repro.sparse.registry import (
    FormatSpec,
    KernelFootprint,
    MatrixStats,
    check_storage_bytes,
    register_format,
)

_VAL_B, _IDX_B = 4.0, 4.0


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class BCSR:
    """Blocked CSR: flat occupied (br x bc) blocks + block-row compression.

    ``data[k]`` is the k-th stored block (block-row-major order); its block
    coordinates are ``(block_rows[k], block_cols[k])``. Trailing padding
    blocks are all-zero with ``block_col == 0`` and ``block_row ==
    n_block_rows`` (the spill row). ``block_ptr`` is the CSR-style pointer
    over block-rows covering the *real* (unpadded) blocks.
    """

    data: jax.Array  # (n_blocks_pad, br, bc)
    block_cols: jax.Array  # (n_blocks_pad,) int32
    block_rows: jax.Array  # (n_blocks_pad,) int32
    block_ptr: jax.Array  # (n_block_rows + 1,) int32
    shape: tuple[int, int] = dataclasses.field(metadata=dict(static=True))
    br: int = dataclasses.field(metadata=dict(static=True))
    bc: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_block_rows(self) -> int:
        return int(self.block_ptr.shape[0] - 1)

    @property
    def n_blocks(self) -> int:
        """Real (unpadded) stored blocks."""
        return int(np.asarray(self.block_ptr)[-1])

    @property
    def nbytes_core(self) -> int:
        arrs = (self.data, self.block_cols, self.block_ptr)
        return int(sum(a.size * a.dtype.itemsize for a in arrs))

    @property
    def nbytes(self) -> int:
        return self.nbytes_core + int(
            self.block_rows.size * self.block_rows.dtype.itemsize
        )


# ---------------------------------------------------------------------------
# Host-side conversion (numpy; timeable as the paper's c_latency)
# ---------------------------------------------------------------------------


def bcsr_from_dense(
    dense: np.ndarray,
    br: int = SUBLANE,
    bc: int = LANE,
    dtype=np.float32,
    pad_blocks_to: int = 1,
) -> BCSR:
    dense = np.asarray(dense)
    n_rows, n_cols = dense.shape
    pr, pc = ceil_to(n_rows, br), ceil_to(n_cols, bc)
    padded = np.zeros((pr, pc), dtype=dtype)
    padded[:n_rows, :n_cols] = dense
    nbr, nbc = pr // br, pc // bc
    blocks = padded.reshape(nbr, br, nbc, bc).transpose(0, 2, 1, 3)  # (nbr, nbc, br, bc)
    occupied = (blocks != 0).any(axis=(2, 3))  # (nbr, nbc)
    rows_idx, cols_idx = np.nonzero(occupied)  # block-row-major order
    nb = rows_idx.size
    counts = np.bincount(rows_idx, minlength=nbr)
    block_ptr = np.zeros(nbr + 1, dtype=np.int32)
    np.cumsum(counts, out=block_ptr[1:])
    nb_pad = ceil_to(max(nb, 1), max(pad_blocks_to, 1))
    data = np.zeros((nb_pad, br, bc), dtype=dtype)
    block_cols = np.zeros(nb_pad, dtype=np.int32)
    block_rows = np.full(nb_pad, nbr, dtype=np.int32)  # padding -> spill row
    data[:nb] = blocks[rows_idx, cols_idx]
    block_cols[:nb] = cols_idx
    block_rows[:nb] = rows_idx
    return BCSR(
        data=jnp.asarray(data),
        block_cols=jnp.asarray(block_cols),
        block_rows=jnp.asarray(block_rows),
        block_ptr=jnp.asarray(block_ptr),
        shape=(n_rows, n_cols),
        br=br,
        bc=bc,
    )


def bcsr_to_dense(mat: BCSR) -> np.ndarray:
    n_rows, n_cols = mat.shape
    out = np.zeros((n_rows, n_cols), dtype=np.asarray(mat.data).dtype)
    data = np.asarray(mat.data)
    brow = np.asarray(mat.block_rows)
    bcol = np.asarray(mat.block_cols)
    nbr = mat.n_block_rows
    for k in range(data.shape[0]):
        if brow[k] >= nbr:  # padding block
            continue
        r0, c0 = int(brow[k]) * mat.br, int(bcol[k]) * mat.bc
        rr = min(mat.br, n_rows - r0)
        cc = min(mat.bc, n_cols - c0)
        if rr > 0 and cc > 0:
            out[r0 : r0 + rr, c0 : c0 + cc] += data[k][:rr, :cc]
    return out


# ---------------------------------------------------------------------------
# Pure-jnp oracle
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("bc", "nbr", "n_rows"))
def _bcsr_ref_impl(data, block_cols, block_rows, x, *, bc, nbr, n_rows):
    n_cols_pad = ((x.shape[0] + bc - 1) // bc) * bc
    xp = jnp.zeros(n_cols_pad, x.dtype).at[: x.shape[0]].set(x)
    xseg = xp.reshape(-1, bc)[block_cols]  # (nb_pad, bc)
    v = jnp.einsum("krc,kc->kr", data, xseg)  # per-block matvec (MXU shapes)
    y = jax.ops.segment_sum(v, block_rows, num_segments=nbr + 1)  # spill row
    return y[:nbr].reshape(-1)[:n_rows]


def spmv_bcsr(mat: BCSR, x: jax.Array) -> jax.Array:
    return _bcsr_ref_impl(
        mat.data,
        mat.block_cols,
        mat.block_rows,
        jnp.asarray(x),
        bc=mat.bc,
        nbr=mat.n_block_rows,
        n_rows=mat.shape[0],
    )


# ---------------------------------------------------------------------------
# Pallas TPU kernel
# ---------------------------------------------------------------------------


def _bcsr_kernel(bcols_ref, brows_ref, d_ref, x_ref, y0_ref, y_ref, *, accum_dtype):
    del bcols_ref, y0_ref  # the X index map / the aliased zero output
    i = pl.program_id(0)

    @pl.when(first_of_run(brows_ref, i))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    v = block_matvec(x_ref[0], d_ref[0], accum_dtype)  # (1, br)
    y_ref[...] += v.reshape(y_ref.shape).astype(y_ref.dtype)


def bcsr_spmv_pallas(
    data: jax.Array,
    block_cols: jax.Array,
    block_rows: jax.Array,
    x: jax.Array,
    n_block_rows: int,
    schedule: KernelSchedule,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMV over flat BCSR storage.

    ``data: (nb_pad, br, bc)``, ``block_cols/block_rows: (nb_pad,)`` int32
    in block-row order (padding blocks: col 0 / row ``n_block_rows``),
    ``x: (n_cols,)``. Returns ``y: ((n_block_rows + 1) * br,)`` — the last
    ``br`` entries are the padding spill, truncated by the wrapper.
    """
    nb_pad, br, bc = data.shape
    kernel = functools.partial(_bcsr_kernel, accum_dtype=schedule.jnp_accum_dtype)
    out_shape = jax.ShapeDtypeStruct((n_block_rows + 1, 1, br), x.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nb_pad,),
        in_specs=[
            pl.BlockSpec((1, br, bc), lambda i, bcols, brows: (i, 0, 0)),
            # scalar-prefetch-driven gather: DMA the X panel this block needs
            pl.BlockSpec((1, 1, bc), lambda i, bcols, brows: (bcols[i], 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # zero output, never read
        ],
        out_specs=pl.BlockSpec((1, 1, br), lambda i, bcols, brows: (brows[i], 0, 0)),
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=compiler_params("arbitrary"),  # carried accumulation
        input_output_aliases={4: 0},  # block-rows without blocks stay zero
        interpret=resolve_interpret(interpret),
        name="bcsr_spmv",
    )(
        block_cols,
        block_rows,
        data,
        x_panels(x, bc),
        jnp.zeros(out_shape.shape, out_shape.dtype),
    )
    return y.reshape(-1)


# ---------------------------------------------------------------------------
# FormatSpec entrypoints
# ---------------------------------------------------------------------------


def _blocks_per_tile(schedule: KernelSchedule) -> int:
    # nnz_tile is lane-quantized; one (br x 128) block consumes 128 lanes,
    # so the schedule's tile maps to a block-count storage quantum
    return max(schedule.nnz_tile // LANE, 1)


def _bcsr_prepare(dense: np.ndarray, schedule: KernelSchedule) -> BCSR:
    dense = np.asarray(dense)
    br = min(schedule.rows_per_block, 256)
    # the true stored size: the occupied blocks, padded to the tile quantum
    n_blocks, _ = MatrixStats(dense).block_occupancy(br, LANE)
    stored = ceil_to(max(n_blocks, 1), _blocks_per_tile(schedule))
    check_storage_bytes(stored * (br * LANE + 2) * 4, "BCSR")
    return bcsr_from_dense(
        dense, br=br, bc=LANE, pad_blocks_to=_blocks_per_tile(schedule)
    )


def _bcsr_spmv(mat: BCSR, x, schedule: KernelSchedule):
    bpt = _blocks_per_tile(schedule)
    if mat.data.shape[0] % bpt:
        raise InfeasibleConfig(
            f"BCSR block count {mat.data.shape[0]} not aligned to the "
            f"nnz_tile={schedule.nnz_tile} storage quantum ({bpt} blocks); "
            "convert with prepare(..., schedule)"
        )
    n_block_rows = mat.block_ptr.shape[0] - 1
    y = bcsr_spmv_pallas(
        mat.data, mat.block_cols, mat.block_rows, x, n_block_rows, schedule
    )
    return y[: mat.shape[0]]


def _bcsr_footprint(stats: MatrixStats, schedule: KernelSchedule) -> KernelFootprint:
    n, m, nnz = stats.n_rows, stats.n_cols, stats.nnz
    x_bytes, y_bytes = m * _VAL_B, n * _VAL_B
    br, bc = min(schedule.rows_per_block, 256), LANE
    n_blocks, _ = stats.block_occupancy(br, bc)
    nb_pad = ceil_to(max(n_blocks, 1), _blocks_per_tile(schedule))
    nbr = ceil_to(n, br) // br
    stored = float(nb_pad) * br * bc  # row-compressed: occupied blocks only
    x_traffic = (
        float(nb_pad) * bc * _VAL_B  # streamed panels (scalar-prefetch DMA)
        if schedule.x_residency == "stream"
        else x_bytes
    )
    hbm = stored * _VAL_B + nb_pad * 2 * _IDX_B + x_traffic + y_bytes
    steps = float(nb_pad)
    tile_b = br * bc * _VAL_B + bc * _VAL_B
    # output resident across the sequential grid, like the CSR kernel's Y
    vmem = (
        2 * tile_b
        + (nbr + 1) * br * _VAL_B
        + (x_bytes if schedule.x_residency == "vmem" else 0)
    )
    return KernelFootprint(
        2.0 * nnz,
        2 * stored,
        hbm,
        0.0,
        float(nb_pad) * br,  # per-block scatter-accumulate into resident Y
        steps,
        1.0,
        vmem,
        vmem <= VMEM_BYTES,
    )


BCSR_SPEC = FormatSpec(
    name="bcsr",
    container=BCSR,
    from_dense=bcsr_from_dense,
    to_dense=bcsr_to_dense,
    prepare=_bcsr_prepare,
    spmv=_bcsr_spmv,
    reference=spmv_bcsr,
    footprint=_bcsr_footprint,
    priority=40,
    description="Blocked CSR: flat occupied 8x128 blocks, row-compressed",
)


def register() -> FormatSpec:
    """Idempotent activation: make BCSR a live format everywhere."""
    return register_format(BCSR_SPEC, overwrite=True)


register()
