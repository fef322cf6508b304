"""Pallas TPU kernel: CSR SpMV (row-block segmented reduction).

GPU scalar/vector-CSR does not map onto the TPU's 8x128 vector unit, so the
CSR kernel is re-thought (DESIGN.md §2). ``prepare`` pads the nonzero
stream of every block of ``rows_per_block`` rows to a whole number of
``nnz_tile`` tiles (explicit zeros in the block's last row, at least one
tile per block), so each tile belongs to exactly one row block. The grid
walks the tiles in row order:

* X is gathered by XLA before the launch (Mosaic cannot gather single
  elements of a vector inside a kernel) into a plane shaped like the
  values;
* a tile -> row-block map (each tile's first row id over ``rows_per_block``,
  scalar-prefetched) drives the output index map, so consecutive tiles of
  one block accumulate into the same lane-dense ``(1, rows_per_block)``
  output block;
* inside a tile, the products are reduced per row by a one-hot mask of the
  row ids against the block's rows — a sorted segmented reduction in
  place of a scatter-add.

Tiles are stored as ``(n_tiles, 1, nnz_tile)`` views so every block's last
two dims equal the array's, as Mosaic's (8, 128) tiling rule requires. The
price of CSR's no-padding storage is the masked reduction — exactly the
"CSR is hostile to wide SIMD" effect the paper observes on GPU (finding 5),
now in TPU form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    GATHER_SCOPE,
    KernelSchedule,
    compiler_params,
    first_of_run,
    resolve_interpret,
    row_sums,
)


def _csr_kernel(
    bmap_ref, d_ref, xg_ref, r_ref, y_ref, *, rpb: int, unroll: int, accum_dtype
):
    t = pl.program_id(0)

    @pl.when(first_of_run(bmap_ref, t))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    p = d_ref[0].astype(accum_dtype) * xg_ref[0].astype(accum_dtype)  # (1, nt)
    local = r_ref[0] - bmap_ref[t] * rpb  # (1, nt) row within the block
    nt = p.shape[1]
    hit = local == lax.broadcasted_iota(jnp.int32, (rpb, nt), 0)
    # select in float32: Mosaic cannot broadcast a mask over packed bf16
    masked = jnp.where(hit, p.astype(jnp.float32), 0.0).astype(accum_dtype)
    y_ref[...] += row_sums(masked, unroll).reshape(y_ref.shape).astype(y_ref.dtype)


def csr_spmv_pallas(
    data: jax.Array,
    indices: jax.Array,
    row_ids: jax.Array,
    x: jax.Array,
    n_rows: int,
    tiling: tuple[int, int],
    schedule: KernelSchedule,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMV over a row-block-aligned CSR stream.

    ``data/indices/row_ids: (nnz_pad,)`` laid out by ``prepare`` for
    ``tiling = (rows_per_block, nnz_tile)``: every tile lies inside one row
    block and every row block has at least one tile. The schedule supplies
    the numerics (``accum_dtype``, ``unroll``). Returns ``y:
    (n_row_blocks * rows_per_block,)``.
    """
    rpb, nt = tiling
    (nnz_pad,) = data.shape
    if nnz_pad % nt or nt % schedule.unroll:
        raise ValueError(f"CSR stream {nnz_pad} not aligned to nnz_tile {nt}")
    n_tiles = nnz_pad // nt
    n_blocks = -(-n_rows // rpb)
    tiles = lambda a: a.reshape(n_tiles, 1, nt)  # noqa: E731
    block_of_tile = row_ids[::nt] // rpb
    with jax.named_scope(GATHER_SCOPE):
        xg = jnp.take(x, tiles(indices), axis=0)  # XLA gather, tile-shaped
    kernel = functools.partial(
        _csr_kernel,
        rpb=rpb,
        unroll=schedule.unroll,
        accum_dtype=schedule.jnp_accum_dtype,
    )
    tile_spec = pl.BlockSpec((1, 1, nt), lambda t, bmap: (t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[tile_spec, tile_spec, tile_spec],
        out_specs=pl.BlockSpec((1, 1, rpb), lambda t, bmap: (bmap[t], 0, 0)),
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, rpb), x.dtype),
        compiler_params=compiler_params("arbitrary"),  # carried accumulation
        interpret=resolve_interpret(interpret),
        name="csr_spmv",
    )(block_of_tile, tiles(data), xg, tiles(row_ids))
    return y.reshape(n_blocks * rpb)
