"""Pallas TPU kernel: CSR SpMV (row-block segmented reduction).

GPU scalar/vector-CSR does not map onto the TPU's 8x128 vector unit, so the
CSR kernel is re-thought (DESIGN.md §2). ``prepare`` pads the nonzero
stream of every block of ``rows_per_block`` rows to a whole number of
``nnz_tile`` tiles (explicit zeros in the block's last row, at least one
tile per block), so each tile belongs to exactly one row block. The grid
walks the tiles in row order:

* X lives in VMEM for the whole call, zero-padded to ``(R, 128)`` (R =
  ceil(n_cols / 128)); each tile's column ids arrive as a ``(nnz_tile/128,
  128)`` plane and the kernel gathers X from VMEM in two levels: for each of
  the R rows of X, a lane gather by ``col & 127``, kept where ``col >> 7``
  names that row (``_vmem_gather``). Where X is not float32, or R passes
  what that walk can afford (``x_gather``), XLA gathers X before the launch
  into a plane shaped like the values instead;
* a tile -> row-block map (each tile's first row id over ``rows_per_block``,
  scalar-prefetched) drives the output index map, so consecutive tiles of
  one block accumulate into the same lane-dense ``(1, rows_per_block)``
  output block;
* inside a tile, the products are reduced per row by a one-hot mask of the
  row ids against the block's rows — a sorted segmented reduction in
  place of a scatter-add.

Values and row ids are stored as ``(n_tiles, 1, nnz_tile)`` views so every
block's last two dims equal the array's, as Mosaic's (8, 128) tiling rule
requires. The price of CSR's no-padding storage is the masked reduction —
exactly the "CSR is hostile to wide SIMD" effect the paper observes on GPU
(finding 5), now in TPU form.
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
    LANE,
    SUBLANE,
    KernelSchedule,
    ceil_to,
    compiler_params,
    first_of_run,
    resolve_interpret,
    row_sums,
)

# Rows of X the in-kernel gather may walk per full (8, 128) vreg of column
# ids. The walk costs ~3.1 ns per row of X for every tile of up to 1,024
# entries, on top of ~0.41 us a tile, so its cost per stream entry grows
# with R, while XLA's gather costs a fixed ~7.6 ns per entry at 1,024-wide
# tiles and ~10 ns at 128-wide ones (TPU v5e; ``benchmarks/csr_gather.py``).
# The two meet near R = 2,400 at 1,024 and R = 290 at 128; the bound, in
# whole steps of the walk, stays under both.
VMEM_GATHER_MAX_ROWS = 2304
# Rows of X per step of the walk, unrolled in the step: Mosaic runs a loop's
# steps one after another, so a one-row step waits out each lane permute.
# On a TPU v5e a row costs ~87 ns at 1 row a step, 4.4 ns at 32, 3.1 at 64
# and 2.5 at 128; 64 keeps the unrolled step, and its compile, moderate.
WALK_ROWS = 64
_LANE_BITS = LANE.bit_length() - 1
_LANE_GATHER = lax.GatherDimensionNumbers(
    offset_dims=(),
    collapsed_slice_dims=(1,),
    start_index_map=(1,),
    operand_batching_dims=(0,),
    start_indices_batching_dims=(0,),
)


def x_rows(n_cols: int) -> int:
    """Rows of 128 that hold an ``x`` of ``n_cols`` in VMEM: whole steps."""
    return ceil_to(-(-n_cols // LANE), WALK_ROWS)


def x_gather(n_cols: int, x_dtype, nnz_tile: int) -> str:
    """Where the CSR kernel gathers an ``x`` of ``n_cols`` entries:
    ``"vmem"`` (inside the kernel) or ``"xla"`` (before the launch).

    A tile narrower than 1,024 entries fills only ``nnz_tile / 128`` of a
    vreg's 8 sublanes but pays the whole vreg per row of X, so its bound
    shrinks in proportion. A bf16 X would be packed 16 x 128 per vreg; only
    float32 is gathered in the kernel, never rounded to fit."""
    filled = min(nnz_tile // LANE, SUBLANE)
    fits = x_rows(n_cols) * SUBLANE <= VMEM_GATHER_MAX_ROWS * filled
    return "vmem" if jnp.dtype(x_dtype) == jnp.float32 and fits else "xla"


def _vmem_gather(cols: jax.Array, x_ref) -> jax.Array:
    """``x[cols]`` for a ``(k, 128)`` plane of column ids, from X held as
    ``(R, 128)`` in VMEM; returns the ``(1, k * 128)`` row of the tile.

    Mosaic gathers lanes within one vreg only, so each row of X in turn is
    broadcast over the plane, lane-gathered by ``col & 127`` and kept where
    ``col >> 7`` names it. A pure selection: every entry is one value of X.
    A one-row plane is widened to a full vreg, which Mosaic needs to gather,
    and cut back."""
    k = cols.shape[0]
    shape = (SUBLANE, LANE) if k == 1 else cols.shape
    hi = jnp.broadcast_to(cols >> _LANE_BITS, shape)
    lo = jnp.broadcast_to(cols & (LANE - 1), shape)[..., None]

    def step(i, xg):
        base = pl.multiple_of(i * WALK_ROWS, WALK_ROWS)
        rows = x_ref[pl.ds(base, WALK_ROWS), :]
        hi_in_step = hi - base
        for r in range(WALK_ROWS):
            src = jnp.broadcast_to(rows[r : r + 1], shape)
            got = lax.gather(
                src, lo, _LANE_GATHER, (1, 1),
                mode=lax.GatherScatterMode.PROMISE_IN_BOUNDS,
            )
            xg = jnp.where(hi_in_step == r, got, xg)
        return xg

    steps = x_ref.shape[0] // WALK_ROWS
    xg = lax.fori_loop(0, steps, step, jnp.zeros(shape, x_ref.dtype))
    return xg[:k].reshape(1, k * LANE)


def _csr_kernel(
    bmap_ref, d_ref, v_ref, r_ref, *refs, rpb: int, unroll: int, accum_dtype
):
    """``v_ref`` holds the tile's X values, gathered by XLA, or, where an
    X ref follows ``r_ref``, its column ids, gathered here from that X."""
    *x_ref, y_ref = refs
    t = pl.program_id(0)

    @pl.when(first_of_run(bmap_ref, t))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    xg = _vmem_gather(v_ref[0], x_ref[0]) if x_ref else v_ref[0]  # (1, nt)
    p = d_ref[0].astype(accum_dtype) * xg.astype(accum_dtype)
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
    gather: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMV over a row-block-aligned CSR stream.

    ``data/indices/row_ids: (nnz_pad,)`` laid out by ``prepare`` for
    ``tiling = (rows_per_block, nnz_tile)``: every tile lies inside one row
    block and every row block has at least one tile. The schedule supplies
    the numerics (``accum_dtype``, ``unroll``). ``gather`` overrides
    ``x_gather``'s choice of where X is gathered (tests and kernel timings
    only). Returns ``y: (n_row_blocks * rows_per_block,)``.
    """
    rpb, nt = tiling
    (nnz_pad,) = data.shape
    if nnz_pad % nt or nt % schedule.unroll:
        raise ValueError(f"CSR stream {nnz_pad} not aligned to nnz_tile {nt}")
    n_tiles = nnz_pad // nt
    n_blocks = -(-n_rows // rpb)
    tiles = lambda a: a.reshape(n_tiles, 1, nt)  # noqa: E731
    block_of_tile = row_ids[::nt] // rpb
    tile_spec = pl.BlockSpec((1, 1, nt), lambda t, bmap: (t, 0, 0))
    if (gather or x_gather(x.shape[0], x.dtype, nt)) == "vmem":
        rows = x_rows(x.shape[0])
        x_held = jnp.pad(x, (0, rows * LANE - x.shape[0])).reshape(rows, LANE)
        planes = (indices.reshape(n_tiles, nt // LANE, LANE), x_held)
        plane_specs = [
            pl.BlockSpec((1, nt // LANE, LANE), lambda t, bmap: (t, 0, 0)),
            pl.BlockSpec((rows, LANE), lambda t, bmap: (0, 0)),  # fetched once
        ]
    else:
        with jax.named_scope(GATHER_SCOPE):
            planes = (jnp.take(x, tiles(indices), axis=0),)  # XLA gather
        plane_specs = [tile_spec]
    kernel = functools.partial(
        _csr_kernel,
        rpb=rpb,
        unroll=schedule.unroll,
        accum_dtype=schedule.jnp_accum_dtype,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[tile_spec, plane_specs[0], tile_spec, *plane_specs[1:]],
        out_specs=pl.BlockSpec((1, 1, rpb), lambda t, bmap: (bmap[t], 0, 0)),
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, rpb), x.dtype),
        compiler_params=compiler_params("arbitrary"),  # carried accumulation
        interpret=resolve_interpret(interpret),
        name="csr_spmv",
    )(block_of_tile, tiles(data), planes[0], tiles(row_ids), *planes[1:])
    return y.reshape(n_blocks * rpb)
