"""Pallas TPU kernel: CSR SpMV (row-block segmented reduction).

GPU scalar/vector-CSR does not map onto the TPU's 8x128 vector unit, so the
CSR kernel is re-thought (DESIGN.md §2). ``prepare`` pads the nonzero
stream of every block of ``rows_per_block`` rows to a whole number of
``nnz_tile`` tiles (explicit zeros in the block's last row, at least one
tile per block), so each tile belongs to exactly one row block. The grid
walks the tiles in row order:

* X lives in VMEM for the whole call, zero-padded to ``(R, 128)`` (R =
  ceil(n_cols / 128) in whole steps of the walk); each tile's column ids
  arrive as a ``(nnz_tile/128, 128)`` plane and the kernel gathers X from
  VMEM in two levels: for each row of X in the tile's window, a lane gather
  by ``col & 127``, kept where ``col >> 7`` names that row
  (``_vmem_gather``). The window is the stream's own (``x_window``, computed
  once in conversion): each tile walks the same whole number of steps from
  its own scalar-prefetched first row, so a banded matrix walks the few rows
  its tiles reach and not all of X. Where X is not float32, or the walk
  passes what it can afford (``x_gather``), XLA gathers X before the launch
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
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    GATHER_SCOPE,
    LANE,
    SUBLANE,
    VMEM_BYTES,
    KernelSchedule,
    ceil_to,
    compiler_params,
    first_of_run,
    resolve_interpret,
    row_sums,
)

# Rows of X the in-kernel gather may walk per tile, for a tile that fills a
# full (8, 128) vreg of column ids. The walk costs ~3.1 ns per row of X for
# every tile of up to 1,024 entries, on top of ~0.41 us a tile, so its cost
# per stream entry grows with R, the rows walked, while XLA's gather costs a
# fixed ~7.6 ns per entry at 1,024-wide tiles and ~10 ns at 128-wide ones
# (TPU v5e; ``benchmarks/csr_gather.py``, whose window sweep reads the same
# ~3.2 ns a row from a windowed start). The two meet near R = 2,400 at 1,024
# and R = 290 at 128; the bound, in whole steps of the walk, stays under both.
VMEM_GATHER_MAX_ROWS = 2304
# Rows of X per step of the walk, unrolled in the step: Mosaic runs a loop's
# steps one after another, so a one-row step waits out each lane permute.
# On a TPU v5e a row costs ~87 ns at 1 row a step, 4.4 ns at 32, 3.1 at 64
# and 2.5 at 128; 64 keeps the unrolled step, and its compile, moderate.
WALK_ROWS = 64
# Bytes of a float32 X the kernel may hold in VMEM. The pipeline keeps two
# buffers of X's block, so X takes twice this: half of ``VMEM_BYTES``, which
# leaves the other half to the double-buffered tiles and the one-hot
# reduction's (rows_per_block, nnz_tile) temporaries, a few MiB at 512 x
# 1,024. 16 MiB is 32,768 rows of 128: n_cols up to ~4.2M.
X_VMEM_BYTES = VMEM_BYTES // 4
# Bytes of SMEM the scalar-prefetched int32 maps may take. A TPU v5e core
# has 1 MiB of SMEM, and Mosaic refuses a kernel whose prefetched operands
# pass it; a window's starts double the one map every call prefetches, so
# a window is kept for streams of up to 65,536 tiles, where both take half.
PREFETCH_SMEM_BYTES = 512 * 1024
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


def x_window(
    indices: np.ndarray, nnz_tile: int, n_cols: int
) -> tuple[np.ndarray | None, int]:
    """Each tile's window of X, from a tiled stream's column ids (host side).

    Returns the first row of 128 each tile's walk starts from, aligned down
    to a sublane so the walk's dynamic slice stays aligned, as ``(n_tiles,)``
    int32; and the rows every tile walks: the widest tile's span in whole
    steps of the walk. A start is pulled back where its walk would run past
    ``x_rows(n_cols)``. Where the walk covers all of X, or the starts and
    the tile -> row-block map would not both fit ``PREFETCH_SMEM_BYTES``,
    there is no window: the starts are None and every tile walks all of X.
    Padding slots must hold a column of their own tile's span, or they
    widen its window."""
    tiles = np.asarray(indices).reshape(-1, nnz_tile)
    first = tiles.min(axis=1) >> _LANE_BITS
    start = first - first % SUBLANE
    span = (tiles.max(axis=1) >> _LANE_BITS) - start + 1
    rows = x_rows(n_cols)
    walk = ceil_to(int(span.max(initial=1)), WALK_ROWS)
    if walk >= rows or 2 * tiles.shape[0] * 4 > PREFETCH_SMEM_BYTES:
        return None, rows
    return np.minimum(start, rows - walk).astype(np.int32), walk


def x_gather(n_cols: int, x_dtype, nnz_tile: int, walk: int | None = None) -> str:
    """Where the CSR kernel gathers an ``x`` of ``n_cols`` entries:
    ``"vmem"`` (inside the kernel) or ``"xla"`` (before the launch).

    The in-kernel gather costs each tile a step per ``WALK_ROWS`` rows it
    walks: ``walk`` rows of its window (``x_window``), or all of X without
    one. A tile narrower than 1,024 entries fills only ``nnz_tile / 128`` of
    a vreg's 8 sublanes but pays the whole vreg per row walked, so its bound
    shrinks in proportion. X itself must fit ``X_VMEM_BYTES``. A bf16 X
    would be packed 16 x 128 per vreg; only float32 is gathered in the
    kernel, never rounded to fit."""
    walked = x_rows(n_cols) if walk is None else walk
    filled = min(nnz_tile // LANE, SUBLANE)
    fits = (
        walked * SUBLANE <= VMEM_GATHER_MAX_ROWS * filled
        and x_rows(n_cols) * LANE * 4 <= X_VMEM_BYTES
    )
    return "vmem" if jnp.dtype(x_dtype) == jnp.float32 and fits else "xla"


def _vmem_gather(cols: jax.Array, x_ref, start, walk: int) -> jax.Array:
    """``x[cols]`` for a ``(k, 128)`` plane of column ids, from X held as
    ``(R, 128)`` in VMEM; returns the ``(1, k * 128)`` row of the tile.

    Mosaic gathers lanes within one vreg only, so each of the ``walk`` rows
    of X from row ``start`` (row 0 where it is None) in turn is broadcast
    over the plane, lane-gathered by ``col & 127`` and kept where ``col >>
    7`` names it. A pure selection: every entry is one value of X, from the
    tile's window. A one-row plane is widened to a full vreg, which Mosaic
    needs to gather, and cut back."""
    k = cols.shape[0]
    shape = (SUBLANE, LANE) if k == 1 else cols.shape
    hi = jnp.broadcast_to(cols >> _LANE_BITS, shape)
    lo = jnp.broadcast_to(cols & (LANE - 1), shape)[..., None]

    def step(i, xg):
        base = pl.multiple_of(i * WALK_ROWS, WALK_ROWS)
        if start is not None:
            base = pl.multiple_of(start + base, SUBLANE)
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

    xg = lax.fori_loop(0, walk // WALK_ROWS, step, jnp.zeros(shape, x_ref.dtype))
    return xg[:k].reshape(1, k * LANE)


def _csr_kernel(
    bmap_ref, *refs, rpb: int, unroll: int, accum_dtype, walk: int | None
):
    """Where ``walk`` is None, ``v_ref`` holds the tile's X values, gathered
    by XLA. Otherwise ``v_ref`` holds the tile's column ids and an X ref
    follows ``r_ref``: the kernel gathers from ``walk`` rows of that X, from
    the tile's start where a window-start ref follows ``bmap_ref``, else
    from row 0."""
    if walk is None:
        d_ref, v_ref, r_ref, y_ref = refs
    else:
        *start_ref, d_ref, v_ref, r_ref, x_ref, y_ref = refs
    t = pl.program_id(0)

    @pl.when(first_of_run(bmap_ref, t))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    if walk is None:
        xg = v_ref[0]
    else:
        start = start_ref[0][t] if start_ref else None
        xg = _vmem_gather(v_ref[0], x_ref, start, walk)  # (1, nt)
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
    x_start: jax.Array | None = None,
    x_walk: int | None = None,
    gather: str | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMV over a row-block-aligned CSR stream.

    ``data/indices/row_ids: (nnz_pad,)`` laid out by ``prepare`` for
    ``tiling = (rows_per_block, nnz_tile)``: every tile lies inside one row
    block and every row block has at least one tile. ``x_start`` and
    ``x_walk`` are the stream's window of X (``x_window``): tile ``t`` reads
    only rows ``x_start[t]`` to ``x_start[t] + x_walk`` of X; without a
    start every tile walks all of X. The schedule supplies the numerics
    (``accum_dtype``, ``unroll``). ``gather`` overrides ``x_gather``'s
    choice of where X is gathered (tests and kernel timings only). Returns
    ``y: (n_row_blocks * rows_per_block,)``.
    """
    rpb, nt = tiling
    (nnz_pad,) = data.shape
    if nnz_pad % nt or nt % schedule.unroll:
        raise ValueError(f"CSR stream {nnz_pad} not aligned to nnz_tile {nt}")
    if x_start is not None and not x_walk:
        raise ValueError("a window of X takes its walk, x_walk, with x_start")
    n_tiles = nnz_pad // nt
    n_blocks = -(-n_rows // rpb)
    tiles = lambda a: a.reshape(n_tiles, 1, nt)  # noqa: E731
    maps = (row_ids[::nt] // rpb,)  # tile -> row block
    tile_spec = pl.BlockSpec((1, 1, nt), lambda t, *_: (t, 0, 0))
    rows = x_rows(x.shape[0])
    walk = rows if x_start is None else x_walk
    if (gather or x_gather(x.shape[0], x.dtype, nt, walk)) == "vmem":
        if x_start is not None:
            maps += (x_start,)
        x_held = jnp.pad(x, (0, rows * LANE - x.shape[0])).reshape(rows, LANE)
        planes = (indices.reshape(n_tiles, nt // LANE, LANE), x_held)
        plane_specs = [
            pl.BlockSpec((1, nt // LANE, LANE), lambda t, *_: (t, 0, 0)),
            pl.BlockSpec((rows, LANE), lambda t, *_: (0, 0)),  # fetched once
        ]
    else:
        walk = None
        with jax.named_scope(GATHER_SCOPE):
            planes = (jnp.take(x, tiles(indices), axis=0),)  # XLA gather
        plane_specs = [tile_spec]
    kernel = functools.partial(
        _csr_kernel,
        rpb=rpb,
        unroll=schedule.unroll,
        accum_dtype=schedule.jnp_accum_dtype,
        walk=walk,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(maps),
        grid=(n_tiles,),
        in_specs=[tile_spec, plane_specs[0], tile_spec, *plane_specs[1:]],
        out_specs=pl.BlockSpec((1, 1, rpb), lambda t, bmap, *_: (bmap[t], 0, 0)),
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_blocks, 1, rpb), x.dtype),
        compiler_params=compiler_params("arbitrary"),  # carried accumulation
        interpret=resolve_interpret(interpret),
        name="csr_spmv",
    )(*maps, tiles(data), planes[0], tiles(row_ids), *planes[1:])
    return y.reshape(n_blocks * rpb)
