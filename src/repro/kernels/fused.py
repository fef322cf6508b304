"""Pallas TPU kernel: fused single-launch partitioned SpMV.

PR 5's partitioned executor runs one Pallas launch per row block and
concatenates the outputs on the host — the per-launch fixed cost (trace,
grid setup, dispatch) times the block count is exactly the composite-format
overhead the SpMV survey flags for hybrid formats. This module fuses the
whole heterogeneous composite into ONE launch, the way merge-path/one-pass
composite kernels do on GPU:

* every block's *prepared* container (CSR / ELL / BELL / SELL / plugin) is
  lowered host-side to a flat ``(values, cols, global row ids)`` nonzero
  stream — the element ORDER stays format-specific (CSR row-major, SELL
  column-major slices, BELL block panels), so the chosen format still
  determines the memory-access pattern, while padding slots (stored zeros)
  are dropped so work assignment is nnz-balanced;
* the streams are padded to one lane-aligned tile quantum (sized from the
  TOTAL work, ``kernels.common.fused_nnz_tile``) and concatenated, and a
  prefix-sum **work descriptor** maps each program id to its (block, tile)
  work item; the descriptor rides in scalar-prefetch SMEM and drives the
  BlockSpec index maps;
* each program scatter-accumulates its tile straight into the one
  VMEM-resident ``(n_rows + 1,)`` output vector (spill slot last) — every
  program writes its y shard in place, no ``jnp.concatenate``, no
  per-block dispatch.

The kernel gathers single elements of X and scatter-adds by unsorted row
id inside the kernel; Mosaic lowers neither, so it runs only in interpret
mode and raises ``NotImplementedError`` on a TPU backend.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    KernelSchedule,
    ceil_to,
    compiler_params,
    fused_nnz_tile,
    resolve_interpret,
)
from repro.sparse.formats import BELL, CSR, ELL, SELL


# ---------------------------------------------------------------------------
# Host-side lowering: prepared container -> flat (values, cols, rows) stream
# ---------------------------------------------------------------------------


def _flatten_csr(mat: CSR) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    return (
        np.asarray(mat.data),
        np.asarray(mat.indices).astype(np.int32),
        np.asarray(mat.row_ids).astype(np.int32),
    )


def _flatten_ell(mat: ELL) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    data = np.asarray(mat.data)  # (R_pad, width), row-major
    width = data.shape[1]
    rows = np.repeat(np.arange(data.shape[0], dtype=np.int32), width)
    return data.ravel(), np.asarray(mat.cols).astype(np.int32).ravel(), rows


def _flatten_bell(mat: BELL) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    data = np.asarray(mat.data)  # (nbr, max_blocks, br, bc), panel order
    nbr, mb, br, bc = data.shape
    rows = (
        np.arange(nbr, dtype=np.int32)[:, None, None, None] * br
        + np.arange(br, dtype=np.int32)[None, None, :, None]
    )
    cols = (
        np.asarray(mat.block_cols).astype(np.int32)[:, :, None, None] * bc
        + np.arange(bc, dtype=np.int32)[None, None, None, :]
    )
    rows = np.broadcast_to(rows, data.shape).ravel()
    cols = np.broadcast_to(cols, data.shape).ravel()
    return data.ravel(), cols, rows


def _flatten_sell(mat: SELL) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    # column-major slice planes; padding row_ids (== n_rows) carry value 0
    # and are dropped by the caller's nonzero filter like any padding slot
    return (
        np.asarray(mat.data).ravel(),
        np.asarray(mat.cols).astype(np.int32).ravel(),
        np.asarray(mat.row_ids).astype(np.int32).ravel(),
    )


def flatten_block(
    mat, row_start: int
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Lower one block's prepared container to its flat nonzero stream.

    Returns ``(values, cols, rows)`` with rows in GLOBAL coordinates
    (``row_start`` added). Padding slots — stored zeros, whatever layout the
    format keeps them in — are filtered out, so the stream length is the
    block's nnz and fused work assignment is nnz-balanced. Plugin containers
    without a dedicated lowering densify through their registered
    ``to_dense`` and flatten as COO.
    """
    if isinstance(mat, CSR):
        data, cols, rows = _flatten_csr(mat)
    elif isinstance(mat, ELL):
        data, cols, rows = _flatten_ell(mat)
    elif isinstance(mat, BELL):
        data, cols, rows = _flatten_bell(mat)
    elif isinstance(mat, SELL):
        data, cols, rows = _flatten_sell(mat)
    else:
        from repro.sparse.registry import spec_for

        dense = np.asarray(spec_for(mat).to_dense(mat))
        rows, cols = np.nonzero(dense)
        data = dense[rows, cols]
        rows, cols = rows.astype(np.int32), cols.astype(np.int32)
    keep = data != 0
    return (
        np.ascontiguousarray(data[keep]),
        np.ascontiguousarray(cols[keep]),
        np.ascontiguousarray(rows[keep] + np.int32(row_start)),
    )


# ---------------------------------------------------------------------------
# The single-launch kernel (flat-tile scatter-add + work descriptor)
# ---------------------------------------------------------------------------


def _fused_kernel(tmap_ref, d_ref, c_ref, r_ref, x_ref, y_ref, *, unroll, accum_dtype):
    del tmap_ref  # consumed by the index maps
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    xv = x_ref[...]
    nt = d_ref.shape[0]
    step = nt // unroll
    y = y_ref[...].astype(accum_dtype)
    for k in range(unroll):
        sl = slice(k * step, (k + 1) * step)
        prods = (d_ref[sl].astype(accum_dtype)) * jnp.take(xv, c_ref[sl]).astype(
            accum_dtype
        )
        y = y.at[r_ref[sl]].add(prods)
    y_ref[...] = y.astype(y_ref.dtype)


def fused_spmv_pallas(
    data: jax.Array,
    cols: jax.Array,
    rows: jax.Array,
    tile_map: jax.Array,
    x: jax.Array,
    n_rows: int,
    tile: int,
    *,
    unroll: int = 1,
    accum_dtype="float32",
    interpret: bool | None = None,
) -> jax.Array:
    """One launch over the fused composite stream.

    ``data/cols/rows: (n_tiles * tile,)``; padding entries carry value 0,
    col 0, row ``n_rows`` (the spill slot). ``tile_map: (n_tiles,)`` is the
    prefix-sum work descriptor: program ``p`` processes flat tile
    ``tile_map[p]``. Returns ``y: (n_rows + 1,)`` (spill slot last).
    """
    if not resolve_interpret(interpret):
        raise NotImplementedError(
            "fused_partitioned_spmv does not lower on TPU (in-kernel gather and "
            "unsorted scatter-add); serve partitioned plans with fused=False"
        )
    n_tiles = int(tile_map.shape[0])
    if data.shape[0] != n_tiles * tile:
        raise ValueError(
            f"stream length {data.shape[0]} != n_tiles*tile {n_tiles * tile}"
        )
    kernel = functools.partial(
        _fused_kernel, unroll=unroll, accum_dtype=jnp.dtype(accum_dtype)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i, tmap: (tmap[i],)),
            pl.BlockSpec((tile,), lambda i, tmap: (tmap[i],)),
            pl.BlockSpec((tile,), lambda i, tmap: (tmap[i],)),
            pl.BlockSpec(x.shape, lambda i, tmap: (0,)),
        ],
        # the whole output vector stays VMEM-resident across the sequential
        # grid: every program writes its y shard in place
        out_specs=pl.BlockSpec((n_rows + 1,), lambda i, tmap: (0,)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows + 1,), x.dtype),
        compiler_params=compiler_params("arbitrary"),  # carried accumulation
        interpret=True,
        name="fused_partitioned_spmv",
    )(tile_map, data, cols, rows, x)


# ---------------------------------------------------------------------------
# Lowering a CompositePlan -> FusedSpmv
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FusedSpmv:
    """A composite plan lowered to one launch-ready fused stream."""

    data: jax.Array  # (n_tiles * tile,)
    cols: jax.Array  # (n_tiles * tile,) int32
    rows: jax.Array  # (n_tiles * tile,) int32, == n_rows on padding
    tile_map: jax.Array  # (n_tiles,) int32 work descriptor
    block_of_tile: tuple[int, ...]  # owning block index per work item
    formats: tuple[str, ...]  # per-block formats the streams were lowered from
    n_rows: int
    tile: int
    unroll: int
    accum_dtype: str

    @property
    def n_tiles(self) -> int:
        return int(self.tile_map.shape[0])

    def _jitted(self):
        # one launch -> one executable: the whole composite is a single
        # pallas_call, so the traced computation is cached per FusedSpmv and
        # repeat calls skip retracing entirely (the per-call fixed cost the
        # sequential per-block dispatch keeps paying k times)
        fn = self.__dict__.get("_jit_call")
        if fn is None:
            fn = jax.jit(
                functools.partial(
                    fused_spmv_pallas,
                    n_rows=self.n_rows,
                    tile=self.tile,
                    unroll=self.unroll,
                    accum_dtype=self.accum_dtype,
                )
            )
            object.__setattr__(self, "_jit_call", fn)
        return fn

    def __call__(self, x: jax.Array) -> jax.Array:
        y = self._jitted()(
            self.data, self.cols, self.rows, self.tile_map, jnp.asarray(x)
        )
        return y[: self.n_rows]


def fused_schedule_params(schedules: list[KernelSchedule], tile: int) -> tuple[int, str]:
    """(unroll, accum_dtype) for the fused stream: the most conservative of
    the per-block schedules — smallest unroll that divides the tile, and
    float32 accumulation unless EVERY block asked for bfloat16."""
    unroll = min((s.unroll for s in schedules), default=1)
    while tile % unroll:
        unroll //= 2
    accum = (
        "bfloat16"
        if schedules and all(s.accum_dtype == "bfloat16" for s in schedules)
        else "float32"
    )
    return max(unroll, 1), accum


def lower_fused(dense: np.ndarray, plan) -> FusedSpmv:
    """Lower every block of a ``CompositePlan`` into one fused stream.

    Each block's dense rows are prepared in the block's chosen format (the
    same conversion the sequential executor performs), flattened with
    ``flatten_block``, padded to the common tile quantum (value 0 / col 0 /
    row ``n_rows`` spill entries), and concatenated. The work descriptor is
    built from the prefix sums of the per-block tile counts.
    """
    from repro.kernels.ops import prepare  # lazy: ops imports this module

    dense = np.asarray(dense)
    n_rows = plan.partition.n_rows
    streams = []
    for bp in plan.blocks:
        block = dense[bp.block.row_start : bp.block.row_end]
        mat = prepare(block, bp.fmt, bp.schedule)
        streams.append(flatten_block(mat, bp.block.row_start))

    total = sum(d.size for d, _, _ in streams)
    tile = fused_nnz_tile(max(total, 1))
    val_dtype = streams[0][0].dtype if streams else np.float32

    datas, colss, rowss = [], [], []
    block_tiles: list[int] = []
    for d, c, r in streams:
        padded = ceil_to(d.size, tile)  # empty block -> zero tiles
        datas.append(np.pad(d, (0, padded - d.size)))
        colss.append(np.pad(c, (0, padded - c.size)))
        rowss.append(np.pad(r, (0, padded - r.size), constant_values=n_rows))
        block_tiles.append(padded // tile)
    if sum(block_tiles) == 0:  # fully empty matrix: one all-spill tile
        datas.append(np.zeros(tile, dtype=val_dtype))
        colss.append(np.zeros(tile, dtype=np.int32))
        rowss.append(np.full(tile, n_rows, dtype=np.int32))
        block_tiles[0] = 1

    # prefix-sum work descriptor: program id -> (block, tile) work item,
    # laid out as the flat tile index block_offset[b] + local tile
    offsets = np.concatenate([[0], np.cumsum(block_tiles)]).astype(np.int32)
    tile_map = np.concatenate(
        [offsets[b] + np.arange(k, dtype=np.int32) for b, k in enumerate(block_tiles)]
    )
    block_of_tile = tuple(
        int(b) for b, k in enumerate(block_tiles) for _ in range(k)
    )

    unroll, accum = fused_schedule_params([bp.schedule for bp in plan.blocks], tile)
    return FusedSpmv(
        data=jnp.asarray(np.concatenate(datas)),
        cols=jnp.asarray(np.concatenate(colss).astype(np.int32)),
        rows=jnp.asarray(np.concatenate(rowss).astype(np.int32)),
        tile_map=jnp.asarray(tile_map),
        block_of_tile=block_of_tile,
        formats=tuple(bp.fmt for bp in plan.blocks),
        n_rows=n_rows,
        tile=tile,
        unroll=unroll,
        accum_dtype=accum,
    )
