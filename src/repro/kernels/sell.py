"""Pallas TPU kernel: SELL (sliced-ELL) SpMV over true ragged storage.

Slices are stored column-major (formats.py) as one ``(total / C, C)``
plane: row ``k`` of slice ``s``'s part holds the k-th stored nonzero of each
of its C rows. Width-tile ``j`` of slice ``s`` is therefore the
``(nnz_tile, C)`` block ``slice_ptr[s] / (nnz_tile * C) + j`` of that plane.

The grid walks the flat list of stored tiles, so raggedness costs no
masked grid steps: a tile -> slice map, built from ``slice_width`` just
before the launch and scalar-prefetched, drives the output index map, and
consecutive tiles of one slice accumulate into the same lane-dense
``(1, C)`` output block. X is gathered by XLA before the launch (Mosaic
cannot gather single elements of a vector inside a kernel). Every slice
stores at least one tile, so every output block is written.

This is the SELL-C-sigma -> TPU adaptation: storage stays ragged (the whole
point of SELL), while every DMA stays tile-aligned.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (
    GATHER_SCOPE,
    KernelSchedule,
    compiler_params,
    first_of_run,
    resolve_interpret,
)


def _sell_kernel(smap_ref, d_ref, xg_ref, y_ref, *, unroll: int, accum_dtype):
    t = pl.program_id(0)

    @pl.when(first_of_run(smap_ref, t))
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    p = d_ref[...].astype(accum_dtype) * xg_ref[...].astype(accum_dtype)  # (nt, C)
    step = p.shape[0] // unroll
    acc = functools.reduce(
        jnp.add,
        [
            jnp.sum(p[k * step : (k + 1) * step], axis=0, keepdims=True)
            for k in range(unroll)
        ],
    )
    y_ref[...] += acc.reshape(y_ref.shape).astype(y_ref.dtype)


def sell_spmv_pallas(
    data: jax.Array,
    cols: jax.Array,
    slice_width: jax.Array,
    x: jax.Array,
    schedule: KernelSchedule,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMV over SELL storage.

    ``data/cols: (total / C, C)`` column-major ragged slices whose widths
    ``slice_width: (n_slices,)`` are multiples of ``nnz_tile`` (``prepare``
    pads them so). Returns ``y: (n_slices * C,)``.
    """
    rows, C = data.shape
    nt = schedule.nnz_tile
    if rows % nt:
        raise ValueError(f"SELL storage rows {rows} not aligned to nnz_tile {nt}")
    n_slices = slice_width.shape[0]
    n_tiles = rows // nt
    slice_of_tile = jnp.repeat(
        jnp.arange(n_slices, dtype=jnp.int32),
        slice_width // nt,
        total_repeat_length=n_tiles,
    )
    with jax.named_scope(GATHER_SCOPE):
        xg = jnp.take(x, cols, axis=0)  # XLA gather: one extra storage plane
    kernel = functools.partial(
        _sell_kernel, unroll=schedule.unroll, accum_dtype=schedule.jnp_accum_dtype
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((nt, C), lambda t, smap: (t, 0)),
            pl.BlockSpec((nt, C), lambda t, smap: (t, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, C), lambda t, smap: (smap[t], 0, 0)),
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_slices, 1, C), x.dtype),
        compiler_params=compiler_params("arbitrary"),  # carried accumulation
        interpret=resolve_interpret(interpret),
        name="sell_spmv",
    )(slice_of_tile, data, xg)
    return y.reshape(n_slices * C)
