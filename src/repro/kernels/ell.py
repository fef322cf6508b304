"""Pallas TPU kernel: ELL SpMV.

Mosaic cannot gather single elements of a vector inside a kernel, so the
X gather runs in XLA just before the launch: ``xg = x[cols]`` is a plane of
the same shape as the value plane. Grid ``(row_blocks, width_tiles)``; each
step loads ``(rows_per_block, nnz_tile)`` tiles of the value plane and of
``xg``, multiplies them, reduces along the lanes, and accumulates the row
sums into a lane-dense ``(1, rows_per_block)`` output block (revisited
across the width axis, so that axis is 'arbitrary'). ``unroll`` splits the
lane reduction into independent partial sums — the VREG-pressure knob
standing in for maxrregcount.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import (
    GATHER_SCOPE,
    KernelSchedule,
    compiler_params,
    resolve_interpret,
    row_sums,
)


def _ell_kernel(d_ref, xg_ref, y_ref, *, unroll: int, accum_dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    p = d_ref[...].astype(accum_dtype) * xg_ref[...].astype(accum_dtype)
    y_ref[...] += row_sums(p, unroll).reshape(y_ref.shape).astype(y_ref.dtype)


def ell_spmv_pallas(
    data: jax.Array,
    cols: jax.Array,
    x: jax.Array,
    schedule: KernelSchedule,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMV over padded ELL planes. Shapes must already be tile-aligned:
    ``data/cols: (R, W)`` with ``R % rows_per_block == 0`` and
    ``W % nnz_tile == 0`` (``prepare`` performs the padding). Returns
    ``y: (R,)``.
    """
    R, W = data.shape
    rpb, nt = schedule.rows_per_block, schedule.nnz_tile
    if R % rpb or W % nt:
        raise ValueError(f"ELL planes ({R},{W}) not aligned to ({rpb},{nt})")
    with jax.named_scope(GATHER_SCOPE):
        xg = jnp.take(x, cols, axis=0)  # XLA gather: one extra (R, W) plane
    kernel = functools.partial(
        _ell_kernel, unroll=schedule.unroll, accum_dtype=schedule.jnp_accum_dtype
    )
    y = pl.pallas_call(
        kernel,
        grid=(R // rpb, W // nt),
        in_specs=[
            pl.BlockSpec((rpb, nt), lambda i, j: (i, j)),
            pl.BlockSpec((rpb, nt), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, rpb), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((R // rpb, 1, rpb), x.dtype),
        compiler_params=compiler_params(schedule.dimension_semantics, "arbitrary"),
        interpret=resolve_interpret(interpret),
        name="ell_spmv",
    )(data, xg)
    return y.reshape(R)


def _ell_spmm_kernel(d_ref, xg_ref, y_ref, *, accum_dtype):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    d = d_ref[...].astype(accum_dtype)  # (rpb, nt)
    xg = xg_ref[...].astype(accum_dtype)  # (rpb, nt, k)
    y_ref[...] += jnp.sum(d[:, :, None] * xg, axis=1).astype(y_ref.dtype)


def ell_spmm_pallas(
    data: jax.Array,
    cols: jax.Array,
    X: jax.Array,
    schedule: KernelSchedule,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """ELL SpMM (dense RHS ``X: (n_cols, k)``) — the MoE-dispatch shape.
    The rows of X are gathered by XLA into an ``(R, W, k)`` operand."""
    R, W = data.shape
    rpb, nt = schedule.rows_per_block, schedule.nnz_tile
    if R % rpb or W % nt:
        raise ValueError(f"ELL planes ({R},{W}) not aligned to ({rpb},{nt})")
    k = X.shape[1]
    xg = jnp.take(X, cols, axis=0)  # (R, W, k)
    kernel = functools.partial(_ell_spmm_kernel, accum_dtype=schedule.jnp_accum_dtype)
    return pl.pallas_call(
        kernel,
        grid=(R // rpb, W // nt),
        in_specs=[
            pl.BlockSpec((rpb, nt), lambda i, j: (i, j)),
            pl.BlockSpec((rpb, nt, k), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((rpb, k), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, k), X.dtype),
        compiler_params=compiler_params(schedule.dimension_semantics, "arbitrary"),
        interpret=resolve_interpret(interpret),
        name="ell_spmm",
    )(data, xg)
