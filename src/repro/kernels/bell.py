"""Pallas TPU kernel: BELL (blocked-ELL) SpMV with scalar-prefetch gather.

The TPU-native trick: the gather of X segments happens in the *pipeline*,
not the kernel body. ``block_cols`` is a scalar-prefetch operand, and the
BlockSpec index map of X reads it to DMA exactly the (1, bc) panel each
stored block needs. Each grid step is then a dense (1, bc) x (br, bc)^T
product on the MXU — the reason BELL blocks are 8..256 x 128 here instead
of the paper's GPU 2x2 (DESIGN.md §2).

X panels and the output carry a unit middle axis — ``(n_col_blocks, 1,
bc)`` and ``(n_block_rows, 1, br)`` — so every block's last two dims equal
the array's and satisfy Mosaic's (8, 128) tiling rule, and each block-row's
result is stored lane-dense.

BELL is also the only format whose X access is *streamed* rather than
VMEM-resident, i.e. the ``x_residency='stream'`` point of the tuning space.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import KernelSchedule, compiler_params, resolve_interpret


def block_matvec(xs: jax.Array, blk: jax.Array, accum_dtype) -> jax.Array:
    """``(1, bc) x (br, bc)^T -> (1, br)`` on the MXU.

    Operands are rounded to ``accum_dtype``; the MXU accumulates in float32
    (full precision for float32 operands) and the result is rounded back."""
    f32 = jnp.dtype(accum_dtype) == jnp.float32
    return lax.dot_general(
        xs.astype(accum_dtype),
        blk.astype(accum_dtype),
        (((1,), (1,)), ((), ())),
        precision=lax.Precision.HIGHEST if f32 else lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    ).astype(accum_dtype)


def x_panels(x: jax.Array, bc: int) -> jax.Array:
    """X zero-padded to a multiple of ``bc`` and cut into ``(n, 1, bc)``."""
    n_pad = -(-x.shape[0] // bc) * bc
    return jnp.pad(x, (0, n_pad - x.shape[0])).reshape(-1, 1, bc)


def _bell_kernel(bc_ref, d_ref, x_ref, y_ref, *, accum_dtype):
    del bc_ref  # consumed by the index maps
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    y = block_matvec(x_ref[0], d_ref[0, 0], accum_dtype)  # (1, br)
    y_ref[...] += y.reshape(y_ref.shape).astype(y_ref.dtype)


def bell_spmv_pallas(
    data: jax.Array,
    block_cols: jax.Array,
    x: jax.Array,
    schedule: KernelSchedule,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """SpMV over BELL storage.

    ``data: (nbr, mb, br, bc)``, ``block_cols: (nbr, mb)`` int32, ``x:
    (n_cols,)``. Returns ``y: (nbr * br,)`` (rows past ``n_rows`` are zero
    padding for the caller to drop).
    """
    nbr, mb, br, bc = data.shape
    kernel = functools.partial(_bell_kernel, accum_dtype=schedule.jnp_accum_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nbr, mb),
        in_specs=[
            pl.BlockSpec((1, 1, br, bc), lambda i, j, bcols: (i, j, 0, 0)),
            # the scalar-prefetch-driven gather: DMA the X panel this block needs
            pl.BlockSpec((1, 1, bc), lambda i, j, bcols: (bcols[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, br), lambda i, j, bcols: (i, 0, 0)),
    )
    y = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nbr, 1, br), x.dtype),
        compiler_params=compiler_params(schedule.dimension_semantics, "arbitrary"),
        interpret=resolve_interpret(interpret),
        name="bell_spmv",
    )(block_cols, data, x_panels(x, bc))
    return y.reshape(nbr * br)
