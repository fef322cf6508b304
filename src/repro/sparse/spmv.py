"""Pure-jnp SpMV per format — the numerical oracles for the Pallas kernels.

Every function computes ``y = A @ x`` for its format and matches the dense
product to float tolerance. These are also the measured implementations the
dataset harness times on CPU (paper §6.3 protocol) — they are written to be
jit-compiled once per (format, shape).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.sparse.formats import BELL, CSR, ELL, SELL, SparseFormat


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _csr_impl(data, indices, row_ids, x, *, n_rows):
    prods = data * x[indices]
    return jax.ops.segment_sum(prods, row_ids, num_segments=n_rows)


def spmv_csr(mat: CSR, x: jax.Array) -> jax.Array:
    """CSR SpMV via flat gather + segmented sum (TPU-idiomatic CSR)."""
    return _csr_impl(mat.data, mat.indices, mat.row_ids, x, n_rows=mat.shape[0])


@jax.jit
def _ell_impl(data, cols, x):
    return jnp.sum(data * x[cols], axis=1)


def spmv_ell(mat: ELL, x: jax.Array) -> jax.Array:
    return _ell_impl(mat.data, mat.cols, x)


@functools.partial(jax.jit, static_argnames=("bc", "n_rows"))
def _bell_impl(data, block_cols, x, *, bc, n_rows):
    n_cols_pad = ((x.shape[0] + bc - 1) // bc) * bc
    xp = jnp.zeros(n_cols_pad, x.dtype).at[: x.shape[0]].set(x)
    xseg = xp.reshape(-1, bc)[block_cols]  # (nbr, maxb, bc)
    y = jnp.einsum("ijrc,ijc->ir", data, xseg)  # block matvec on MXU shapes
    return y.reshape(-1)[:n_rows]


def spmv_bell(mat: BELL, x: jax.Array) -> jax.Array:
    return _bell_impl(mat.data, mat.block_cols, x, bc=mat.bc, n_rows=mat.shape[0])


@functools.partial(jax.jit, static_argnames=("n_rows",))
def _sell_impl(data, cols, row_ids, x, *, n_rows):
    prods = data * x[cols]
    # padding slots carry row_id == n_rows -> dropped by the extra segment
    return jax.ops.segment_sum(prods, row_ids, num_segments=n_rows + 1)[:n_rows]


def spmv_sell(mat: SELL, x: jax.Array) -> jax.Array:
    return _sell_impl(
        mat.data.reshape(-1),
        mat.cols.reshape(-1),
        mat.row_ids.reshape(-1),
        x,
        n_rows=mat.shape[0],
    )


_DISPATCH = {CSR: spmv_csr, ELL: spmv_ell, BELL: spmv_bell, SELL: spmv_sell}


def spmv(mat: SparseFormat, x: jax.Array) -> jax.Array:
    """Format-dispatching SpMV.

    Routed through the registry so an overwritten or plugin spec's
    ``reference`` is honored; the static table only serves containers the
    registry does not know (e.g. a seed format that was unregistered)."""
    from repro.sparse.registry import spec_for

    try:
        spec = spec_for(mat)
    except TypeError:
        fn = _DISPATCH.get(type(mat))
        if fn is None:
            raise
        return fn(mat, x)
    return spec.reference(mat, x)


@jax.jit
def _ell_spmm_impl(data, cols, X):
    # X: (n_cols, k). Gather rows of X per stored nonzero, contract width.
    Xg = X[cols]  # (n_rows, width, k)
    return jnp.einsum("rw,rwk->rk", data, Xg)


def spmm_ell(mat: ELL, X: jax.Array) -> jax.Array:
    """ELL SpMM (multi-vector SpMV) — the MoE-dispatch-shaped variant."""
    return _ell_spmm_impl(mat.data, mat.cols, X)
