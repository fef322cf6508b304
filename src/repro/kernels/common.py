"""Shared kernel-schedule definition for the Pallas SpMV kernels.

``KernelSchedule`` is the TPU analogue of the paper's compile-time parameter
vector (DESIGN.md §2 table):

=====================  =========================  ============================
paper (CUDA)           ours (Pallas/TPU)          resource trade-off
=====================  =========================  ============================
thread-block size      ``rows_per_block``         work granularity / grid size
maxrregcount           ``unroll``                 VREG pressure vs ILP
L1/shared split        ``x_residency``            VMEM residency policy for X
(ILP per thread)       ``nnz_tile``               lane-aligned tile width
(precision)            ``accum_dtype``            MXU/VPU rate vs accuracy
(SM scheduling)        ``dimension_semantics``    grid-axis scheduling
=====================  =========================  ============================

All Pallas kernels accept a ``KernelSchedule`` and honour its tiling; the
schedule is what the Auto-SpMV compile-time mode predicts per input matrix.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # TPU vector lane quantum — the single source of truth
SUBLANE = 8  # TPU sublane quantum (sparse/formats re-exports both)
GATHER_SCOPE = "spmv.gather"  # named scope of the XLA gather of x in every SpMV


class InfeasibleConfig(ValueError):
    """Raised when a (format, schedule) pair cannot be materialized.

    The tuner's search space contains invalid points (exactly as on GPU,
    where e.g. a thread-block size can exceed resource limits); the dataset
    harness records them as failures rather than crashing. Format plugins
    raise this from their ``prepare``/``spmv`` entrypoints (see
    ``repro.sparse.registry.FormatSpec``).
    """

# Discrete choice sets — the tuning space the classifiers predict over.
ROWS_PER_BLOCK_CHOICES = (8, 16, 32, 64, 128, 256, 512)
NNZ_TILE_CHOICES = (128, 256, 512, 1024)
UNROLL_CHOICES = (1, 2, 4, 8)
ACCUM_DTYPE_CHOICES = ("float32", "bfloat16")
X_RESIDENCY_CHOICES = ("vmem", "stream")
DIMENSION_SEMANTICS_CHOICES = ("parallel", "arbitrary")

# TPU v5e VMEM per core (bytes) — the hard budget the schedule must respect.
# Every kernel passes it to Mosaic as ``vmem_limit_bytes`` (the compiler's
# own scoped default is smaller), so the footprint model's feasibility check
# and the compiler enforce the same limit.
VMEM_BYTES = 128 * 1024 * 1024 // 2  # 64 MiB


@functools.cache
def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode: only on the CPU backend.

    Resolved once, on first kernel call (never at import), so every kernel
    on a TPU host is compiled by Mosaic and nothing above ``kernels/`` can
    ask for the interpreter."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """A kernel wrapper's explicit override, else ``default_interpret()``."""
    return default_interpret() if interpret is None else bool(interpret)


def row_sums(p: jax.Array, unroll: int) -> jax.Array:
    """Kernel-body lane reduction of a ``(rows, width)`` tile into a
    lane-dense ``(1, rows)`` row, as ``unroll`` independent partial sums."""
    step = p.shape[1] // unroll
    parts = [
        jnp.sum(p[:, k * step : (k + 1) * step], axis=1, keepdims=True)
        for k in range(unroll)
    ]
    return functools.reduce(jnp.add, parts).T


def first_of_run(ids_ref, t):
    """Kernel-body test: is grid step ``t`` the first of its run in the
    sorted scalar-prefetched id map ``ids_ref`` (step -> output block)?"""
    return (t == 0) | (ids_ref[t] != ids_ref[jnp.maximum(t - 1, 0)])


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic compiler parameters shared by every kernel."""
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics, vmem_limit_bytes=VMEM_BYTES
    )


@dataclass(frozen=True)
class KernelSchedule:
    rows_per_block: int = 64
    nnz_tile: int = LANE
    unroll: int = 1
    accum_dtype: str = "float32"
    x_residency: str = "vmem"
    dimension_semantics: str = "arbitrary"

    def __post_init__(self):
        if self.rows_per_block % SUBLANE:
            raise ValueError(f"rows_per_block must be a multiple of {SUBLANE}")
        if self.nnz_tile % LANE:
            raise ValueError(f"nnz_tile must be a multiple of {LANE}")
        if self.nnz_tile % self.unroll:
            raise ValueError("unroll must divide nnz_tile")
        if self.accum_dtype not in ACCUM_DTYPE_CHOICES:
            raise ValueError(f"accum_dtype must be one of {ACCUM_DTYPE_CHOICES}")
        if self.x_residency not in X_RESIDENCY_CHOICES:
            raise ValueError(f"x_residency must be one of {X_RESIDENCY_CHOICES}")

    @property
    def jnp_accum_dtype(self):
        return jnp.dtype(self.accum_dtype)

    def replace(self, **kw) -> "KernelSchedule":
        return dataclasses.replace(self, **kw)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


DEFAULT_SCHEDULE = KernelSchedule()

def ceil_to(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


def pad_axis(a: np.ndarray, axis: int, to: int, fill=0) -> np.ndarray:
    """Pad ``a`` along ``axis`` up to length ``to`` with ``fill``."""
    cur = a.shape[axis]
    if cur >= to:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, to - cur)
    return np.pad(a, widths, constant_values=fill)
