"""Pallas TPU kernels for the paper's compute hot-spot: the SpMV kernel
itself (CSR / ELL / BELL / SELL), schedule-parameterized by the Auto-SpMV
compile-time mode. ``ops.py`` is the jit-facing wrapper; ``ref.py`` holds the
pure-jnp oracles."""

from repro.kernels.common import (
    DEFAULT_SCHEDULE,
    KernelSchedule,
    ROWS_PER_BLOCK_CHOICES,
    NNZ_TILE_CHOICES,
    UNROLL_CHOICES,
    ACCUM_DTYPE_CHOICES,
    X_RESIDENCY_CHOICES,
    InfeasibleConfig,
)
from repro.kernels.ops import (
    PreparedSpmspv,
    PreparedSpmv,
    clear_kernel_memo,
    compile_spmspv,
    compile_spmv,
    kernel_memo_limit,
    kernel_memo_size,
    kernel_memo_stats,
    kernel_memoized,
    matrix_fingerprint,
    prepare,
    set_kernel_memo_limit,
    spmm_pallas,
    spmspv,
    spmv_pallas,
)

__all__ = [
    "DEFAULT_SCHEDULE",
    "KernelSchedule",
    "ROWS_PER_BLOCK_CHOICES",
    "NNZ_TILE_CHOICES",
    "UNROLL_CHOICES",
    "ACCUM_DTYPE_CHOICES",
    "X_RESIDENCY_CHOICES",
    "InfeasibleConfig",
    "PreparedSpmspv",
    "PreparedSpmv",
    "clear_kernel_memo",
    "compile_spmspv",
    "compile_spmv",
    "kernel_memo_limit",
    "kernel_memo_size",
    "kernel_memo_stats",
    "kernel_memoized",
    "matrix_fingerprint",
    "prepare",
    "set_kernel_memo_limit",
    "spmm_pallas",
    "spmspv",
    "spmv_pallas",
]
