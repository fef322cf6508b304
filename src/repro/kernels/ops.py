"""Public SpMV kernel API: schedule-aware preparation + dispatch.

``prepare`` converts a dense matrix into the requested format with storage
geometry matched to a ``KernelSchedule`` (the compile-time parameters the
Auto-SpMV predictor emits), and ``spmv_pallas`` runs the matching Pallas
kernel as one jitted program per (format, storage shape, schedule). Both
are thin lookups into the pluggable format registry
(``repro.sparse.registry``): the per-format conversion, alignment padding,
feasibility checks, and kernel binding live on each ``FormatSpec``, so a
format registered at runtime is served here with no code change. Whether
the kernels are compiled by Mosaic or interpreted is decided once, in
``kernels.common.default_interpret``: interpret only on the CPU backend.

The registry import is deliberately lazy (inside the functions): this module
is imported by ``repro.kernels.__init__``, which the sparse substrate itself
imports for the tiling constants — a module-level registry import would
close that cycle during package initialization.
"""

from __future__ import annotations

import functools
import hashlib
from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Hashable

import jax
import numpy as np

from repro.kernels.common import (
    DEFAULT_SCHEDULE,
    InfeasibleConfig,  # noqa: F401  (canonical home moved to kernels.common)
    KernelSchedule,
)
from repro.obs.metrics import get_metrics
from repro.obs.trace import span as _span

# memo counters mirrored into the process metrics registry so the /metrics
# scrape sees kernel-compile economics without importing this module
_M_HITS = get_metrics().counter("spmv_kernel_memo_hits_total")
_M_COMPILES = get_metrics().counter("spmv_kernel_memo_compiles_total")
_M_EVICTIONS = get_metrics().counter("spmv_kernel_memo_evictions_total")


def __getattr__(name):
    if name == "MAX_STORAGE_BYTES":
        # deprecated alias: the live bound moved to the format registry;
        # resolve it there so the two names can never drift apart
        from repro.sparse.registry import MAX_STORAGE_BYTES

        return MAX_STORAGE_BYTES
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def prepare(
    matrix, fmt: str, schedule: KernelSchedule = DEFAULT_SCHEDULE
) -> Any:
    """Convert ``matrix`` (dense, or a ``HostCSR``) to ``fmt`` with
    schedule-aligned storage geometry."""
    from repro.sparse.host import HostCSR
    from repro.sparse.registry import get_format

    spec = get_format(fmt)
    if isinstance(matrix, HostCSR):
        if spec.prepare_csr is None:
            raise InfeasibleConfig(
                f"format {fmt!r} converts only from a dense matrix; "
                "a HostCSR input needs a format with prepare_csr"
            )
        return spec.prepare_csr(matrix, schedule)
    return spec.prepare(np.asarray(matrix), schedule)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _jitted_spmv(spmv, mat, x, schedule):
    # the registered ``spmv`` is a static argument, so re-registering a
    # format for the same container never replays a stale trace
    return spmv(mat, x, schedule)


def spmv_pallas(
    mat: Any, x: jax.Array, schedule: KernelSchedule = DEFAULT_SCHEDULE
) -> jax.Array:
    """Run the Pallas SpMV kernel matching ``type(mat)``; returns y: (n_rows,)."""
    from repro.sparse.registry import spec_for

    return _jitted_spmv(spec_for(mat).spmv, mat, x, schedule)


def spmm_pallas(
    mat: Any, X: jax.Array, schedule: KernelSchedule = DEFAULT_SCHEDULE
) -> jax.Array:
    """Multi-vector SpMV (ELL only — the MoE-dispatch shape)."""
    import jax.numpy as jnp

    from repro.kernels.ell import ell_spmm_pallas
    from repro.sparse.formats import ELL

    if not isinstance(mat, ELL):
        raise TypeError("spmm_pallas currently supports ELL")
    n_rows = mat.shape[0]
    return ell_spmm_pallas(mat.data, mat.cols, jnp.asarray(X), schedule)[:n_rows]


def spmspv(
    mat,
    active: np.ndarray,
    xvals: np.ndarray,
    schedule: KernelSchedule = DEFAULT_SCHEDULE,
) -> jax.Array:
    """Sparse-input-vector SpMV over a ``CscEll`` container.

    ``active`` holds the frontier's column indices and ``xvals`` the
    corresponding x values; work scales with the frontier's column nnz,
    not nnz(A). See ``repro.kernels.spmspv`` for the kernel design."""
    from repro.kernels.spmspv import CscEll, csc_spmspv

    if not isinstance(mat, CscEll):
        raise TypeError("spmspv expects a CscEll container (see prepare_spmspv)")
    return csc_spmspv(mat, active, xvals, schedule)


@dataclass(frozen=True)
class PreparedSpmv:
    """A (format, schedule)-specialized SpMV — what compile-time mode emits."""

    mat: Any  # a registered format container (CSR / ELL / BELL / SELL / plugin)
    schedule: KernelSchedule

    def __call__(self, x: jax.Array) -> jax.Array:
        return spmv_pallas(self.mat, x, self.schedule)

    @property
    def gather(self) -> str | None:
        """Where this kernel gathers a float32 x: "vmem" inside the kernel,
        "xla" before the launch, None where the format does not say."""
        from repro.sparse.registry import spec_for

        site = spec_for(self.mat).x_gather
        return None if site is None else site(self.mat, np.float32)

    @property
    def x_rows_walked(self) -> int | None:
        """Rows of 128 of x each tile walks where the kernel gathers x
        itself from a window (CSR's ``x_walk``); None elsewhere."""
        if self.gather != "vmem":
            return None
        return getattr(self.mat, "x_walk", None) or None


def gather_attrs(kernel) -> dict:
    """A ``kernel.execute`` span's attributes of where ``kernel`` gathers x:
    ``gather`` ("vmem", "xla" or None) and ``x_rows_walked``."""
    return {
        "gather": getattr(kernel, "gather", None),
        "x_rows_walked": getattr(kernel, "x_rows_walked", None),
    }


def matrix_fingerprint(matrix) -> str:
    """Content hash of a matrix — the kernel-memo identity.

    Two matrices with equal bytes/shape/dtype share every prepared kernel;
    the session layer uses this to deduplicate batched tuning requests. A
    ``HostCSR`` is hashed from its shape, dtypes and three arrays, under a
    ``csr`` tag, so it never shares an identity with a dense matrix.
    """
    from repro.sparse.host import HostCSR

    h = hashlib.sha256()
    if isinstance(matrix, HostCSR):
        parts = [np.ascontiguousarray(a) for a in (matrix.indptr, matrix.indices, matrix.data)]
        h.update(str(("csr", matrix.shape, [str(a.dtype) for a in parts])).encode())
        for a in parts:
            h.update(memoryview(a).cast("B"))
        return h.hexdigest()[:32]
    a = np.ascontiguousarray(np.asarray(matrix))
    h.update(str((a.shape, str(a.dtype))).encode())
    h.update(a.tobytes())
    return h.hexdigest()[:32]


# Process-wide LRU memo of prepared kernels, keyed by (caller key, fmt,
# schedule). Opt-in via ``compile_spmv(..., memo_key=...)`` so
# one-off callers don't pin large format storage. Bounded: each entry holds
# the full converted matrix storage, so an unbounded memo on a serving path
# streaming distinct matrices would grow RSS until OOM. Fused composite
# kernels share the memo with a "fused:<fmt>+<fmt>..." format tag and the
# composite-plan signature in the schedule slot (one entry per plan).
_KERNEL_MEMO: "OrderedDict[tuple, object]" = OrderedDict()
_MEMO_STATS = {"hits": 0, "compiles": 0, "evictions": 0}
_MEMO_LIMIT = 256


def kernel_memo_stats() -> dict[str, int]:
    """Copy of the process-wide memo counters (hits / compiles / evictions)."""
    return dict(_MEMO_STATS)


def kernel_memo_size() -> int:
    return len(_KERNEL_MEMO)


def kernel_memo_limit() -> int:
    return _MEMO_LIMIT


def set_kernel_memo_limit(limit: int) -> None:
    """Resize the LRU bound (evicts immediately if shrinking)."""
    global _MEMO_LIMIT
    if limit < 1:
        raise ValueError("kernel memo limit must be >= 1")
    _MEMO_LIMIT = limit
    while len(_KERNEL_MEMO) > _MEMO_LIMIT:
        _KERNEL_MEMO.popitem(last=False)
        _MEMO_STATS["evictions"] += 1
        _M_EVICTIONS.inc()


def kernel_memoized(
    memo_key: Hashable, fmt: str, schedule: KernelSchedule = DEFAULT_SCHEDULE
) -> bool:
    """Whether ``compile_spmv`` with these arguments would be a memo hit.

    Lets the session's amortized-overhead accounting charge the conversion
    term only when conversion will actually run."""
    return (memo_key, fmt, schedule) in _KERNEL_MEMO


def clear_kernel_memo() -> None:
    _KERNEL_MEMO.clear()


def _fused_tag_contains(tag, fmt: str) -> bool:
    """Whether a fused memo tag ("fused:ell+csr+...") involves ``fmt``."""
    return (
        isinstance(tag, str)
        and tag.startswith(_FUSED_TAG_PREFIX)
        and fmt in tag[len(_FUSED_TAG_PREFIX) :].split("+")
    )


def evict_kernel_memo_format(fmt: str) -> int:
    """Drop every memoized kernel of one format.

    Called by the registry when a format is unregistered or re-registered:
    a memoized ``PreparedSpmv`` must not outlive the ``FormatSpec`` that
    built it (its container would no longer resolve in ``spec_for``, or
    would silently run the old implementation). Fused composite kernels are
    evicted when ANY of their block formats matches — their flattened
    streams were lowered through the retiring ``FormatSpec``."""
    stale = [
        k for k in _KERNEL_MEMO if k[1] == fmt or _fused_tag_contains(k[1], fmt)
    ]
    for k in stale:
        del _KERNEL_MEMO[k]
        _MEMO_STATS["evictions"] += 1
        _M_EVICTIONS.inc()
    return len(stale)


def compile_spmv(
    dense: np.ndarray,
    fmt: str,
    schedule: KernelSchedule = DEFAULT_SCHEDULE,
    *,
    memo_key: Hashable | None = None,
) -> PreparedSpmv:
    """prepare + bind: the full compile-time-mode product.

    With ``memo_key`` (typically ``matrix_fingerprint(dense)``) the prepared
    kernel is memoized process-wide: repeated compilation requests for the
    same (matrix, format, schedule) return the existing ``PreparedSpmv``
    without re-running conversion — the ``c`` term of the §5.3 overhead
    model is paid once per unique matrix (until LRU eviction)."""
    if memo_key is not None:
        key = (memo_key, fmt, schedule)
        hit = _KERNEL_MEMO.get(key)
        if hit is not None:
            _MEMO_STATS["hits"] += 1
            _M_HITS.inc()
            _KERNEL_MEMO.move_to_end(key)
            return hit
    with _span("kernel.compile", fmt=fmt):
        prepared = PreparedSpmv(prepare(dense, fmt, schedule), schedule)
    if memo_key is not None:
        # counters cover memoized traffic only, so hits/(hits+compiles) is a
        # true memo hit rate (plain one-off compiles don't skew it)
        _MEMO_STATS["compiles"] += 1
        _M_COMPILES.inc()
        _KERNEL_MEMO[key] = prepared
        while len(_KERNEL_MEMO) > _MEMO_LIMIT:
            _KERNEL_MEMO.popitem(last=False)
            _MEMO_STATS["evictions"] += 1
            _M_EVICTIONS.inc()
    return prepared


def compile_spmv_block(
    dense: np.ndarray,
    row_start: int,
    row_end: int,
    fmt: str,
    schedule: KernelSchedule = DEFAULT_SCHEDULE,
    *,
    memo_key: Hashable | None = None,
) -> PreparedSpmv:
    """``compile_spmv`` for one row block of a larger matrix.

    The memo identity composes the caller's whole-matrix key with the row
    range, so a partitioned executor's per-block kernels are memoized (and
    LRU-evicted, and format-evicted) exactly like whole-matrix kernels —
    two composite plans over the same matrix share every block they agree
    on, without colliding with the monolithic kernel for the same matrix.
    """
    block = np.asarray(dense)[row_start:row_end]
    key = (memo_key, row_start, row_end) if memo_key is not None else None
    return compile_spmv(block, fmt, schedule, memo_key=key)


_FUSED_TAG_PREFIX = "fused:"


def fused_plan_signature(plan) -> tuple:
    """Hashable identity of a ``CompositePlan``'s executable content.

    Two plans lower to the same fused stream iff their (row range, format,
    schedule) tuples agree per block — the memo key component that makes
    "one kernel memo entry keyed on the composite plan" precise."""
    return tuple(
        (bp.block.row_start, bp.block.row_end, bp.fmt, bp.schedule)
        for bp in plan.blocks
    )


def compile_spmv_fused(
    dense: np.ndarray, plan, *, memo_key: Hashable | None = None
):
    """Lower a ``CompositePlan`` to its single-launch fused kernel.

    The whole composite memoizes as ONE entry: the format slot carries a
    ``fused:<fmt>+<fmt>...`` tag (so ``evict_kernel_memo_format`` retires it
    with any constituent format) and the schedule slot carries the plan
    signature. Returns a ``repro.kernels.fused.FusedSpmv``."""
    from repro.kernels.fused import lower_fused

    key = None
    if memo_key is not None:
        tag = _FUSED_TAG_PREFIX + "+".join(bp.fmt for bp in plan.blocks)
        key = (memo_key, tag, fused_plan_signature(plan))
        hit = _KERNEL_MEMO.get(key)
        if hit is not None:
            _MEMO_STATS["hits"] += 1
            _M_HITS.inc()
            _KERNEL_MEMO.move_to_end(key)
            return hit
    with _span("kernel.compile", fused=True, formats="+".join(bp.fmt for bp in plan.blocks)):
        kernel = lower_fused(dense, plan)
    if key is not None:
        _MEMO_STATS["compiles"] += 1
        _M_COMPILES.inc()
        _KERNEL_MEMO[key] = kernel
        while len(_KERNEL_MEMO) > _MEMO_LIMIT:
            _KERNEL_MEMO.popitem(last=False)
            _MEMO_STATS["evictions"] += 1
            _M_EVICTIONS.inc()
    return kernel


_SPMSPV_TAG = "spmspv"


@dataclass(frozen=True)
class PreparedSpmspv:
    """A schedule-specialized SpMSpV — the sparse-frontier twin of
    ``PreparedSpmv``.

    Holds the column-slice storage plus the host-side per-column nnz
    vector, so the adaptive policy can price a frontier
    (``modeled_work``) without touching device memory.
    """

    mat: Any  # repro.kernels.spmspv.CscEll
    schedule: KernelSchedule
    col_nnz: Any = None  # np.ndarray (n_cols,) int64

    def call_frontier(self, active: np.ndarray, xvals: np.ndarray) -> jax.Array:
        return spmspv(self.mat, active, xvals, self.schedule)

    def __call__(self, x: jax.Array) -> jax.Array:
        """Dense-in/dense-out convenience: extracts the frontier host-side."""
        xh = np.asarray(x)
        active = np.flatnonzero(xh).astype(np.int32)
        return self.call_frontier(active, xh[active])

    def modeled_work(self, active: np.ndarray) -> int:
        """Stored nonzeros this frontier touches — the SpMSpV cost model."""
        if self.col_nnz is None:
            return 0
        return int(np.asarray(self.col_nnz)[np.asarray(active, dtype=np.int64)].sum())


def compile_spmspv(
    dense: np.ndarray,
    schedule: KernelSchedule = DEFAULT_SCHEDULE,
    *,
    memo_key: Hashable | None = None,
) -> PreparedSpmspv:
    """prepare + bind the sparse-input-vector path.

    Memoizes alongside the SpMV kernels with the ``"spmspv"`` tag in the
    format slot — one extra entry per (matrix, schedule), subject to the
    same LRU bound and counters, so an iterative solve that uses both
    paths pays each conversion once."""
    from repro.kernels.spmspv import col_nnz as _col_nnz
    from repro.kernels.spmspv import csc_from_dense

    if memo_key is not None:
        key = (memo_key, _SPMSPV_TAG, schedule)
        hit = _KERNEL_MEMO.get(key)
        if hit is not None:
            _MEMO_STATS["hits"] += 1
            _M_HITS.inc()
            _KERNEL_MEMO.move_to_end(key)
            return hit
    with _span("kernel.compile", fmt=_SPMSPV_TAG):
        prepared = PreparedSpmspv(
            csc_from_dense(dense, schedule), schedule, _col_nnz(dense)
        )
    if memo_key is not None:
        _MEMO_STATS["compiles"] += 1
        _M_COMPILES.inc()
        _KERNEL_MEMO[key] = prepared
        while len(_KERNEL_MEMO) > _MEMO_LIMIT:
            _KERNEL_MEMO.popitem(last=False)
            _MEMO_STATS["evictions"] += 1
            _M_EVICTIONS.inc()
    return prepared
