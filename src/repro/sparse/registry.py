"""Pluggable sparse-format registry: one ``FormatSpec`` from converter to
bandit arm.

Historically every layer of the reproduction hard-coded the four seed
formats: ``if fmt == "csr"`` chains in the kernel wrapper, per-format
footprint branches in the cost model, ``FORMAT_NAMES`` literals in the
tuning space, and ``"csr"`` defaults through session / predictor / bandit /
serve CLI. The SpMV literature catalogues dozens of formats (Gao et al.,
arXiv:2404.06047; Koza et al.'s CMRS, arXiv:1203.2946), so format count must
be a *runtime* property: this module defines the ``FormatSpec`` contract
that bundles everything the system branches on per format, and every
dispatch site consumes the registry instead of a literal.

Adding a format is one call::

    from repro.sparse.registry import FormatSpec, register_format

    register_format(FormatSpec(
        name="myfmt",
        container=MyFmt,            # jax-pytree dataclass
        from_dense=myfmt_from_dense,
        to_dense=myfmt_to_dense,
        prepare=my_prepare,         # (dense, schedule) -> MyFmt, aligned
        prepare_csr=my_prepare_csr, # optional: (HostCSR, schedule) -> MyFmt
        spmv=my_spmv,               # (mat, x, schedule) -> y, jit-traceable
        reference=my_reference,     # pure-jnp oracle, (mat, x) -> y
        footprint=my_footprint,     # (MatrixStats, schedule) -> KernelFootprint
    ))

and the format then appears in ``full_space()``, the tuning dataset,
classifier labels, the serving bandit's arm set, and the SpMV server —
no edits to any of those layers. ``repro/sparse/bcsr.py`` is the proof:
a fifth format (blocked-CSR) registered exactly this way.

Contract notes for plugin authors (enforced by the shared suite in
``tests/test_format_registry.py``):

* ``from_dense``/``to_dense`` must round-trip exactly;
* ``prepare`` aligns storage geometry to the ``KernelSchedule`` and raises
  ``InfeasibleConfig`` when storage would blow up (``check_storage_bytes``);
  all padding and pointer arithmetic happens there, on the host, once;
  ``prepare_csr`` does the same from a ``repro.sparse.host.HostCSR``
  without making it dense; a format without one refuses that input;
* ``spmv`` is traced under ``jax.jit`` (``kernels.ops.spmv_pallas``): it
  may only read static metadata and shapes of the container, never its
  values, so a call moves no matrix storage through the host;
* ``spmv`` on storage prepared with a *different* schedule must either
  compute the exact result or raise ``InfeasibleConfig`` — never silently
  corrupt;
* ``footprint`` must return finite, non-negative statistics with
  ``useful_flops == 2 * nnz``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Callable

import jax.numpy as jnp
import numpy as np

from repro.kernels.common import (
    LANE,
    VMEM_BYTES,
    InfeasibleConfig,
    KernelSchedule,
    ceil_to,
    pad_axis,
)
from repro.obs.trace import span as _span
from repro.sparse.host import HostCSR

__all__ = [
    "FormatSpec",
    "InfeasibleConfig",
    "KernelFootprint",
    "MatrixStats",
    "MAX_STORAGE_BYTES",
    "check_storage_bytes",
    "default_format",
    "format_names",
    "get_format",
    "register_format",
    "registered_specs",
    "spec_for",
    "unregister_format",
]

MAX_STORAGE_BYTES = 512 * 1024 * 1024  # refuse >512 MiB single-format storage


def check_storage_bytes(estimate: int, what: str) -> None:
    """Shared feasibility guard for ``FormatSpec.prepare`` implementations."""
    if estimate > MAX_STORAGE_BYTES:
        raise InfeasibleConfig(f"{what} storage would be {estimate/1e6:.0f} MB")


# ---------------------------------------------------------------------------
# Matrix statistics + footprint model (the cost model's per-format inputs)
# ---------------------------------------------------------------------------


class MatrixStats:
    """Cached structural statistics of one matrix (host-side numpy).

    The duck-typed interface ``FormatSpec.footprint`` implementations rely
    on: ``n_rows``, ``n_cols``, ``nnz``, ``max_nnz``, ``row_counts``, plus
    the cached ``block_occupancy(br, bc)`` and ``sell_storage(C, q)``
    reductions.
    """

    def __init__(self, dense: np.ndarray):
        dense = np.asarray(dense)
        self.n_rows, self.n_cols = dense.shape
        self.row_counts = (dense != 0).sum(axis=1).astype(np.int64)
        self.nnz = int(self.row_counts.sum())
        self.max_nnz = int(self.row_counts.max(initial=0))
        self._mask = dense != 0

    @lru_cache(maxsize=16)
    def block_occupancy(self, br: int, bc: int) -> tuple[int, int]:
        """(#occupied blocks, max occupied blocks per block-row)."""
        pr, pc = ceil_to(self.n_rows, br), ceil_to(self.n_cols, bc)
        m = np.zeros((pr, pc), dtype=bool)
        m[: self.n_rows, : self.n_cols] = self._mask
        occ = m.reshape(pr // br, br, pc // bc, bc).any(axis=(1, 3))
        per_row = occ.sum(axis=1)
        return int(occ.sum()), int(per_row.max(initial=0))

    @lru_cache(maxsize=16)
    def sell_storage(self, C: int, q: int) -> tuple[int, int]:
        """(total stored elems, max width) for SELL-C-q."""
        n_slices = (self.n_rows + C - 1) // C
        total, maxw = 0, 0
        for s in range(n_slices):
            w = int(self.row_counts[s * C : (s + 1) * C].max(initial=0))
            w = ceil_to(max(w, 1), q)
            total += w * C
            maxw = max(maxw, w)
        return total, maxw


@dataclass(frozen=True)
class KernelFootprint:
    """Work/traffic summary of one (matrix, format, schedule) point."""

    useful_flops: float
    total_flops: float  # includes padding compute
    hbm_bytes: float  # format storage + X + Y traffic
    gather_elems: float  # in-kernel dynamic gathers
    scatter_elems: float  # in-kernel scatter-adds
    grid_steps: float
    mxu_fraction: float  # fraction of FLOPs running on the MXU
    vmem_resident_bytes: float  # steady-state VMEM requirement
    feasible: bool
    note: str = ""


# ---------------------------------------------------------------------------
# The FormatSpec contract + registry
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class FormatSpec:
    """Everything the system needs to know about one sparse format.

    ``priority`` orders ``format_names()`` and picks ``default_format()``
    (lowest wins); plugins default to 100 so they never displace the seed
    default unless they ask to.
    """

    name: str
    container: type  # the jax-pytree storage dataclass
    from_dense: Callable  # (dense, **kw) -> container
    to_dense: Callable  # (mat) -> np.ndarray (exact inverse)
    prepare: Callable  # (dense, KernelSchedule) -> container, tile-aligned
    spmv: Callable  # (mat, x, KernelSchedule) -> y, traced under jax.jit
    reference: Callable  # (mat, x) -> y — pure-jnp oracle
    footprint: Callable  # (MatrixStats, KernelSchedule) -> KernelFootprint
    priority: int = 100
    # (HostCSR, KernelSchedule) -> container, tile-aligned, never dense;
    # None where the format converts only from a dense matrix
    prepare_csr: Callable | None = None
    # (mat, x dtype) -> where spmv gathers x: "vmem" inside the kernel,
    # "xla" before the launch; None where the format does not say
    x_gather: Callable | None = None
    description: str = ""


_REGISTRY: dict[str, FormatSpec] = {}
_BY_CONTAINER: dict[type, FormatSpec] = {}
_INSERTION: dict[str, int] = {}
_counter = 0


def register_format(spec: FormatSpec, *, overwrite: bool = False) -> FormatSpec:
    """Register ``spec``; after this call the format is live everywhere
    (tuning space, dataset harness, cost model, bandit arms, serving)."""
    global _counter
    if not spec.name or not spec.name.isidentifier():
        raise ValueError(f"format name must be an identifier, got {spec.name!r}")
    if spec.name in _REGISTRY and not overwrite:
        raise ValueError(
            f"format {spec.name!r} already registered; pass overwrite=True"
        )
    bound = _BY_CONTAINER.get(spec.container)
    if bound is not None and bound.name != spec.name:
        raise ValueError(
            f"container {spec.container.__name__} already bound to format "
            f"{bound.name!r}"
        )
    prev = _REGISTRY.get(spec.name)
    if prev is not None:
        _BY_CONTAINER.pop(prev.container, None)
        _evict_prepared_kernels(spec.name)
    _REGISTRY[spec.name] = spec
    _BY_CONTAINER[spec.container] = spec
    if spec.name not in _INSERTION:
        _INSERTION[spec.name] = _counter
        _counter += 1
    return spec


def unregister_format(name: str) -> None:
    spec = _REGISTRY.pop(name, None)
    if spec is None:
        raise ValueError(f"format {name!r} is not registered")
    _BY_CONTAINER.pop(spec.container, None)
    _INSERTION.pop(name, None)
    _evict_prepared_kernels(name)


def _evict_prepared_kernels(name: str) -> None:
    """A memoized ``PreparedSpmv`` must not outlive the spec that built it."""
    from repro.kernels.ops import evict_kernel_memo_format

    evict_kernel_memo_format(name)


def get_format(name: str) -> FormatSpec:
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(
            f"unknown format {name!r}; registered formats: {format_names()}"
        )
    return spec


def format_names() -> tuple[str, ...]:
    """Registered format names, ordered by (priority, registration order)."""
    return tuple(
        sorted(_REGISTRY, key=lambda n: (_REGISTRY[n].priority, _INSERTION[n]))
    )


def default_format() -> str:
    """The format the system holds/serves when nothing better is known."""
    names = format_names()
    if not names:
        raise RuntimeError("no sparse formats registered")
    return names[0]


def registered_specs() -> tuple[FormatSpec, ...]:
    return tuple(_REGISTRY[n] for n in format_names())


def spec_for(mat) -> FormatSpec:
    """Resolve the spec governing a storage container instance."""
    spec = _BY_CONTAINER.get(type(mat))
    if spec is None:
        raise TypeError(
            f"no registered format for container {type(mat).__name__}; "
            f"registered: {format_names()}"
        )
    return spec


# ---------------------------------------------------------------------------
# Seed formats: CSR / ELL / BELL / SELL
#
# Everything below is ordinary plugin code — it uses only the public
# machinery above, exactly as third-party formats do. The kernel entrypoints
# are imported here (after the machinery is defined) so that the
# kernels <-> sparse import cycle resolves cleanly in either direction.
# ---------------------------------------------------------------------------

from repro.kernels.bell import bell_spmv_pallas  # noqa: E402
from repro.kernels.csr import csr_spmv_pallas, x_gather  # noqa: E402
from repro.kernels.ell import ell_spmv_pallas  # noqa: E402
from repro.kernels.sell import sell_spmv_pallas  # noqa: E402
from repro.sparse.formats import (  # noqa: E402
    BELL,
    CSR,
    ELL,
    SELL,
    bell_from_dense,
    bell_to_dense,
    csr_from_dense,
    csr_tiled,
    csr_tiled_from_host,
    csr_to_dense,
    ell_from_dense,
    ell_to_dense,
    sell_from_dense,
    sell_to_dense,
    tiled_stored,
)
from repro.sparse.spmv import (  # noqa: E402  (pure-jnp oracles)
    spmv_bell as _ref_bell,
    spmv_csr as _ref_csr,
    spmv_ell as _ref_ell,
    spmv_sell as _ref_sell,
)

_VAL_B, _IDX_B = 4.0, 4.0  # fp32 values, int32 indices


# --- CSR -------------------------------------------------------------------


_CSR_ENTRY_B = 3 * 4  # value, column id and row id of a stored entry


def csr_storable(row_counts: np.ndarray, schedule: KernelSchedule) -> int | None:
    """Entries of the tiled CSR stream of a matrix with these nonzeros per
    row at ``schedule``'s tiling, or None where ``check_storage_bytes``
    would refuse it."""
    stored = tiled_stored(row_counts, schedule.rows_per_block, schedule.nnz_tile)
    return stored if stored * _CSR_ENTRY_B <= MAX_STORAGE_BYTES else None


def _csr_stored(row_counts: np.ndarray, schedule: KernelSchedule) -> int:
    stored = tiled_stored(row_counts, schedule.rows_per_block, schedule.nnz_tile)
    check_storage_bytes(stored * _CSR_ENTRY_B, "CSR")
    return stored


def _csr_prepare(dense: np.ndarray, schedule: KernelSchedule) -> CSR:
    dense = np.asarray(dense)
    _csr_stored((dense != 0).sum(axis=1), schedule)
    return csr_tiled(dense, schedule.rows_per_block, schedule.nnz_tile)


def _csr_prepare_csr(mat: HostCSR, schedule: KernelSchedule) -> CSR:
    stored = _csr_stored(mat.row_counts(), schedule)
    with _span("sparse.convert", fmt="csr", stored=stored, nnz=mat.nnz):
        return csr_tiled_from_host(mat, schedule.rows_per_block, schedule.nnz_tile)


def _csr_spmv(mat: CSR, x, schedule: KernelSchedule):
    if mat.tiling == (0, 0):
        raise InfeasibleConfig(
            "CSR storage is not tile-aligned; convert with prepare(..., schedule)"
        )
    n_rows, _ = mat.shape
    # the stream's own tiling fixes the geometry; the schedule the numerics
    y = csr_spmv_pallas(
        mat.data, mat.indices, mat.row_ids, x, n_rows, mat.tiling, schedule,
        x_start=mat.x_start, x_walk=mat.x_walk or None,
    )
    return y[:n_rows]


def _csr_x_gather(mat: CSR, x_dtype) -> str:
    return x_gather(mat.shape[1], x_dtype, mat.tiling[1], mat.x_walk or None)


def _csr_footprint(stats: MatrixStats, schedule: KernelSchedule) -> KernelFootprint:
    n, m, nnz = stats.n_rows, stats.n_cols, stats.nnz
    nt = schedule.nnz_tile
    x_bytes, y_bytes = m * _VAL_B, n * _VAL_B
    nnz_pad = ceil_to(max(nnz, 1), nt)
    stored = float(nnz_pad)
    # data + cols + row_ids + indptr + x + y
    hbm = stored * (_VAL_B + 2 * _IDX_B) + (n + 1) * _IDX_B + x_bytes + y_bytes
    steps = nnz_pad / nt
    tile_b = nt * (_VAL_B + 2 * _IDX_B)
    vmem = 2 * tile_b + x_bytes + (n + 1) * _VAL_B  # y resident too
    return KernelFootprint(
        2.0 * nnz, 2 * stored, hbm, stored, stored, steps, 0.0, vmem,
        vmem <= VMEM_BYTES and schedule.x_residency == "vmem",
        note="" if schedule.x_residency == "vmem"
        else "CSR requires VMEM-resident X and Y on TPU",
    )


# --- ELL -------------------------------------------------------------------


def _ell_prepare(dense: np.ndarray, schedule: KernelSchedule) -> ELL:
    dense = np.asarray(dense)
    n_rows, _ = dense.shape
    rpb, nt = schedule.rows_per_block, schedule.nnz_tile
    counts_max = int((dense != 0).sum(axis=1).max(initial=0))
    width = ceil_to(max(counts_max, 1), nt)
    check_storage_bytes(ceil_to(n_rows, rpb) * width * 8, "ELL")
    mat = ell_from_dense(dense, min_width=width)
    data = pad_axis(np.asarray(mat.data), 0, ceil_to(n_rows, rpb))
    cols = pad_axis(np.asarray(mat.cols), 0, ceil_to(n_rows, rpb))
    return ELL(jnp.asarray(data), jnp.asarray(cols), shape=mat.shape)


def _ell_spmv(mat: ELL, x, schedule: KernelSchedule):
    n_rows, _ = mat.shape
    rpb, nt = schedule.rows_per_block, schedule.nnz_tile
    R, W = mat.data.shape
    if R % rpb or W % nt:
        raise InfeasibleConfig(
            f"ELL planes ({R},{W}) not aligned to schedule ({rpb},{nt}); "
            "use prepare() with the same schedule"
        )
    return ell_spmv_pallas(mat.data, mat.cols, x, schedule)[:n_rows]


def _ell_footprint(stats: MatrixStats, schedule: KernelSchedule) -> KernelFootprint:
    n, m, nnz = stats.n_rows, stats.n_cols, stats.nnz
    rpb, nt = schedule.rows_per_block, schedule.nnz_tile
    x_bytes, y_bytes = m * _VAL_B, n * _VAL_B
    width = ceil_to(max(stats.max_nnz, 1), nt)
    rows = ceil_to(n, rpb)
    stored = float(rows) * width
    hbm = stored * (_VAL_B + _IDX_B) + x_bytes + y_bytes
    steps = (rows / rpb) * (width / nt)
    tile_b = rpb * nt * (_VAL_B + _IDX_B)
    vmem = 2 * tile_b + (x_bytes if schedule.x_residency == "vmem" else 0) + rpb * _VAL_B
    return KernelFootprint(
        2.0 * nnz, 2 * stored, hbm, stored, 0.0, steps, 0.0, vmem,
        vmem <= VMEM_BYTES and schedule.x_residency == "vmem",
        note="" if schedule.x_residency == "vmem"
        else "ELL requires VMEM-resident X on TPU",
    )


# --- BELL ------------------------------------------------------------------


def _bell_prepare(dense: np.ndarray, schedule: KernelSchedule) -> BELL:
    dense = np.asarray(dense)
    br = min(schedule.rows_per_block, 256)
    # the true stored size, before materializing: every block-row holds as
    # many (br x 128) blocks as the fullest one
    _, max_blocks = MatrixStats(dense).block_occupancy(br, LANE)
    nbr = ceil_to(dense.shape[0], br) // br
    stored_blocks = nbr * max(max_blocks, 1)
    check_storage_bytes(stored_blocks * (br * LANE + 1) * 4, "BELL")
    return bell_from_dense(dense, br=br, bc=LANE)


def _bell_spmv(mat: BELL, x, schedule: KernelSchedule):
    return bell_spmv_pallas(mat.data, mat.block_cols, x, schedule)[: mat.shape[0]]


def _bell_footprint(stats: MatrixStats, schedule: KernelSchedule) -> KernelFootprint:
    n, m, nnz = stats.n_rows, stats.n_cols, stats.nnz
    x_bytes, y_bytes = m * _VAL_B, n * _VAL_B
    br, bc = min(schedule.rows_per_block, 256), LANE
    n_blocks, max_blocks = stats.block_occupancy(br, bc)
    nbr = ceil_to(n, br) // br
    stored_blocks = float(nbr) * max(max_blocks, 1)
    stored = stored_blocks * br * bc
    x_traffic = (
        stored_blocks * bc * _VAL_B  # streamed panels (scalar-prefetch DMA)
        if schedule.x_residency == "stream"
        else x_bytes
    )
    hbm = stored * _VAL_B + stored_blocks * _IDX_B + x_traffic + y_bytes
    steps = stored_blocks
    tile_b = br * bc * _VAL_B + bc * _VAL_B
    vmem = 2 * tile_b + br * _VAL_B + (x_bytes if schedule.x_residency == "vmem" else 0)
    return KernelFootprint(
        2.0 * nnz, 2 * stored, hbm, 0.0, 0.0, steps, 1.0, vmem,
        vmem <= VMEM_BYTES,
    )


# --- SELL ------------------------------------------------------------------


def _sell_prepare(dense: np.ndarray, schedule: KernelSchedule) -> SELL:
    return sell_from_dense(
        np.asarray(dense), C=schedule.rows_per_block, q=schedule.nnz_tile
    )


def _sell_spmv(mat: SELL, x, schedule: KernelSchedule):
    nt = schedule.nnz_tile
    if mat.q % nt:
        raise InfeasibleConfig(
            f"SELL widths padded to {mat.q}, not a multiple of nnz_tile={nt}; "
            "convert with prepare(..., schedule) so widths are nt-aligned"
        )
    y = sell_spmv_pallas(mat.data, mat.cols, mat.slice_width, x, schedule)
    return y[: mat.shape[0]]


def _sell_footprint(stats: MatrixStats, schedule: KernelSchedule) -> KernelFootprint:
    n, m, nnz = stats.n_rows, stats.n_cols, stats.nnz
    rpb, nt = schedule.rows_per_block, schedule.nnz_tile
    x_bytes, y_bytes = m * _VAL_B, n * _VAL_B
    C = rpb
    total, maxw = stats.sell_storage(C, nt)
    n_slices = (n + C - 1) // C
    stored = float(total)
    hbm = stored * (_VAL_B + _IDX_B) + x_bytes + y_bytes
    steps = n_slices * (maxw / nt)  # grid includes masked tiles
    tile_b = nt * C * (_VAL_B + _IDX_B)
    vmem = 2 * tile_b + (x_bytes if schedule.x_residency == "vmem" else 0) + C * _VAL_B
    return KernelFootprint(
        2.0 * nnz, 2 * stored, hbm, stored, 0.0, steps, 0.0, vmem,
        vmem <= VMEM_BYTES and schedule.x_residency == "vmem",
        note="" if schedule.x_residency == "vmem"
        else "SELL requires VMEM-resident X on TPU",
    )


register_format(FormatSpec(
    name="csr",
    container=CSR,
    from_dense=csr_from_dense,
    to_dense=csr_to_dense,
    prepare=_csr_prepare,
    spmv=_csr_spmv,
    reference=_ref_csr,
    footprint=_csr_footprint,
    prepare_csr=_csr_prepare_csr,
    x_gather=_csr_x_gather,
    priority=0,
    description="Compressed Sparse Row (flat segmented-sum kernel)",
))
register_format(FormatSpec(
    name="ell",
    container=ELL,
    from_dense=ell_from_dense,
    to_dense=ell_to_dense,
    prepare=_ell_prepare,
    spmv=_ell_spmv,
    reference=_ref_ell,
    footprint=_ell_footprint,
    priority=10,
    description="ELLPACK dense value/column planes",
))
register_format(FormatSpec(
    name="bell",
    container=BELL,
    from_dense=bell_from_dense,
    to_dense=bell_to_dense,
    prepare=_bell_prepare,
    spmv=_bell_spmv,
    reference=_ref_bell,
    footprint=_bell_footprint,
    priority=20,
    description="Blocked ELL over 8x128 MXU tiles",
))
register_format(FormatSpec(
    name="sell",
    container=SELL,
    from_dense=sell_from_dense,
    to_dense=sell_to_dense,
    prepare=_sell_prepare,
    spmv=_sell_spmv,
    reference=_ref_sell,
    footprint=_sell_footprint,
    priority=30,
    description="Sliced ELL (SELL-C-q) ragged storage",
))
