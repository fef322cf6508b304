"""Composite execution of a partitioned plan.

Two execution paths, matching the two scales the ROADMAP cares about:

* ``PartitionedSpmv`` — single device. Each block's format-specific Pallas
  kernel (compiled through the ``FormatSpec`` registry and the process-wide
  kernel memo, keyed per row range) runs in sequence and the per-block
  outputs concatenate back into ``y``. Formats are fully heterogeneous —
  this is the paper's run-time mode, per block.

* ``ShardedPartitionedSpmv`` — multi device. Row blocks map one-per-device
  onto a mesh ``data`` axis via ``shard_map``. SPMD requires one program on
  every device, so the sharded path executes through a homogeneous *carrier*
  format (ELL planes, padded to a common per-block geometry and stacked on a
  leading "blocks" axis); the nnz-balanced partition is what keeps the
  per-device work even. Sharding follows ``repro.dist.sharding.SPMV_RULES``:
  the blocks axis shards over ``data``, X is gathered (replicated) to every
  device, and each Y shard stays local to the device that computed it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Hashable

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import SPMV_RULES, spec_for as sharding_spec, spmv_mesh
from repro.kernels.common import DEFAULT_SCHEDULE, KernelSchedule, ceil_to, pad_axis
from repro.kernels.ell import ell_spmv_pallas
from repro.kernels.ops import PreparedSpmv, compile_spmv_block
from repro.obs.trace import span as _span
from repro.partition.partitioner import RowPartition
from repro.partition.plan import CompositePlan
from repro.sparse.registry import get_format
from repro.utils.logging import get_logger

log = get_logger("partition.executor")

CARRIER_FORMAT = "ell"  # dense-plane storage: stackable + shardable


@dataclass(frozen=True)
class BlockKernel:
    """One row block's prepared kernel, with enough identity to observe."""

    index: int
    row_start: int
    row_end: int
    fmt: str
    kernel: PreparedSpmv


class PartitionedSpmv:
    """Heterogeneous-format composite SpMV on one device.

    Calls each block's ``PreparedSpmv`` and concatenates the outputs in row
    order. ``timed_call`` additionally returns per-block wall times so the
    serving layer can feed every (block, format) arm its own measurement.
    """

    def __init__(self, blocks: list[BlockKernel], n_rows: int):
        if not blocks:
            raise ValueError("PartitionedSpmv needs at least one block")
        self.blocks = list(blocks)
        self.n_rows = n_rows
        self._warmed = False

    @property
    def n_blocks(self) -> int:
        return len(self.blocks)

    @property
    def formats(self) -> tuple[str, ...]:
        return tuple(b.fmt for b in self.blocks)

    def __call__(self, x: jax.Array) -> jax.Array:
        x = jnp.asarray(x)
        with _span(
            "kernel.execute",
            mode="partitioned",
            n_blocks=self.n_blocks,
            formats="+".join(self.formats),
        ):
            parts = [b.kernel(x) for b in self.blocks]
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def timed_call(
        self, x: jax.Array, *, warmup: bool = True
    ) -> tuple[np.ndarray, list[float]]:
        """Execute block-by-block, timing each kernel — the measurement feed
        for per-block telemetry arms.

        The first ever call runs every block once untimed (``warmup``):
        without it the first sample's window includes trace + compile time,
        often orders of magnitude above steady state, and that poisoned
        sample seeds the bandit arms and the drift detector. Timing blocks
        on ``block_until_ready`` so only the kernel's own async work is in
        the window, not the host-side copy a full ``np.asarray`` adds."""
        x = jnp.asarray(x)
        if warmup and not self._warmed:
            for b in self.blocks:
                jax.block_until_ready(b.kernel(x))
            self._warmed = True
        parts, times = [], []
        for b in self.blocks:
            with _span("kernel.execute", mode="block", block=b.index, fmt=b.fmt):
                t0 = time.perf_counter()
                y = jax.block_until_ready(b.kernel(x))
                times.append(time.perf_counter() - t0)
            parts.append(np.asarray(y))
        return np.concatenate(parts), times


def compile_partitioned(
    dense: np.ndarray,
    plan: CompositePlan,
    *,
    memo_key: Hashable | None = None,
) -> PartitionedSpmv:
    """Compile every block of ``plan`` through the registry + kernel memo."""
    dense = np.asarray(dense)
    blocks = [
        BlockKernel(
            index=bp.block.index,
            row_start=bp.block.row_start,
            row_end=bp.block.row_end,
            fmt=bp.fmt,
            kernel=compile_spmv_block(
                dense,
                bp.block.row_start,
                bp.block.row_end,
                bp.fmt,
                bp.schedule,
                memo_key=memo_key,
            ),
        )
        for bp in plan.blocks
    ]
    log.info(
        "compiled partitioned kernel: %d block(s), formats=%s",
        len(blocks),
        "+".join(b.fmt for b in blocks),
    )
    return PartitionedSpmv(blocks, plan.partition.n_rows)


class ShardedPartitionedSpmv:
    """SPMD multi-device composite SpMV (one row block per mesh device).

    ``sharded_call`` returns the raw ``(n_blocks, padded_rows)`` output with
    its Y shards still resident on the devices that computed them (callers
    composing further sharded work should stay in this form); ``__call__``
    gathers and concatenates the valid rows into a host ``(n_rows,)`` array.
    """

    def __init__(
        self,
        dense: np.ndarray,
        partition: RowPartition,
        *,
        schedule: KernelSchedule = DEFAULT_SCHEDULE,
        mesh=None,
    ):
        from jax.sharding import NamedSharding

        dense = np.asarray(dense)
        self.partition = partition
        self.schedule = schedule
        self.mesh = mesh if mesh is not None else spmv_mesh(partition.n_blocks)
        axis_size = self.mesh.shape["data"]
        if partition.n_blocks != axis_size:
            raise ValueError(
                f"partition has {partition.n_blocks} blocks but the mesh "
                f"data axis has {axis_size} devices; partition with "
                f"n_blocks == mesh extent (spmv_mesh(n_blocks))"
            )

        # homogeneous ELL carrier: per-block planes padded to one geometry
        spec = get_format(CARRIER_FORMAT)
        mats = [
            spec.prepare(dense[b.row_start : b.row_end], schedule)
            for b in partition.blocks
        ]
        R = max(int(m.data.shape[0]) for m in mats)
        W = max(int(m.data.shape[1]) for m in mats)
        R, W = ceil_to(R, schedule.rows_per_block), ceil_to(W, schedule.nnz_tile)
        data = np.stack(
            [pad_axis(pad_axis(np.asarray(m.data), 0, R), 1, W) for m in mats]
        )
        cols = np.stack(
            [pad_axis(pad_axis(np.asarray(m.cols), 0, R), 1, W) for m in mats]
        )

        # dist.sharding rules: blocks axis -> data; X replicated; Y local
        plane_spec = sharding_spec(self.mesh, data.shape, ("blocks", None, None), SPMV_RULES)
        x_spec = sharding_spec(self.mesh, (partition.n_cols,), (None,), SPMV_RULES)
        y_spec = sharding_spec(self.mesh, (partition.n_blocks, R), ("blocks", None), SPMV_RULES)
        self.data = jax.device_put(data, NamedSharding(self.mesh, plane_spec))
        self.cols = jax.device_put(cols, NamedSharding(self.mesh, plane_spec))
        self._x_sharding = NamedSharding(self.mesh, x_spec)
        self.padded_rows = R

        def _block_body(d, c, x):
            # local shard: (1, R, W) planes + the replicated (gathered) x
            y = ell_spmv_pallas(d[0], c[0], x, schedule)
            return y[None, :]

        self._fn = jax.jit(
            jax.shard_map(
                _block_body,
                mesh=self.mesh,
                in_specs=(plane_spec, plane_spec, x_spec),
                out_specs=y_spec,
                # pallas_call has no shard_map replication rule; the body is
                # purely local (no collectives), so the check adds nothing
                check_vma=False,
            )
        )

    @property
    def n_blocks(self) -> int:
        return self.partition.n_blocks

    def sharded_call(self, x: jax.Array) -> jax.Array:
        """Run the SPMD kernel; the result's Y shards stay device-local."""
        x = jax.device_put(jnp.asarray(x), self._x_sharding)
        return self._fn(self.data, self.cols, x)

    def __call__(self, x: jax.Array) -> np.ndarray:
        y = np.asarray(self.sharded_call(x))  # gathers shards to host
        return np.concatenate(
            [y[b.index, : b.n_rows] for b in self.partition.blocks]
        )


def shard_partitioned(
    dense: np.ndarray,
    plan_or_partition: CompositePlan | RowPartition,
    *,
    schedule: KernelSchedule | None = None,
    mesh=None,
) -> ShardedPartitionedSpmv:
    """Build the multi-device executor from a plan or a bare partition.

    From a ``CompositePlan`` the (uniform) carrier schedule defaults to the
    first block's predicted schedule — per-block *formats* do not transfer to
    the SPMD path (one program per device), only the nnz-balanced row map.
    When the mesh (default: every local device) has a different extent than
    the partition, the rows are re-partitioned to one block per device.
    """
    if isinstance(plan_or_partition, CompositePlan):
        partition = plan_or_partition.partition
        if schedule is None:
            schedule = plan_or_partition.blocks[0].schedule
    else:
        partition = plan_or_partition
    from repro.partition.partitioner import partition_rows

    extent = (mesh if mesh is not None else spmv_mesh(partition.n_blocks)).shape["data"]
    if partition.n_blocks != extent:
        log.info(
            "re-partitioning %d block(s) -> %d device(s) for the SPMD path",
            partition.n_blocks,
            extent,
        )
        partition = partition_rows(dense, extent)
    return ShardedPartitionedSpmv(
        dense,
        partition,
        schedule=schedule or DEFAULT_SCHEDULE,
        mesh=mesh,
    )
