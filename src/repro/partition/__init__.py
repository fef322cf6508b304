"""Partitioned heterogeneous-format SpMV.

The paper's run-time mode picks one format for the whole matrix; this
subsystem runs it per row block. ``partitioner`` splits the row range into
nnz-balanced blocks (each with its own Table-2 feature vector), ``plan``
routes every block through the format registry + predictors + cost model
and searches block counts {1, 2, 4, 8} with a monolithic fallback, and
``executor`` runs the winning composite plan — heterogeneous per-block
Pallas kernels on one device, or one block per device over a mesh
``data`` axis via ``shard_map`` (X gathered, Y shards local).

Session/cache/serving integration lives in ``repro.core.session``
(``partitioned_optimize``), ``repro.core.cache`` (per-block plan entries),
and ``repro.train.serve`` / ``repro.launch.serve`` (``--partition``).
"""

from repro.partition.executor import (
    BlockKernel,
    PartitionedSpmv,
    ShardedPartitionedSpmv,
    compile_partitioned,
    shard_partitioned,
)
from repro.partition.partitioner import (
    SUPPORTED_BLOCK_COUNTS,
    RowBlock,
    RowPartition,
    partition_rows,
)
from repro.partition.plan import (
    BlockPlan,
    CompositePlan,
    plan_for_partition,
    plan_partitioned,
    route_block,
)

__all__ = [
    "BlockKernel",
    "BlockPlan",
    "CompositePlan",
    "PartitionedSpmv",
    "RowBlock",
    "RowPartition",
    "SUPPORTED_BLOCK_COUNTS",
    "ShardedPartitionedSpmv",
    "compile_partitioned",
    "partition_rows",
    "plan_for_partition",
    "plan_partitioned",
    "route_block",
    "shard_partitioned",
]
