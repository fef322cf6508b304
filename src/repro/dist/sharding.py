"""Logical-axis -> mesh-axis sharding rules (FSDP + TP).

The logical vocabulary is documented in ``models/param.py``. Placement:

* data-like logical axes (``batch``, ``embed``) shard over every non-model
  mesh axis, in mesh order — ``("data",)`` on a 2D mesh, ``("pod", "data")``
  on a multi-pod mesh (ZeRO-3-style weight sharding over the full data
  extent);
* tensor-parallel logical axes (``vocab``, ``heads``, ``kv``, ``ffn``,
  ``rnn``) shard over the ``model`` axis;
* everything else (``experts``, ``layers``, ``seq``, ``None``) replicates.

Two guards make the mapping total: a dimension that does not divide the
mesh extent replicates instead (kv=8 on a 16-way model axis), and a mesh
axis is never assigned twice in one spec (the second ``embed`` of a square
weight replicates).
"""

from __future__ import annotations

import math
from typing import Any, Mapping

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"

# logical axis -> placement class: "data" (all non-model axes), "model", or
# None (replicated). A rule set is a plain dict so variants can override.
TRAIN_RULES: dict[str, str | None] = {
    "batch": "data",
    "embed": "data",
    "vocab": MODEL_AXIS,
    "heads": MODEL_AXIS,
    "kv": MODEL_AXIS,
    "ffn": MODEL_AXIS,
    "rnn": MODEL_AXIS,
    "experts": None,
    "layers": None,
    "seq": None,
}

# Inference keeps weights TP-sharded but replicates embed (no ZeRO gather on
# the decode path; the per-chip weight residency is paid once).
INFER_RULES: dict[str, str | None] = dict(TRAIN_RULES, embed=None)

# Partitioned SpMV (repro.partition.executor): the stacked per-block sparse
# storage shards its leading "blocks" axis over the data axes (one row block
# per device); the dense X vector replicates, because every block may gather
# arbitrary columns; per-block Y keeps the "blocks" axis sharded so output
# shards stay local to the device that produced them.
SPMV_RULES: dict[str, str | None] = {
    "blocks": "data",
    "rows": None,
    "cols": None,
}

RULE_SETS: dict[str, dict[str, str | None]] = {
    "train": TRAIN_RULES,
    "infer": INFER_RULES,
    "spmv": SPMV_RULES,
}


def spmv_mesh(n_blocks: int | None = None):
    """1-D ``("data",)`` mesh over the first ``n_blocks`` local devices.

    The partitioned executor maps one row block per device, so the mesh
    extent is ``min(n_blocks, available devices)`` — on a single-device host
    this degrades to a 1-extent mesh and ``shard_map`` runs everything
    locally (same program, no collectives)."""
    import numpy as np

    devices = jax.devices()
    n = len(devices) if n_blocks is None else max(1, min(n_blocks, len(devices)))
    return jax.sharding.Mesh(np.asarray(devices[:n]), ("data",))


def _mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(mesh.shape)


def _data_axes(mesh) -> tuple[str, ...]:
    return tuple(n for n in mesh.axis_names if n != MODEL_AXIS)


def spec_for(
    mesh,
    shape: tuple[int, ...],
    axes: tuple[str | None, ...],
    rules: Mapping[str, str | None] | None = None,
) -> P:
    """PartitionSpec for one array given its logical axes.

    Indivisible dims and already-used mesh axes fall back to replication;
    trailing replicated entries are stripped so specs compare canonically.
    """
    rules = TRAIN_RULES if rules is None else rules
    sizes = _mesh_axis_sizes(mesh)
    used: set[str] = set()
    entries: list[Any] = []
    for dim, logical in zip(shape, axes):
        placement = rules.get(logical) if logical is not None else None
        if placement is None:
            entries.append(None)
            continue
        names = _data_axes(mesh) if placement == "data" else (placement,)
        names = tuple(n for n in names if n in sizes and n not in used)
        extent = math.prod(sizes[n] for n in names) if names else 0
        if not names or dim % extent:
            entries.append(None)
            continue
        used.update(names)
        entries.append(names if len(names) > 1 else names[0])
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def build_sharding(mesh, spec_tree: Any, rules: Mapping | None = None) -> Any:
    """NamedSharding tree for a ParamSpec pytree (same structure)."""
    from repro.models.param import is_spec

    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_for(mesh, s.shape, s.axes, rules)),
        spec_tree,
        is_leaf=is_spec,
    )


def batch_sharding(mesh, batch: Any, rules: Mapping | None = None) -> Any:
    """Shard the leading (batch) axis of every leaf over the data axes."""

    def one(leaf):
        shape = tuple(leaf.shape)
        axes = ("batch",) + (None,) * (len(shape) - 1)
        return NamedSharding(mesh, spec_for(mesh, shape, axes, rules))

    return jax.tree.map(one, batch)
