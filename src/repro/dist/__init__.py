"""Distribution substrate: logical-axis sharding rules + partition hints.

``sharding.py`` maps the logical axis vocabulary of ``models/param.py``
(vocab/embed/heads/kv/ffn/...) onto mesh axes (FSDP over the data axes, TP
over the model axis) with divisibility and no-reuse guards. ``partition.py``
provides the ambient-context ``hint`` that model code sprinkles on
activations; outside a ``sharding_context`` it is an identity, so the same
model code runs unmodified on a single CPU device.
"""

from repro.dist.partition import hint, sharding_context
from repro.dist.sharding import (
    RULE_SETS,
    batch_sharding,
    build_sharding,
    spec_for,
)

__all__ = [
    "RULE_SETS",
    "batch_sharding",
    "build_sharding",
    "hint",
    "sharding_context",
    "spec_for",
]
