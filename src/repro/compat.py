"""``shard_map`` as the engines call it.

Code under ``src/`` calls this module's :func:`shard_map`; replication
checking is disabled (the engines manage replication explicitly).
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
