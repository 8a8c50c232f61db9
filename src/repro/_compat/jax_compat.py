"""jax API compatibility helpers."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off: our bodies mix
    replicated and per-shard collectives."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
