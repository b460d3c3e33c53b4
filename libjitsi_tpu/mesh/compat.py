"""The one `shard_map` symbol every mesh module routes through
(`jax.shard_map`, replication checking off by default: the sharded
seams return per-shard results that are not replicated)."""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
