"""``jax.shard_map`` with replication checking off.

Every algorithm shards its fused train step through this wrapper: train steps
mix replicated params with data-sharded batches and per-device RNG folding,
which the ``check_vma`` pass rejects.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
