"""``shard_map`` with the repo's one setting.

Everything in the repo routes through :func:`shard_map` below, so the
replication-check setting lives in one place.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks disabled.

    (The repo's collectives deliberately produce replicated outputs from
    sharded inputs — e.g. top-k merges after an all-gather — which the
    strict checker rejects.)
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
