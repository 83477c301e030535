"""JAX shims shared across the codebase.

One place for the ``shard_map`` flavour the collective bodies use, so
the replication-check setting moves together on a JAX upgrade.
"""

from __future__ import annotations

from functools import partial


def shard_map_compat(*, check: bool = False):
    """``jax.shard_map``, with replication checking disabled by default
    (our collective bodies return deliberately replicated outputs that
    the checker cannot always prove)."""
    from jax import shard_map
    return shard_map if check else partial(shard_map, check_vma=False)
