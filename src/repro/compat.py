"""``shard_map`` with the keyword this codebase uses.

Call sites pass ``check_vma``; it defaults to off here, as the model's
collectives are written out by hand.
"""

from __future__ import annotations

from jax import shard_map as _shard_map


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with the replication check off by default."""
    return _shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=check_vma)
