"""The one ``shard_map`` spelling call sites use.

Callers here always want the replication (vma) check OFF —
collective-heavy bodies (pallas out_shapes, masked psum broadcasts) trip
the checker — so the wrapper fixes that default once instead of every
call site repeating it.
"""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs, check=False):
    """``jax.shard_map`` with the vma check defaulting off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def axis_size(axis_name):
    """Size of a named mesh axis from inside a shard_map/pmap body."""
    return jax.lax.psum(1, axis_name)
