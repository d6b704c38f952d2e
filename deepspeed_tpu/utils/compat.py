"""The two jax entry points the framework reaches through one name.

Call sites import ``shard_map`` and ``get_abstract_mesh`` from here; both
are thin passes to the installed jax.
"""
from __future__ import annotations

import jax


def get_abstract_mesh():
    """``jax.sharding.get_abstract_mesh()`` — the ambient mesh context."""
    return jax.sharding.get_abstract_mesh()


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = True,
              axis_names=None):
    """``jax.shard_map``; ``axis_names`` (partial-manual) is passed only
    when given."""
    kw = {} if axis_names is None else {"axis_names": axis_names}
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma, **kw)
