"""One place for the mesh / shard_map spellings the codebase uses.

The code is written for the installed jax (see requirements.txt), whose
spellings are `jax.shard_map`, `jax.set_mesh` and
`jax.sharding.get_abstract_mesh`. Callers route through this module so a
future API change is made here once:

    from repro import compat
    step = compat.shard_map(f, mesh=mesh, in_specs=..., out_specs=...,
                            check_vma=False)
    with compat.set_mesh(mesh):
        ...
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["shard_map", "set_mesh", "get_mesh"]


def get_mesh():
    """The ambient mesh installed by `set_mesh`, or None."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or not m.axis_names else m


def set_mesh(mesh):
    """Context manager installing `mesh` as the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, mesh=None, *, in_specs, out_specs,
              check_vma: Optional[bool] = None):
    """`jax.shard_map` with an optional explicit mesh.

    Args:
      mesh: explicit mesh; None uses the ambient mesh (`set_mesh` context).
      check_vma: jax's replication-checking flag; None keeps its default.
    """
    kw = {} if check_vma is None else {"check_vma": check_vma}
    if mesh is not None:
        kw["mesh"] = mesh
    return jax.shard_map(f, in_specs=in_specs, out_specs=out_specs, **kw)
