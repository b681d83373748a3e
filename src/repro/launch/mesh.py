"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state; the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init.

Every axis is `AxisType.Auto`: the model and valuation code places data
with `NamedSharding`s and lets the partitioner propagate them, which is the
Auto contract. (`jax.make_mesh` defaults to Explicit axes, under which every
indexing update must name its `out_sharding`.)
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "make_auto_mesh"]


def make_auto_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis `AxisType.Auto`."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod (v5e); multi_pod adds a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_local_mesh():
    """Whatever devices exist locally, as a (data, model=1) mesh -- used by
    examples/tests on CPU."""
    n = len(jax.devices())
    return make_auto_mesh((n, 1), ("data", "model"))
