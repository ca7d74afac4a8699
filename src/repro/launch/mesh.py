"""Device-mesh construction.

FUNCTIONS, not module constants: importing this module never touches jax
device state (smoke tests see the 1 real CPU device; the multi-device CPU
lane sets ``XLA_FLAGS`` before jax initializes).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_local_mesh",
    "make_mesh",
    "make_shard_mesh",
]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh():
    """All locally visible devices on ('data',) — tests and examples."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))


def make_shard_mesh(n_shards: int, axis: str = "shards"):
    """A 1-D mesh over the first ``n_shards`` local devices for the sharded
    provenance index's collective walkers, or ``None`` when the host does
    not expose that many devices (callers fall back to the sequential
    per-shard engine — identical semantics, no mesh)."""
    devices = jax.devices()
    if n_shards < 1 or len(devices) < n_shards:
        return None
    import numpy as np

    from jax.sharding import Mesh

    return Mesh(np.asarray(devices[:n_shards]), (axis,))
