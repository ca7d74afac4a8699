"""Production mesh construction.

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before first jax init; smoke tests
see the 1 real CPU device).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "make_mesh",
    "make_shard_mesh",
    "host_device_count",
    "request_host_devices",
]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; (2, 16, 16) = 512 chips across 2 pods."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """All locally visible devices on ('data',) — tests and examples."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))


def host_device_count() -> int:
    """Locally visible device count (after any XLA_FLAGS host-platform
    override — see :func:`request_host_devices`)."""
    return len(jax.devices())


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


def request_host_devices(n: int) -> bool:
    """Ask XLA's host platform for ``n`` CPU devices by setting
    ``--xla_force_host_platform_device_count`` in ``XLA_FLAGS``.

    Only effective BEFORE the jax backend initializes — CI's multi-device
    lane exports the flag in the job environment; this helper is for
    launchers that assemble the environment in-process.  Returns whether
    the request can still take effect (False once jax has initialized with
    a different count)."""
    import os

    flag = f"--xla_force_host_platform_device_count={int(n)}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()
    import jax._src.xla_bridge as xb

    if xb._backends:  # backend already up: the flag cannot apply anymore
        return len(jax.devices()) >= int(n)
    return True
