"""Activation-sharding hints (logical names -> mesh axes).

Model code annotates activations with LOGICAL axis names; the launcher
installs a policy mapping them to mesh axes before tracing.  Without a
policy every hint is a no-op, so smoke tests and single-device runs are
untouched.

Why this exists: GSPMD propagates parameter shardings to activations, but
boundary ops (embedding gather, logits matmul, MoE dispatch scatter) give it
freedom it sometimes spends badly — the dry-run showed XLA choosing
"involuntary full rematerialization" (replicate-then-reshard) for exactly
those ops, inflating per-device temp memory ~50x.  Pinning three activations
per model removes that freedom.  Policies are also the §Perf hillclimbing
lever: the launcher swaps policies per cell without touching model code.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import PartitionSpec as P, get_abstract_mesh

__all__ = ["set_policy", "policy", "hint", "use_policy"]

_STATE = threading.local()

Axes = Union[None, str, Tuple[str, ...]]


def set_policy(mapping: Optional[Dict[str, Axes]]) -> None:
    """mapping: logical name ('dp', 'tp', ...) -> mesh axis/axes."""
    _STATE.policy = mapping


def policy() -> Optional[Dict[str, Axes]]:
    return getattr(_STATE, "policy", None)


@contextlib.contextmanager
def use_policy(mapping: Optional[Dict[str, Axes]]):
    prev = policy()
    set_policy(mapping)
    try:
        yield
    finally:
        set_policy(prev)


def hint(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding; dims named None stay unconstrained."""
    pol = policy()
    if pol is None:
        return x
    mesh = get_abstract_mesh()
    if not mesh.axis_names:          # policy set but no mesh (local runs)
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"hint: {len(logical)} names for rank-{x.ndim} array")
    import numpy as np
    resolved = []
    for dim, name in enumerate(logical):
        ax = pol.get(name) if name else None
        if ax is not None:
            n = ax if isinstance(ax, tuple) else (ax,)
            # divisibility guard mirrors launch.sharding._guard
            size = int(np.prod([mesh.shape[a] for a in n]))
            if size <= 1 or x.shape[dim] % size != 0:
                ax = None
        resolved.append(ax)
    return jax.lax.with_sharding_constraint(x, P(*resolved))
