"""Mesh-sharded provenance index — 'in-memory' generalized to 'in-HBM'.

The paper's premise is an index resident in the memory of one development
machine.  At pod scale the training data (and therefore its provenance
relations) are sharded; this module keeps the SAME tensor algebra but lays
the packed relation bitplanes out over the device mesh:

* a relation R (n_src × n_dst bits, packed to (n_src, ceil(n_dst/32)) uint32)
  is sharded by SOURCE ROWS across the ("pod", "data") axes — each data shard
  owns the lineage of the records it feeds to training;
* composition (R1 · R2) is a LOCAL boolean matmul per shard: R1's row shard
  contracts against the full R2, which is all-gathered in WORD-packed form
  (32x smaller than the boolean operand — this is why bitplanes, not masks,
  cross the ICI);
* dataset-level audits (the paper's fairness / consent example) are a local
  popcount + ``psum`` — one scalar vector crosses the mesh, never records.

Everything here is shard_map'd jax; the host-resident ProvenanceIndex hands
over packed numpy bitplanes.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

if hasattr(jax, "shard_map"):  # jax >= 0.6
    _shard_map = jax.shard_map
else:  # pragma: no cover - version-dependent
    from jax.experimental.shard_map import shard_map as _shard_map

from repro.kernels import ref as kref

__all__ = [
    "shard_relation",
    "compose_sharded",
    "lineage_audit_sharded",
    "backward_frontier_sharded",
]


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The axes provenance rows shard over: ('pod','data') when present."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def shard_relation(bits: np.ndarray, mesh: Mesh) -> jax.Array:
    """Place a packed (rows, words) relation with rows sharded over the data
    axes (rows padded up to the shard multiple)."""
    axes = _data_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    r, w = bits.shape
    pad = (-r) % n_shards
    if pad:
        bits = np.pad(bits, ((0, pad), (0, 0)))
    spec = P(axes if axes else None, None)
    return jax.device_put(jnp.asarray(bits, jnp.uint32), NamedSharding(mesh, spec))


def compose_sharded(a_bits: jax.Array, b_bits: jax.Array, mesh: Mesh) -> jax.Array:
    """C = A·B over the (OR,AND) semiring; A row-sharded, B row-sharded.

    B's rows are A's contraction dim: the local matmul needs ALL of B, so B is
    all-gathered in packed (uint32) form — 1/32 the bytes of a boolean gather.
    Output C inherits A's row sharding (no re-shard, no output collective).
    """
    axes = _data_axes(mesh)
    if not axes:
        return _bitmm(a_bits, b_bits)

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes, None)),
        out_specs=P(axes, None),
    )
    def _kernel(a_shard, b_shard):
        b_full = jax.lax.all_gather(b_shard, axes, axis=0, tiled=True)
        return _bitmm(a_shard, b_full)

    return _kernel(a_bits, b_bits)


def _bitmm(a_bits: jax.Array, b_bits: jax.Array) -> jax.Array:
    """(OR,AND) matmul on packed operands, jnp path (Pallas on real TPU via
    repro.kernels.ops.bitmatmul; the jnp form lowers on any backend)."""
    m, kw = a_bits.shape
    k, nw = b_bits.shape
    a = kref.unpack_bits(a_bits, kw * 32)[:, :k].astype(jnp.float32)  # (m, k)
    b = kref.unpack_bits(b_bits, nw * 32).astype(jnp.float32)          # (k, n)
    c = (a @ b) > 0
    return kref.pack_bits(c)


def lineage_audit_sharded(
    rel_bits: jax.Array,
    group: jax.Array,
    dst_mask_words: jax.Array,
    n_groups: int,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """The paper's dataset-level audit, sharded.

    For each source-row group g (e.g. gender value), count source rows of
    group g that contributed to ANY selected output record:

        hits[i] = OR_w popcount(rel[i, w] & dst_mask[w]) > 0
        out[g]  = sum_i hits[i] * [group[i] == g]

    ``rel_bits`` row-sharded; ``group`` row-aligned int32; ``dst_mask_words``
    packed output-row selector, replicated.  Result: (n_groups,) int32,
    identical on all devices (psum).
    """
    if mesh is None or not _data_axes(mesh):
        return _audit_local(rel_bits, group, dst_mask_words, n_groups)
    axes = _data_axes(mesh)

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(None)),
        out_specs=P(),
    )
    def _kernel(rel_shard, group_shard, mask_words):
        local = _audit_local(rel_shard, group_shard, mask_words, n_groups)
        return jax.lax.psum(local, axes)

    return _kernel(rel_bits, group, dst_mask_words)


def _audit_local(rel_bits, group, mask_words, n_groups: int):
    hit_words = rel_bits & mask_words[None, :]
    hits = jax.lax.population_count(hit_words).astype(jnp.int32).sum(axis=1) > 0
    onehot = jax.nn.one_hot(group, n_groups, dtype=jnp.int32)
    return (hits.astype(jnp.int32)[:, None] * onehot).sum(axis=0)


def backward_frontier_sharded(
    rel_bits: jax.Array,
    dst_mask_words: jax.Array,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Backward record lineage at dataset scale: which SOURCE rows reach any
    selected output record.  Local AND+popcount per shard; the result mask is
    row-aligned with the shard — no collective at all (owner-computes)."""
    def _local(rel_shard, mask_words):
        hit_words = rel_shard & mask_words[None, :]
        return jax.lax.population_count(hit_words).astype(jnp.int32).sum(axis=1) > 0

    if mesh is None or not _data_axes(mesh):
        return _local(rel_bits, dst_mask_words)
    axes = _data_axes(mesh)

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(axes, None), P(None)),
        out_specs=P(axes),
    )
    def _kernel(rel_shard, mask_words):
        return _local(rel_shard, mask_words)

    return _kernel(rel_bits, dst_mask_words)
