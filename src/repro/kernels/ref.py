"""Pure-jnp oracles for every Pallas kernel in this package.

Each oracle is the simplest correct implementation; tests sweep shapes and
dtypes and assert exact equality (the kernels are integer/boolean — no
tolerance needed) against these under ``interpret=True``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "pack_bits",
    "unpack_bits",
    "bitmatmul_ref",
    "lineage_gather_ref",
    "bitset_rank_ref",
    "batched_walk_ref",
]


def pack_bits(dense: jax.Array) -> jax.Array:
    """bool (R, C) -> uint32 (R, ceil(C/32)), little-endian within a word."""
    r, c = dense.shape
    cw = (c + 31) // 32
    padded = jnp.zeros((r, cw * 32), dtype=jnp.uint32)
    padded = padded.at[:, :c].set(dense.astype(jnp.uint32))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return (padded.reshape(r, cw, 32) << shifts[None, None, :]).sum(
        axis=-1, dtype=jnp.uint32
    )


def unpack_bits(words: jax.Array, n_cols: int) -> jax.Array:
    r, cw = words.shape
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[:, :, None] >> shifts[None, None, :]) & jnp.uint32(1)
    return bits.reshape(r, cw * 32)[:, :n_cols].astype(bool)


def bitmatmul_ref(a_bits: jax.Array, b_bits: jax.Array) -> jax.Array:
    """(OR,AND) matmul oracle: unpack, integer matmul, threshold, repack."""
    m, kw = a_bits.shape
    k, nw = b_bits.shape
    a = unpack_bits(a_bits, k).astype(jnp.int32)          # (m, k)
    b = unpack_bits(b_bits, nw * 32).astype(jnp.int32)    # (k, n)
    c = (a @ b) > 0                                       # boolean semiring
    return pack_bits(c)


def lineage_gather_ref(
    queries: jax.Array, row_ptr: jax.Array, col_idx: jax.Array, *, max_deg: int
) -> jax.Array:
    """Padded (Q, max_deg) neighbor table oracle: row q holds the segment
    ``col_idx[row_ptr[r] : row_ptr[r + 1]]`` of ``r = queries[q]``, then
    ``-1``; a query of ``-1`` has an empty segment."""
    valid = queries >= 0
    safe = jnp.where(valid, queries, 0)
    starts = row_ptr[safe]
    lens = jnp.where(valid, row_ptr[safe + 1] - starts, 0)
    lane = jnp.arange(max_deg, dtype=jnp.int32)[None, :]
    seg = col_idx[jnp.minimum(starts[:, None] + lane, col_idx.shape[0] - 1)]
    return jnp.where(lane < lens[:, None], seg, jnp.int32(-1))


def batched_walk_ref(mask_bits: jax.Array, planes) -> tuple:
    """K-hop fused-walk oracle: fold :func:`bitmatmul_ref` over the chain.

    ``mask_bits`` (B, ⌈n_0/32⌉) packs B probe sets; ``planes[j]`` is the
    packed (n_j, ⌈n_{j+1}/32⌉) relation of hop j.  Returns the final packed
    frontier (B, ⌈n_K/32⌉) and the per-hop frontier sizes (K, B) int32 —
    the rank term of the per-hop rank/gather the fused kernel subsumes.
    """
    cur = mask_bits
    counts = []
    for plane in planes:
        cur = bitmatmul_ref(cur, plane)
        counts.append(
            jax.lax.population_count(cur).astype(jnp.int32).sum(axis=1)
        )
    return cur, jnp.stack(counts, axis=0)


def bitset_rank_ref(words: jax.Array, positions: jax.Array) -> jax.Array:
    """Inclusive rank oracle: rank(p) = popcount(bits[0..p]); rank(-1) = 0."""
    pops = jax.lax.population_count(words).astype(jnp.int32)
    prefix = jnp.cumsum(pops)
    w = positions // 32
    b = positions % 32
    word = words[jnp.maximum(w, 0)]
    mask = (jnp.uint32(0xFFFFFFFF) >> (31 - b.astype(jnp.uint32))).astype(jnp.uint32)
    partial = jax.lax.population_count(word & mask).astype(jnp.int32)
    before = jnp.where(w > 0, prefix[jnp.maximum(w - 1, 0)], 0)
    return jnp.where(positions < 0, 0, before + partial)
