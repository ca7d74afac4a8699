"""Batched bitset rank Pallas kernel.

The paper's attribute maps (Section IV) are rank/select queries over the
Table-VI bitsets: ``map_vr_f(b, i) = sum_{k<=i} b_k`` etc.  This kernel
evaluates ``rank(pos) = popcount(bits[0..pos])`` (inclusive) for a batch of
positions against one packed bitset.

Structure: a block of queries sits on the sublanes, the bitset's words on
the lanes.  Each query's rank is the popcount of the whole words before
its word plus the masked popcount of its own word, both taken as masked
lane sums over word chunks — vector compares and reductions only, with no
per-query scalar indexing and no prefix scan (which the TPU lowering lacks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.batched_walk import popcount32

__all__ = ["bitset_rank_kernel", "bitset_rank_pallas"]


def bitset_rank_kernel(words_ref, pos_ref, out_ref, *, chunk: int):
    pos = pos_ref[...]  # (bq, 1) int32; pos < 0 is a null query -> rank 0
    safe = jnp.maximum(pos, 0)
    w = safe // 32
    b = safe % 32
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def one_chunk(c, carry):
        before, word = carry
        lo = pl.multiple_of(c * chunk, chunk)
        ws = words_ref[:, pl.ds(lo, chunk)]  # (1, chunk)
        idx = lane + lo
        before += jnp.sum(jnp.where(idx < w, popcount32(ws), 0), axis=1,
                          keepdims=True)
        word += jnp.sum(jnp.where(idx == w, ws, 0), axis=1, keepdims=True)
        return before, word

    zero = jnp.zeros_like(pos)
    n_chunks = words_ref.shape[1] // chunk
    before, word = jax.lax.fori_loop(0, n_chunks, one_chunk, (zero, zero))
    # bits [0..b] of the query's own word
    mask = jax.lax.shift_right_logical(jnp.full_like(b, -1), 31 - b)
    rank = before + popcount32(word & mask)
    out_ref[...] = jnp.where(pos < 0, 0, rank)


@functools.partial(jax.jit, static_argnames=("block_q", "chunk", "interpret"))
def bitset_rank_pallas(
    words: jax.Array,      # (1, W) int32, W % chunk == 0
    positions: jax.Array,  # (Q, 1) int32, Q % block_q == 0
    *,
    block_q: int = 128,
    chunk: int = 512,
    interpret: bool = False,
) -> jax.Array:
    q = positions.shape[0]
    assert q % block_q == 0 and words.shape[1] % chunk == 0
    grid = (q // block_q,)
    return pl.pallas_call(
        functools.partial(bitset_rank_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec(words.shape, lambda i: (0, 0)),  # bitset in VMEM
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="bitset_rank",
    )(words, positions)
