"""Fused K-hop batched-walk Pallas kernel.

A batched Q1/Q2 record probe over a linear op chain used to cost K×3 kernel
launches: per hop one select-OR bitplane contraction (``bitmatmul``), one
``bitset_rank`` for the per-probe frontier sizes, and one ``lineage_gather``
to materialize the frontier — with the mask stack bouncing through HBM (and
host memory, off-TPU) between every launch.  This kernel fuses the whole
chain into ONE launch:

* the probe mask lives in a VMEM scratch tile (``cur``) for the entire walk
  — it is read from HBM once and written once, never in between;
* the K relation bitplanes are zero-padded to one common square dim and
  stacked into a single ``(K, N, N/32)`` operand whose ``(1, bk, bnw)``
  blocks stream through the grid's inner dimensions — Pallas
  double-buffers the next plane block behind the current contraction;
* each hop's contraction (:func:`~repro.kernels.bitmatmul.or_and_block`, the
  MXU 0/1 matmul) ORs into a second scratch tile (``nxt``); at the hop's
  last block the per-probe popcount (the fused ``bitset_rank``) is recorded
  and the frontier swaps into ``cur`` for the next hop.

Plane rows are stored bit-major (:func:`~repro.kernels.bitmatmul.
bit_major_rows`), so the slice of ``cur`` that one grid step contracts is
one bit position of ``bk`` whole words: a lane-aligned window and a scalar
shift.  Zero padding is inert under the (OR, AND) semiring — a padded
row/column can never set a bit — so one common padded dim is exact.

Grid ``(B/bb, K, Nw/bnw, N/bk)``: batch blocks are independent
("parallel"); hops, output blocks and contraction blocks carry the scratch
frontier ("arbitrary").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.bitmatmul import bit01, or_and_block, select_dtype

__all__ = ["batched_walk_kernel", "batched_walk_pallas", "popcount32"]

# lanes of the per-hop counts output block (the count is broadcast across
# them: a (bb, 1) block would break the TPU's (8, 128) block tiling)
COUNT_LANES = 128


def popcount32(x: jax.Array) -> jax.Array:
    """Per-element popcount of int32 words (SWAR: shifts, masks, a multiply)."""
    srl = jax.lax.shift_right_logical
    x = x - (srl(x, 1) & 0x55555555)
    x = (x & 0x33333333) + (srl(x, 2) & 0x33333333)
    x = (x + srl(x, 4)) & 0x0F0F0F0F
    return srl(x * 0x01010101, 24)


def batched_walk_kernel(mask_ref, planes_ref, out_ref, counts_ref,
                        cur_ref, nxt_ref, *, block_k: int, block_nw: int,
                        dtype):
    """One (bb,) batch block × one hop × one output block × one bk-row
    slice of the contraction."""
    hop = pl.program_id(1)
    nb = pl.program_id(2)
    ks = pl.program_id(3)
    k_blocks = cur_ref.shape[1] // block_k
    first = (nb == 0) & (ks == 0)
    last = (nb == pl.num_programs(2) - 1) & (ks == pl.num_programs(3) - 1)

    @pl.when((hop == 0) & first)
    def _load_mask():
        # one HBM read per batch block; the mask then stays VMEM-resident
        cur_ref[...] = mask_ref[...]

    @pl.when(first)
    def _clear_frontier():
        nxt_ref[...] = jnp.zeros_like(nxt_ref)

    w0 = pl.multiple_of((ks % k_blocks) * block_k, block_k)
    a01 = bit01(cur_ref[:, pl.ds(w0, block_k)], ks // k_blocks, dtype)
    part = or_and_block(a01, planes_ref[0])
    n0 = pl.multiple_of(nb * block_nw, block_nw)
    nxt_ref[:, pl.ds(n0, block_nw)] = nxt_ref[:, pl.ds(n0, block_nw)] | part

    @pl.when(last)
    def _hop_done():
        frontier = nxt_ref[...]
        # fused bitset_rank: per-probe frontier size for this hop
        pops = jnp.sum(popcount32(frontier), axis=1, keepdims=True)
        counts_ref[0] = jnp.broadcast_to(pops, counts_ref.shape[1:])
        # the frontier becomes the next hop's resident mask
        cur_ref[...] = frontier

        @pl.when(hop == pl.num_programs(1) - 1)
        def _final():
            out_ref[...] = frontier


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_k", "block_nw", "interpret")
)
def batched_walk_pallas(
    mask_bits: jax.Array,
    planes: jax.Array,
    *,
    block_b: int = 8,
    block_k: int = 512,
    block_nw: int = 128,
    interpret: bool = False,
) -> tuple:
    """Fused walk over pre-padded int32 operands.

    ``mask_bits`` is (B, N/32); ``planes`` is (K, N, N/32) — every hop
    padded to the one square dim N, rows bit-major.  B % block_b == 0 and
    (N/32) divisible by block_k and block_nw.  Returns ``(out_bits (B, N/32)
    int32, counts (K, B, COUNT_LANES) int32)``.
    ``repro.kernels.ops.batched_walk`` handles padding/stacking/unpadding.
    """
    b, nw = mask_bits.shape
    k, n, nw2 = planes.shape
    assert nw == nw2 and nw * 32 == n, (nw, nw2, n)
    assert b % block_b == 0 and nw % block_k == 0 and nw % block_nw == 0

    grid = (b // block_b, k, nw // block_nw, n // block_k)
    return pl.pallas_call(
        functools.partial(batched_walk_kernel, block_k=block_k,
                          block_nw=block_nw, dtype=select_dtype(interpret)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, nw), lambda i, j, nb, ks: (i, 0)),
            pl.BlockSpec((1, block_k, block_nw),
                         lambda i, j, nb, ks: (j, ks, nb)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, nw), lambda i, j, nb, ks: (i, 0)),
            pl.BlockSpec((1, block_b, COUNT_LANES),
                         lambda i, j, nb, ks: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nw), jnp.int32),
            jax.ShapeDtypeStruct((k, b, COUNT_LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_b, nw), jnp.int32),  # cur: resident mask
            pltpu.VMEM((block_b, nw), jnp.int32),  # nxt: hop accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")
        ),
        interpret=interpret,
        name="batched_walk",
    )(mask_bits, planes)
