"""Boolean-semiring bitplane matmul Pallas kernel.

The paper composes provenance tensors along a pipeline with Einstein
summation (Section IV).  Over binary relations the semiring is (OR, AND):

    C[i, j] = OR_m  A[i, m] AND B[m, j]

TPU formulation: the contraction runs on the MXU as a 0/1 bf16 matmul with
an f32 count, thresholded ``> 0`` and repacked.  Operands stay bit-packed
in HBM (uint32 words, bit ``p % 32`` of word ``p // 32``):

* ``a_bits``:  (M, K/32) — relation A packed along the contraction dim
* ``b_perm``:  (K, N/32) — relation B packed along the output dim, its rows
  stored BIT-MAJOR (row ``b * K/32 + w`` holds original row ``32 w + b``;
  :func:`bit_major_rows` builds it)
* ``c_bits``:  (M, N/32) — result packed along the output dim

The bit-major row order is what keeps every in-kernel access lane-aligned:
grid step ``ks`` contracts ``block_k`` consecutive rows of ``b_perm``, which
are bit ``ks // (K/32/block_k)`` of ``block_k`` consecutive words of A — a
whole-word block shifted by one scalar, never a lane-changing reshape.  On
the output side each of the 32 bit positions of B's words is one 0/1 matmul
whose ``> 0`` result lands back in the same bit position, so C comes out in
the plain packing with no reshuffle.

Grid (M/bm, Nw/bnw, K/bk); the contraction axis ORs into the revisited
output block (init at ``ks == 0``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bitmatmul_kernel", "bitmatmul_pallas", "bit_major_rows",
           "bit01", "or_and_block", "select_dtype"]


def bit_major_rows(b_bits: jax.Array) -> jax.Array:
    """Reorder the rows of a (32·W, N/32) packed operand bit-major: row
    ``b * W + w`` of the result is row ``32 w + b`` of the input."""
    k, nw = b_bits.shape
    return b_bits.reshape(k // 32, 32, nw).transpose(1, 0, 2).reshape(k, nw)


def select_dtype(interpret: bool):
    """dtype of the 0/1 matmul operands: bf16 feeds the MXU natively; the
    host backend behind interpret mode refuses some small bf16 dots, so it
    gets f32.  0/1 values and their counts are exact in either."""
    return jnp.float32 if interpret else jnp.bfloat16


def bit01(words: jax.Array, bit, dtype=jnp.bfloat16) -> jax.Array:
    """0/1 plane of one bit position of int32 words, as ``dtype``."""
    b = jax.lax.shift_right_logical(words, bit) & 1
    return b.astype(jnp.float32).astype(dtype)


def or_and_block(a01: jax.Array, b_words: jax.Array) -> jax.Array:
    """(OR, AND) product of a 0/1 (rows, bk) selector and a packed int32
    (bk, bnw) block -> packed int32 (rows, bnw): one MXU matmul per output
    bit position, with operands in the selector's dtype."""
    def one_bit(c, acc):
        cnt = jnp.dot(a01, bit01(b_words, c, a01.dtype),
                      preferred_element_type=jnp.float32)
        return acc | ((cnt > 0).astype(jnp.int32) << c)

    acc = jnp.zeros((a01.shape[0], b_words.shape[1]), jnp.int32)
    return jax.lax.fori_loop(0, 32, one_bit, acc)


def bitmatmul_kernel(a_ref, b_ref, c_ref, *, k_blocks: int, dtype):
    """One (bm, bnw) output tile for one bk-row slice of the contraction."""
    ks = pl.program_id(2)
    part = or_and_block(bit01(a_ref[...], ks // k_blocks, dtype), b_ref[...])

    @pl.when(ks == 0)
    def _init():
        c_ref[...] = part

    @pl.when(ks > 0)
    def _accum():
        c_ref[...] = c_ref[...] | part


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_nw", "block_k", "interpret")
)
def bitmatmul_pallas(
    a_bits: jax.Array,
    b_perm: jax.Array,
    *,
    block_m: int = 256,
    block_nw: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """C_bits = (OR,AND)-matmul of packed int32 relations.

    Shapes must be pre-padded and ``b_perm`` bit-major
    (:func:`bit_major_rows`): M % block_m == 0, (K/32) % block_k == 0,
    Nw % block_nw == 0.  ``repro.kernels.ops.bitmatmul`` handles all of it.
    """
    m, kw = a_bits.shape
    k, nw = b_perm.shape
    assert kw * 32 == k, (kw, k)
    assert m % block_m == 0 and nw % block_nw == 0 and kw % block_k == 0
    k_blocks = kw // block_k

    grid = (m // block_m, nw // block_nw, k // block_k)
    return pl.pallas_call(
        functools.partial(bitmatmul_kernel, k_blocks=k_blocks,
                          dtype=select_dtype(interpret)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda i, j, ks: (i, ks % k_blocks)),
            pl.BlockSpec((block_k, block_nw), lambda i, j, ks: (ks, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_nw), lambda i, j, ks: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, nw), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="bitmatmul",
    )(a_bits, b_perm)
