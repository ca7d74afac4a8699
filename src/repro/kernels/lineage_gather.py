"""Batched CSR lineage-probe Pallas kernel.

The paper's optimized tensor representation answers a lineage probe with
"three list accesses" (root -> dataset -> record -> triples).  The array
realization is a bidirectional CSR; a probe for query row ``q`` is:

    start, end = row_ptr[q], row_ptr[q+1]       (access 1, 2)
    neighbors  = col_idx[start:end]             (access 3 — bounded gather)

This kernel vectorizes the probe over a BATCH of queries — strictly more
general than the paper's scalar traversal — emitting a padded (Q, max_deg)
neighbor table (-1 padding).  The wrapper (:func:`repro.kernels.ops.
lineage_gather`) does accesses 1 and 2 as one gather before the launch and
hands the kernel each query's segment (``starts``, ``lens``), so the
offsets need not fit in SMEM; the kernel does access 3 with ``col_idx``
resident in VMEM for the whole launch.

TPU notes: a segment starts at an arbitrary lane, and the TPU loads only
128-lane-aligned windows.  Each 128-lane chunk of a segment is therefore
one aligned ``(1, 256)`` load rotated left by the segment's offset in its
window (one lane rotate), so the inner loop is an aligned vector load +
rotate + compare + select — no scatter, no ragged addressing.  The table
is written chunk-major, ``(max_deg / 128, Q, 128)``, so that every store is
one whole row of a tile, and its chunks are tiled over the grid's second
axis, so the VMEM a launch needs does not grow with ``max_deg``.
``col_idx`` must be padded to whole lanes plus :data:`VALUE_TAIL` trailing
sentinels, which the last chunk's window may read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lineage_gather_kernel", "lineage_gather_pallas", "vmem_bytes",
           "LANES", "VALUE_TAIL"]

LANES = 128
# sentinels col_idx carries past its last aligned window: a chunk reads the
# 256 lanes from its aligned base
VALUE_TAIL = 2 * LANES


def lineage_gather_kernel(starts_ref, lens_ref, col_idx_ref, out_ref, *,
                          block_q: int, tile: int):
    """Chunks ``[t * tile, (t + 1) * tile)`` of ``block_q`` queries'
    segments, ``t`` the grid's second index."""
    out_ref[...] = jnp.full(out_ref.shape, -1, jnp.int32)
    first = pl.program_id(1) * tile
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    width = 2 * LANES

    def row(qi, carry):
        start = starts_ref[0, qi]
        n = lens_ref[0, qi]
        base = (start // LANES) * LANES
        shift = (width - (start - base)) % width
        chunks = jnp.clip((n + LANES - 1) // LANES - first, 0, tile)

        def chunk(c, carry):
            left = n - (first + c) * LANES
            at = pl.multiple_of(base + (first + c) * LANES, LANES)
            seg = pltpu.roll(col_idx_ref[:, pl.ds(at, width)], shift, 1)
            out_ref[c, pl.ds(qi, 1), :] = jnp.where(
                lane < left, seg[:, :LANES], jnp.int32(-1))
            return carry

        return jax.lax.fori_loop(0, chunks, chunk, carry)

    jax.lax.fori_loop(0, block_q, row, 0)


def vmem_bytes(n_col: int, block_q: int, tile: int) -> int:
    """VMEM a launch asks for: ``col_idx`` and the table block, each
    double-buffered, and headroom."""
    return 2 * 4 * n_col + 2 * 4 * block_q * tile * LANES + (4 << 20)


@functools.partial(jax.jit,
                   static_argnames=("max_deg", "block_q", "tile", "interpret",
                                    "name"))
def lineage_gather_pallas(
    starts: jax.Array,    # (Q / block_q, 1, block_q) int32
    lens: jax.Array,      # (Q / block_q, 1, block_q) int32
    col_idx: jax.Array,   # (1, L) int32, L % 128 == 0, VALUE_TAIL sentinels
    *,
    max_deg: int,         # table width, a multiple of 128 * tile
    block_q: int = 128,
    tile: int = 8,        # 128-lane chunks per grid step
    interpret: bool = False,
    name: str | None = None,
) -> jax.Array:
    """``(max_deg / 128, Q, 128)`` int32: query q's segment
    ``col_idx[starts[q] : starts[q] + min(lens[q], max_deg)]`` in 128-lane
    chunks, ``-1`` past its end.  ``name`` names the launch in a trace."""
    blocks, _, bq = starts.shape
    assert bq == block_q and max_deg % (tile * LANES) == 0, \
        (starts.shape, block_q, max_deg, tile)
    chunks = max_deg // LANES
    smem = pl.BlockSpec((None, 1, block_q), lambda i, t: (i, 0, 0),
                        memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(lineage_gather_kernel, block_q=block_q, tile=tile),
        grid=(blocks, chunks // tile),
        in_specs=[
            # each query's segment, looked up before the launch: SMEM
            smem,
            smem,
            pl.BlockSpec(col_idx.shape, lambda i, t: (0, 0)),  # in VMEM
        ],
        out_specs=pl.BlockSpec((tile, block_q, LANES),
                               lambda i, t: (t, i, 0)),
        out_shape=jax.ShapeDtypeStruct((chunks, blocks * block_q, LANES),
                                       jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(
                vmem_bytes(col_idx.shape[1], block_q, tile), 16 << 20)),
        interpret=interpret,
        name=name,
    )(starts, lens, col_idx)
