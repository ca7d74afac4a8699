"""Batched CSR lineage-probe Pallas kernel.

The paper's optimized tensor representation answers a lineage probe with
"three list accesses" (root -> dataset -> record -> triples).  The array
realization is a bidirectional CSR; a probe for query row ``q`` is:

    start, end = row_ptr[q], row_ptr[q+1]       (access 1, 2)
    neighbors  = col_idx[start:end]             (access 3 — bounded gather)

This kernel vectorizes the probe over a BATCH of queries — strictly more
general than the paper's scalar traversal — emitting a padded (Q, max_deg)
neighbor table (-1 padding).  ``col_idx`` must be padded by ``max_deg``
trailing sentinels so the dynamic contiguous slice never reads OOB.

TPU notes: a probe's segment starts at an arbitrary lane, and the TPU
loads only 128-lane-aligned windows.  Each query therefore loads the
aligned window ``[base, base + max_deg + 128)`` around its segment and
rotates it left by ``start - base`` (one lane rotate), so the inner loop is
an aligned vector load + rotate + compare + select — no scatter, no
ragged addressing.  ``max_deg`` must be a multiple of 128 on TPU, and
``col_idx`` must hold ``max_deg + 128`` trailing sentinels past its last
aligned window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lineage_gather_kernel", "lineage_gather_pallas"]


def lineage_gather_kernel(queries_ref, row_ptr_ref, col_idx_ref, out_ref, *,
                          block_q: int, max_deg: int):
    """Probe ``block_q`` queries against the full CSR resident in VMEM."""
    width = max_deg + 128
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, max_deg), 1)

    def body(qi, _):
        q = queries_ref[qi]
        start = row_ptr_ref[q]
        end = row_ptr_ref[q + 1]
        base = pl.multiple_of((start // 128) * 128, 128)
        window = col_idx_ref[:, pl.ds(base, width)]  # (1, max_deg + 128)
        seg = pltpu.roll(window, (width - (start - base)) % width, 1)
        out_ref[pl.ds(qi, 1), :] = jnp.where(lane < (end - start),
                                             seg[:, :max_deg], jnp.int32(-1))
        return 0

    jax.lax.fori_loop(0, block_q, body, 0)


@functools.partial(jax.jit, static_argnames=("max_deg", "block_q", "interpret"))
def lineage_gather_pallas(
    queries: jax.Array,   # (Q,) int32, Q % block_q == 0
    row_ptr: jax.Array,   # (R+1,) int32
    col_idx: jax.Array,   # (1, L) int32 — sentinel-padded, see module doc
    *,
    max_deg: int,
    block_q: int = 128,
    interpret: bool = False,
) -> jax.Array:
    (q,) = queries.shape
    assert q % block_q == 0, (q, block_q)
    grid = (q // block_q,)
    return pl.pallas_call(
        functools.partial(lineage_gather_kernel, block_q=block_q, max_deg=max_deg),
        grid=grid,
        in_specs=[
            # scalars the probe loop indexes by: SMEM
            pl.BlockSpec((block_q,), lambda i: (i,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(row_ptr.shape, lambda i: (0,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec(col_idx.shape, lambda i: (0, 0)),  # CSR in VMEM
        ],
        out_specs=pl.BlockSpec((block_q, max_deg), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, max_deg), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(queries, row_ptr, col_idx)
