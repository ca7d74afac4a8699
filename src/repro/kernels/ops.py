"""Jit'd public wrappers around the Pallas kernels.

These handle padding to hardware-aligned block shapes, choose interpret mode
automatically off-TPU, and unpad results.  All call sites in
:mod:`repro.core` go through here.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bitmatmul import bit_major_rows, bitmatmul_pallas
from repro.kernels.lineage_gather import (LANES, VALUE_TAIL,
                                          lineage_gather_pallas, vmem_bytes)
from repro.kernels.bitset_rank import bitset_rank_pallas
from repro.kernels.batched_walk import batched_walk_pallas
from repro.kernels import ref

__all__ = [
    "bitmatmul",
    "bitplane_probe",
    "lineage_gather",
    "bitset_rank",
    "batched_walk",
    "batched_walk_unfused",
    "batched_walk_prepared",
    "prepare_walk_planes",
    "WalkPlanes",
    "gather_walk",
    "prepare_gather_walk",
    "GatherMap",
    "on_tpu",
    "launch_counts",
    "pallas_launch_counts",
    "reset_launch_counts",
]


@functools.cache
def on_tpu() -> bool:
    """Whether this process runs on a TPU — the one routing decision every
    ``use_pallas=None`` / ``fused_walk=None`` default resolves from."""
    return jax.default_backend() == "tpu"


# -- launch accounting --------------------------------------------------------
# Every public kernel entry counts ONE device dispatch (the Pallas launch on
# TPU, the equivalent jit'd oracle call elsewhere).  The tests assert the
# fused walk's K×3 -> 1 launch reduction off these counters.
# Compiled (not interpreted) Pallas launches are counted again on their own,
# so a run can show that the device kernels, not their oracles, answered.
_LAUNCHES: dict = {}
_PALLAS_LAUNCHES: dict = {}


def _note_launch(name: str, compiled_pallas: bool = False) -> None:
    _LAUNCHES[name] = _LAUNCHES.get(name, 0) + 1
    if compiled_pallas:
        _PALLAS_LAUNCHES[name] = _PALLAS_LAUNCHES.get(name, 0) + 1


def launch_counts() -> dict:
    """{kernel entry: dispatch count} since the last reset."""
    return dict(_LAUNCHES)


def pallas_launch_counts() -> dict:
    """{kernel entry: compiled Pallas launch count} since the last reset."""
    return dict(_PALLAS_LAUNCHES)


def reset_launch_counts() -> None:
    _LAUNCHES.clear()
    _PALLAS_LAUNCHES.clear()


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _as_i32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    return jnp.pad(x, ((0, rows - x.shape[0]), (0, cols - x.shape[1])))


def _lane(interpret: bool) -> int:
    # the TPU tiles the last block dim by 128 lanes; the interpreter has no
    # tiling, so small parity shapes pad only to a sublane multiple
    return 8 if interpret else 128


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_nw", "block_k", "interpret")
)
def _bitmatmul_kernel_call(a_bits, b_bits, *, block_m, block_nw, block_k,
                           interpret):
    m, kw = a_bits.shape
    nw = b_bits.shape[1]
    lane = _lane(interpret)
    kw_p = _round_up(kw, lane)
    nw_p = _round_up(nw, lane)
    bm = min(block_m, _round_up(m, 8))
    a_p = _pad2(_as_i32(a_bits), _round_up(m, bm), kw_p)
    b_p = bit_major_rows(_pad2(_as_i32(b_bits), 32 * kw_p, nw_p))
    out = bitmatmul_pallas(
        a_p,
        b_p,
        block_m=bm,
        block_nw=math.gcd(block_nw, nw_p),
        block_k=math.gcd(block_k, kw_p),
        interpret=interpret,
    )
    return jax.lax.bitcast_convert_type(out[:m, :nw], jnp.uint32)


def bitmatmul(
    a_bits,
    b_bits,
    *,
    block_m: int = 256,
    block_nw: int = 128,
    block_k: int = 512,
    interpret: bool | None = None,
    use_pallas: bool | None = True,
):
    """(OR,AND)-compose packed relations: (M, K/32) x (K, N/32) -> (M, N/32).

    ``use_pallas=False`` falls back to the jnp oracle (used for very small
    relations where kernel launch overhead dominates, and on hosts where
    interpret-mode cost would be prohibitive for large shapes).
    ``use_pallas=None`` resolves automatically — the cost model's
    kernel-launch guard: the Pallas kernel on TPU, the oracle elsewhere
    (interpret-mode emulation is never the cheaper backend on host).
    ``block_k`` counts words of A contracted per grid step.
    """
    a_bits = jnp.asarray(a_bits, dtype=jnp.uint32)
    b_bits = jnp.asarray(b_bits, dtype=jnp.uint32)
    kw = a_bits.shape[1]
    k = b_bits.shape[0]
    if use_pallas is None:
        use_pallas = on_tpu()
    if not ((kw - 1) * 32 < k <= kw * 32):
        raise ValueError(f"contraction mismatch: A packs {kw * 32} cols, B has {k} rows")
    if interpret is None:
        interpret = not on_tpu()
    _note_launch("bitmatmul", use_pallas and not interpret)
    if not use_pallas:
        # Zero-pad B's contraction rows up to A's packed width (inert rows).
        return ref.bitmatmul_ref(a_bits, _pad_to(b_bits, 0, 32))
    return _bitmatmul_kernel_call(a_bits, b_bits, block_m=block_m,
                                  block_nw=block_nw, block_k=block_k,
                                  interpret=interpret)


def bitplane_probe(mask_bits, plane_bits, *, use_pallas: bool | None = True, **kw):
    """Batched lineage probe of a composed relation (the hop-cache hot path).

    ``mask_bits`` (B, ⌈K/32⌉) packs B row-selector sets; ``plane_bits``
    (K, ⌈N/32⌉) is a composed relation bitplane.  Row b of the result packs
    the union of plane rows selected by probe b — the same (OR,AND)
    contraction as :func:`bitmatmul`, so it shares the Pallas kernel.
    """
    return bitmatmul(mask_bits, plane_bits, use_pallas=use_pallas, **kw)


# VMEM a lineage_gather launch may ask for; a larger col_idx is probed by
# the jnp oracle, still on the device
GATHER_VMEM_BYTES = 64 << 20
GATHER_BLOCK_Q = 128
GATHER_TILE = 8     # 128-lane chunks of the table per grid step


def _gather_fits(n_col: int) -> bool:
    """Whether the Pallas probe of a ``col_idx`` of ``n_col`` entries fits
    :data:`GATHER_VMEM_BYTES` (whatever the table's width)."""
    n = _round_up(n_col, LANES) + VALUE_TAIL
    return vmem_bytes(n, GATHER_BLOCK_Q, GATHER_TILE) <= GATHER_VMEM_BYTES


def _gather_col_idx(col_idx: np.ndarray) -> np.ndarray:
    """``(1, L)`` int32: ``col_idx`` padded with ``-1`` to whole lanes plus
    the kernel's tail."""
    out = np.full(_round_up(len(col_idx), LANES) + VALUE_TAIL, -1, np.int32)
    out[:len(col_idx)] = col_idx
    return out[None, :]


@functools.partial(jax.jit, static_argnames=("max_deg", "block_q",
                                             "interpret", "name"))
def _lineage_gather_call(queries, row_ptr, col_p, *, max_deg,
                         block_q=GATHER_BLOCK_Q, interpret, name):
    """The kernel on ``(Q,)`` queries (``-1`` = none): offsets looked up
    here, the table laid out ``(Q, max_deg)``."""
    q = queries.shape[0]
    qp = _pad_to(queries, 0, block_q, value=-1)
    valid = qp >= 0
    safe = jnp.where(valid, qp, 0)
    starts = jnp.where(valid, row_ptr[safe], 0)
    lens = jnp.where(valid, row_ptr[safe + 1] - starts, 0)
    blocks = (qp.shape[0] // block_q, 1, block_q)
    md = _round_up(max_deg, LANES)
    # the most chunks per grid step that tile the table exactly
    tile = max(t for t in range(1, GATHER_TILE + 1) if md // LANES % t == 0)
    out = lineage_gather_pallas(
        starts.reshape(blocks), lens.reshape(blocks), col_p, max_deg=md,
        block_q=block_q, tile=tile, interpret=interpret, name=name)
    return out.transpose(1, 0, 2).reshape(qp.shape[0], -1)[:q, :max_deg]


@functools.partial(jax.jit, static_argnames=("max_deg",))
def _lineage_gather_oracle(queries, row_ptr, col_p, *, max_deg):
    return ref.lineage_gather_ref(queries, row_ptr, col_p[0], max_deg=max_deg)


def lineage_gather(
    row_ptr,
    col_idx,
    queries,
    *,
    max_deg: int,
    block_q: int = GATHER_BLOCK_Q,
    interpret: bool | None = None,
    use_pallas: bool | None = True,
):
    """Batched CSR probe -> (Q, max_deg) padded neighbor table (a query of
    ``-1`` probes nothing).

    ``use_pallas=None`` applies the same kernel-launch guard as
    :func:`bitmatmul`: Pallas on TPU, the jnp oracle elsewhere and for a
    ``col_idx`` too large for :data:`GATHER_VMEM_BYTES`.
    """
    col_idx = np.asarray(col_idx, dtype=np.int32).reshape(-1)
    if use_pallas is None:
        use_pallas = on_tpu() and _gather_fits(len(col_idx))
    if interpret is None:
        interpret = not on_tpu()
    _note_launch("lineage_gather", use_pallas and not interpret)
    args = (jnp.asarray(queries, dtype=jnp.int32),
            jnp.asarray(row_ptr, dtype=jnp.int32),
            jnp.asarray(_gather_col_idx(col_idx)))
    md = max(int(max_deg), 1)
    if not use_pallas:
        return _lineage_gather_oracle(*args, max_deg=md)
    return _lineage_gather_call(*args, max_deg=md, block_q=block_q,
                                interpret=interpret, name=None)


def bitset_rank(
    words,
    positions,
    *,
    block_q: int = 128,
    interpret: bool | None = None,
    use_pallas: bool | None = True,
):
    """Batched inclusive rank over one packed bitset.

    ``use_pallas=None`` applies the same kernel-launch guard as
    :func:`bitmatmul`: Pallas on TPU, the jnp oracle elsewhere.
    """
    words = jnp.asarray(words, dtype=jnp.uint32)
    positions = jnp.asarray(positions, dtype=jnp.int32)
    if use_pallas is None:
        use_pallas = on_tpu()
    if interpret is None:
        interpret = not on_tpu()
    _note_launch("bitset_rank", use_pallas and not interpret)
    if not use_pallas:
        return ref.bitset_rank_ref(words, positions)
    q = positions.shape[0]
    w = _round_up(max(words.shape[0], 1), _lane(interpret))
    chunk = math.gcd(512, w)
    words_p = _pad2(_as_i32(words)[None, :], 1, w)
    p_p = _pad_to(positions, 0, block_q, value=-1)[:, None]
    out = bitset_rank_pallas(words_p, p_p, block_q=block_q, chunk=chunk,
                             interpret=interpret)
    return out[:q, 0]


# ---------------------------------------------------------------------------
# Fused K-hop batched walk (ROADMAP item 4)
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("n_hops",))
def _batched_walk_oracle(mask_bits, *planes, n_hops: int):
    # one jit'd fold over the whole chain == one device dispatch
    return ref.batched_walk_ref(mask_bits, planes)


def _check_walk_chain(kw: int, shapes) -> None:
    """Each hop's plane rows must fit the packed width that enters it:
    ``kw`` words for hop 0, the previous plane's words after it."""
    for j, (rows, words) in enumerate(shapes):
        if not ((kw - 1) * 32 < rows <= kw * 32):
            raise ValueError(
                f"hop {j}: frontier packs {kw * 32} cols, plane has {rows} rows"
            )
        kw = words


@dataclasses.dataclass(frozen=True)
class WalkPlanes:
    """A fused walk's plane operand on the device, made once by
    :func:`prepare_walk_planes` and walked by :func:`batched_walk_prepared`
    any number of times.

    ``operand`` is the padded, bit-major ``(K, 32·nw, nw)`` int32 stack the
    Pallas kernel streams, or on the oracle path the tuple of per-hop uint32
    planes.  ``shapes`` holds each hop's packed ``(rows, words)``: the chain
    check and the answer's width (the last hop's words) read it."""

    operand: object
    shapes: tuple
    use_pallas: bool
    interpret: bool

    @property
    def nbytes(self) -> int:
        """Device bytes the operand holds."""
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(self.operand))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _prepare_walk_stack(*planes, interpret):
    # One common padded dim: every hop's rows AND packed cols fit inside it
    # (a checked chain's mask packs ceil(rows_0 / 32) words, so it fits too).
    words = max(max(-(-p.shape[0] // 32), p.shape[1]) for p in planes)
    nw = _round_up(words, _lane(interpret))
    return jnp.stack(
        [bit_major_rows(_pad2(_as_i32(p), 32 * nw, nw)) for p in planes])


@functools.partial(
    jax.jit,
    static_argnames=("out_words", "block_b", "block_k", "block_nw",
                     "interpret"),
)
def _walk_stack(mask_bits, stacked, *, out_words, block_b, block_k, block_nw,
                interpret):
    nw = stacked.shape[2]
    b = mask_bits.shape[0]
    bb = min(block_b, _round_up(b, 8))
    mask_p = _pad2(_as_i32(mask_bits), _round_up(b, bb), nw)
    out_p, counts_p = batched_walk_pallas(
        mask_p, stacked, block_b=bb, block_k=math.gcd(block_k, nw),
        block_nw=math.gcd(block_nw, nw), interpret=interpret,
    )
    out = out_p[:b, :out_words]
    return jax.lax.bitcast_convert_type(out, jnp.uint32), counts_p[:, :b, 0]


def prepare_walk_planes(
    planes,
    *,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
) -> WalkPlanes:
    """The K hop planes of a chain as the fused walk's device operand.

    ``planes[j]`` is hop j's packed (n_j, ⌈n_{j+1}/32⌉) relation bitplane.
    On the Pallas path they are zero-padded to the one common square dim,
    reordered bit-major and stacked (one jit'd program); on the oracle path
    they are put on the device as they are.  The result depends on the
    planes alone, so a caller that walks one chain many times keeps it and
    ships only each call's mask (:func:`batched_walk_prepared`).
    """
    planes = [jnp.asarray(p, dtype=jnp.uint32) for p in planes]
    if not planes:
        raise ValueError("batched_walk needs at least one hop")
    shapes = tuple(tuple(p.shape) for p in planes)
    _check_walk_chain(-(-shapes[0][0] // 32), shapes)
    if use_pallas is None:
        use_pallas = on_tpu()
    if interpret is None:
        interpret = not on_tpu()
    operand = (_prepare_walk_stack(*planes, interpret=interpret)
               if use_pallas else tuple(planes))
    return WalkPlanes(operand, shapes, use_pallas, interpret)


def batched_walk_prepared(
    mask_bits,
    prepared: WalkPlanes,
    *,
    block_b: int = 128,
    block_k: int = 512,
    block_nw: int = 128,
):
    """:func:`batched_walk` over a prepared plane operand: only the mask is
    padded, and the answer is the same ``(out_bits, counts)``."""
    mask_bits = jnp.asarray(mask_bits, dtype=jnp.uint32)
    _check_walk_chain(mask_bits.shape[1], prepared.shapes)
    _note_launch("batched_walk", prepared.use_pallas and not prepared.interpret)
    if not prepared.use_pallas:
        return _batched_walk_oracle(mask_bits, *prepared.operand,
                                    n_hops=len(prepared.shapes))
    return _walk_stack(mask_bits, prepared.operand,
                       out_words=prepared.shapes[-1][1], block_b=block_b,
                       block_k=block_k, block_nw=block_nw,
                       interpret=prepared.interpret)


def batched_walk(
    mask_bits,
    planes,
    *,
    block_b: int = 128,
    block_k: int = 512,
    block_nw: int = 128,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
):
    """K-hop batched record probe in ONE kernel launch.

    ``mask_bits`` (B, ⌈n_0/32⌉) packs B probe sets over the chain's entry
    dim; ``planes[j]`` is hop j's packed (n_j, ⌈n_{j+1}/32⌉) relation
    bitplane.  Returns ``(out_bits (B, ⌈n_K/32⌉) uint32, counts (K, B)
    int32)`` — the final frontier plus each hop's per-probe frontier size
    (the rank term the per-hop path pays a separate ``bitset_rank`` for).

    ``use_pallas=None`` (the default) applies the kernel-launch guard: the
    fused Pallas kernel on TPU, the jit'd jnp oracle (still one dispatch)
    elsewhere.  For the Pallas path every hop dim is zero-padded to one
    common square dim (inert under (OR, AND)) and the planes stack into a
    single streamed operand; see :mod:`repro.kernels.batched_walk`.  It is
    :func:`prepare_walk_planes` then :func:`batched_walk_prepared`: a
    caller that walks one chain many times keeps the prepared operand.
    """
    return batched_walk_prepared(
        mask_bits,
        prepare_walk_planes(planes, interpret=interpret, use_pallas=use_pallas),
        block_b=block_b, block_k=block_k, block_nw=block_nw)


def batched_walk_unfused(
    mask_bits,
    planes,
    *,
    max_deg: int | None = None,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
):
    """The per-hop baseline the fused kernel replaces: K×3 launches.

    Per hop: :func:`bitplane_probe` (select-OR contraction),
    :func:`bitset_rank` over the flattened frontier bitset (per-probe
    frontier sizes as rank differences at row boundaries), and
    :func:`lineage_gather` materializing the frontier's padded neighbor
    table from a host-rebuilt CSR — with the mask stack round-tripping
    through the host between every launch, which is exactly the traffic
    the fused kernel keeps resident in VMEM.  Returns the same
    ``(out_bits, counts)`` as :func:`batched_walk` (byte-identical).
    """
    cur = np.asarray(jnp.asarray(mask_bits, dtype=jnp.uint32))
    b = cur.shape[0]
    all_counts = []
    for plane in planes:
        cur = np.asarray(
            bitplane_probe(cur, plane, use_pallas=use_pallas,
                           interpret=interpret)
        )
        w = cur.shape[1]
        ends = np.arange(1, b + 1, dtype=np.int32) * (w * 32) - 1
        ranks = np.asarray(
            bitset_rank(cur.reshape(-1), ends, use_pallas=use_pallas,
                        interpret=interpret)
        )
        counts = np.diff(np.concatenate([[0], ranks])).astype(np.int32)
        all_counts.append(counts)
        # host-side CSR rebuild of the frontier — the per-hop tax the fused
        # kernel's resident mask avoids entirely
        row_ptr = np.zeros(b + 1, dtype=np.int32)
        np.cumsum(counts, out=row_ptr[1:])
        col_idx = np.concatenate(
            [np.flatnonzero(ref.unpack_bits(cur[i : i + 1], w * 32)[0])
             for i in range(b)]
        ).astype(np.int32) if row_ptr[-1] else np.zeros(0, dtype=np.int32)
        md = max_deg if max_deg is not None else max(int(counts.max()), 1)
        lineage_gather(row_ptr, col_idx, np.arange(b, dtype=np.int32),
                       max_deg=md, use_pallas=use_pallas, interpret=interpret)
    return jnp.asarray(cur), jnp.asarray(np.stack(all_counts, axis=0))


# ---------------------------------------------------------------------------
# Gather walk: record probes through a composed row map
# ---------------------------------------------------------------------------
# device bytes one gather walk's answer table may take; a plan that would
# need more is answered on the host
GATHER_TABLE_BYTES = 256 << 20


@dataclasses.dataclass(frozen=True)
class GatherMap:
    """A composed row map's device operand, made once by
    :func:`prepare_gather_walk` and walked by :func:`gather_walk` in both
    directions.

    ``fwd`` and ``bwd`` each hold ``(offsets, col_idx, deg)``: a CSR over
    the rows a probe of that direction names, as :func:`lineage_gather`
    probes one, and the answer table's width (the largest segment, padded
    to whole lanes).  ``links`` counts the map's links (its rows other
    than ``-1``)."""

    fwd: tuple
    bwd: tuple
    links: int
    use_pallas: bool
    interpret: bool

    @property
    def nbytes(self) -> int:
        """Device bytes the operand holds."""
        return sum(x.nbytes for d in (self.fwd, self.bwd) for x in d[:2])


def prepare_gather_walk(
    g,
    n_src: int,
    *,
    interpret: bool | None = None,
    use_pallas: bool | None = None,
) -> GatherMap:
    """A destination→source row map ``g`` (int32 ``(n_dst,)``, ``-1`` = no
    link) as the gather walk's device operand.

    ``bwd`` probes destination rows: row ``j``'s segment is ``g[j]`` (empty
    where ``-1``).  ``fwd`` probes the ``n_src`` source rows: row ``i``'s
    segment is every ``j`` with ``g[j] = i``, ascending (the inverse map).
    ``use_pallas=None`` is :func:`lineage_gather`'s kernel-launch guard."""
    g = np.asarray(g, dtype=np.int64).reshape(-1)
    dst = np.flatnonzero(g >= 0)
    src = g[dst]
    counts = np.bincount(src, minlength=n_src)
    fwd_ptr = np.zeros(n_src + 1, np.int64)
    np.cumsum(counts, out=fwd_ptr[1:])
    bwd_ptr = np.zeros(len(g) + 1, np.int64)
    np.cumsum(g >= 0, out=bwd_ptr[1:])
    fwd_deg = _round_up(max(int(counts.max(initial=0)), 1), LANES)
    if use_pallas is None:
        use_pallas = on_tpu() and _gather_fits(len(src))
    if interpret is None:
        interpret = not on_tpu()

    def csr(ptr, col_idx, deg):
        return (jax.device_put(ptr.astype(np.int32)),
                jax.device_put(_gather_col_idx(col_idx)), deg)

    return GatherMap(
        fwd=csr(fwd_ptr, dst[np.argsort(src, kind="stable")], fwd_deg),
        bwd=csr(bwd_ptr, src, LANES), links=len(src),
        use_pallas=bool(use_pallas), interpret=bool(interpret))


def gather_walk(queries, gmap: GatherMap, direction: str):
    """Record probe rows through a prepared row map in ONE kernel launch
    (the :func:`lineage_gather` kernel, named ``gather_walk``).

    ``queries`` (Q,) int32 names probe rows of the ``direction``'s probed
    side (``-1`` = none).  Returns the ``(Q, deg)`` int32 table whose row q
    lists the rows that ``queries[q]`` reaches, then ``-1``; the caller
    ORs each row into its probe's answer."""
    ptr, col_p, deg = gmap.fwd if direction == "fwd" else gmap.bwd
    queries = jnp.asarray(queries, dtype=jnp.int32)
    _note_launch("gather_walk", gmap.use_pallas and not gmap.interpret)
    if not gmap.use_pallas:
        return _lineage_gather_oracle(queries, ptr, col_p, max_deg=deg)
    return _lineage_gather_call(queries, ptr, col_p, max_deg=deg,
                                interpret=gmap.interpret, name="gather_walk")
