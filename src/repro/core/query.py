"""Provenance query processing (paper Section IV, Table VII: Q1-Q11).

This module is now two layers:

**The physical layer** (kept public, used by :mod:`repro.provenance`):
record-level queries chain ``project(slice(T, p_in, rows), p_out)`` hops —
realized as batched CSR probes (the optimized representation of §III-C) —
over the topologically-ordered op DAG; attribute-level queries additionally
thread (row-set x attr-set) terms through the Table-VI bitset maps.  It is
fully array-vectorized:

* attribute masks travel PACKED (uint32 words, 32 attrs per lane) and advance
  through an op via one select-OR contraction against the op's memoized
  attribute bitplane (:meth:`AttrMap.fwd_plane` / ``bwd_plane``) — no
  per-attribute rank/select dispatch;
* ``_cells`` materializes the union of (row-set × attr-set) products as a
  broadcasted outer product over packed masks, then one ``argwhere``;
* record-level hops through STRUCTURED op tensors (identities, selections,
  gathers, append blocks — the capture default) skip the CSR entirely: the
  per-op ``forward_mask_batch`` / ``backward_mask_batch`` dispatch to a
  take/scatter fast path on the implicit form, so a filter/gather-heavy
  walk allocates no per-op index at all;
* the batch walkers answer a whole probe batch in one pass — the per-op CSR
  gather covers all batch elements with a single ragged gather
  (:meth:`CSR.neighbor_mask_many`) — and can collect per-probe ``Hop``
  traces (``collect_hops=True``), so how-provenance (Q5-Q8) batches too.

**The legacy shims**: ``q1_forward`` … ``q11_co_dependency`` are THIN
DEPRECATION SHIMS over :mod:`repro.provenance` — each compiles its arguments
to a :class:`~repro.provenance.plan.QueryPlan` and executes it through the
index's shared :class:`~repro.provenance.session.QuerySession`, which owns
the hop-cache routing.  Prefer the builder::

    from repro.provenance import prov
    prov(index).source(src).rows([...]).forward().to(dst).run()

The shims keep the old single-vs-batch *guess* (:func:`is_probe_batch`) and
warn with :class:`~repro.provenance.plan.AmbiguousProbeWarning` on the
inputs where the guess is ambiguous (an empty list; a 1-D integer ndarray):
the builder's explicit ``.rows(...)`` / ``.rows_batch(...)`` never guesses.
"""
from __future__ import annotations

import dataclasses
import warnings
from collections import OrderedDict
from typing import Dict, List, Optional, Set, Tuple, Union

import jax
import numpy as np

from repro.core.opcat import AttrMap
from repro.core.pipeline import ProvenanceIndex
from repro.core.provtensor import (
    bitplane_or_reduce,
    pack_bitplane,
    pack_mask,
    unpack_bitplane,
)
from repro.core.spans import span

__all__ = [
    "Hop",
    "forward_record_masks",
    "backward_record_masks",
    "forward_record_masks_batch",
    "backward_record_masks_batch",
    "fused_walk_record_masks_batch",
    "WalkPlaneStore",
    "plane_store_budget",
    "record_masks_terms_batch",
    "attr_propagate_terms_batch",
    "q1_forward",
    "q2_backward",
    "q3_forward_attr",
    "q4_backward_attr",
    "q5_forward_how",
    "q6_backward_how",
    "q7_forward_attr_how",
    "q8_backward_attr_how",
    "q9_all_transformations",
    "q10_co_contributory",
    "q11_co_dependency",
]


@dataclasses.dataclass(frozen=True)
class Hop:
    """One op traversal — the *how* part of how-provenance (Q5-Q8)."""

    op_id: int
    op_name: str
    category: str
    src_dataset: str
    dst_dataset: str
    n_records: int


# ---------------------------------------------------------------------------
# Probe normalization: single probe vs batch of probes
# ---------------------------------------------------------------------------
def _as_mask(rows, n: int) -> np.ndarray:
    if isinstance(rows, np.ndarray):
        if rows.dtype == bool:
            return rows
        idx = rows.astype(np.int64, copy=False).reshape(-1)
    else:
        # no list() round-trip: consume any iterable of row indices directly
        idx = np.fromiter(rows, dtype=np.int64)
    m = np.zeros(n, dtype=bool)
    m[idx] = True
    return m


def is_probe_batch(rows) -> bool:
    """A batch is a 2-D mask stack or a non-empty list/tuple of probe sets."""
    if isinstance(rows, np.ndarray):
        return rows.ndim == 2
    if isinstance(rows, (list, tuple)):
        return len(rows) > 0 and all(
            isinstance(r, (list, tuple, np.ndarray, set, frozenset, range))
            for r in rows
        )
    return False


def _as_mask_batch(rows_batch, n: int) -> np.ndarray:
    if isinstance(rows_batch, np.ndarray) and rows_batch.ndim == 2:
        if rows_batch.dtype == bool:
            return rows_batch
        out = np.zeros((rows_batch.shape[0], n), dtype=bool)
        out[np.arange(rows_batch.shape[0])[:, None], rows_batch.astype(np.int64)] = True
        return out
    return np.stack([_as_mask(r, n) for r in rows_batch], axis=0)


def mask_rows(stack: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The probe and the row of each set entry of a ``(B, n)`` bool stack,
    in row-major order: ``np.divmod(np.flatnonzero(stack), n)``.  A sparse
    stack is scanned once, as 8-byte words, and only the words that hold a
    set entry are looked at byte by byte."""
    flat = np.ascontiguousarray(stack).reshape(-1)
    if flat.dtype == bool and flat.size % 8 == 0:
        words = np.flatnonzero(flat.view(np.uint64) != 0)
        word, byte = np.nonzero(flat.reshape(-1, 8)[words])
        ids = words[word] * 8 + byte
    else:
        ids = np.flatnonzero(flat)
    return np.divmod(ids, stack.shape[1])


# ---------------------------------------------------------------------------
# Record-level propagation (Q1/Q2 cores)
# ---------------------------------------------------------------------------
def forward_record_masks(
    index: ProvenanceIndex, src: str, rows, collect_hops: bool = False
) -> Tuple[Dict[str, np.ndarray], List[Hop]]:
    """Propagate a row mask from ``src`` to every reachable dataset."""
    masks: Dict[str, np.ndarray] = {src: _as_mask(rows, index.datasets[src].n_rows)}
    hops: List[Hop] = []
    for op in index.downstream_ops(src):
        out_n = op.tensor.n_out
        out_mask = masks.get(op.output_id, np.zeros(out_n, dtype=bool))
        for k, in_id in enumerate(op.input_ids):
            if in_id in masks and masks[in_id].any():
                contrib = op.tensor.forward_mask(k, masks[in_id])
                if collect_hops and contrib.any():
                    hops.append(
                        Hop(op.op_id, op.info.op_name, op.info.category.value,
                            in_id, op.output_id, int(contrib.sum()))
                    )
                out_mask |= contrib
        masks[op.output_id] = out_mask
    return masks, hops


def backward_record_masks(
    index: ProvenanceIndex, dst: str, rows, collect_hops: bool = False
) -> Tuple[Dict[str, np.ndarray], List[Hop]]:
    masks: Dict[str, np.ndarray] = {dst: _as_mask(rows, index.datasets[dst].n_rows)}
    hops: List[Hop] = []
    for op in reversed(index.upstream_ops(dst)):
        if op.output_id not in masks or not masks[op.output_id].any():
            continue
        for k, in_id in enumerate(op.input_ids):
            contrib = op.tensor.backward_mask(k, masks[op.output_id])
            if collect_hops and contrib.any():
                hops.append(
                    Hop(op.op_id, op.info.op_name, op.info.category.value,
                        op.output_id, in_id, int(contrib.sum()))
                )
            prev = masks.get(in_id, np.zeros(index.datasets[in_id].n_rows, dtype=bool))
            masks[in_id] = prev | contrib
    return masks, hops


def forward_record_masks_batch(
    index: ProvenanceIndex, src: str, rows_batch, collect_hops: bool = False
):
    """Batched :func:`forward_record_masks`: every value is (B, n_rows) bool.

    One pass over the op DAG answers all B probes — each hop is a single
    batched CSR gather, not B sequential walks.  With ``collect_hops`` the
    return is ``(masks, hops)`` where ``hops[b]`` is probe b's :class:`Hop`
    trace, identical to the single-probe trace (a hop is recorded for probe
    b iff that probe's contribution through the op is non-empty).
    """
    stack = _as_mask_batch(rows_batch, index.datasets[src].n_rows)
    masks: Dict[str, np.ndarray] = {src: stack}
    B = stack.shape[0]
    hops: List[List[Hop]] = [[] for _ in range(B)]
    for op in index.downstream_ops(src):
        out_mask = masks.get(op.output_id, np.zeros((B, op.tensor.n_out), dtype=bool))
        for k, in_id in enumerate(op.input_ids):
            if in_id in masks and masks[in_id].any():
                contrib = op.tensor.forward_mask_batch(k, masks[in_id])
                if collect_hops:
                    counts = contrib.sum(axis=1)
                    for b in np.flatnonzero(counts):
                        hops[b].append(
                            Hop(op.op_id, op.info.op_name, op.info.category.value,
                                in_id, op.output_id, int(counts[b]))
                        )
                out_mask = out_mask | contrib
        masks[op.output_id] = out_mask
    if collect_hops:
        return masks, hops
    return masks


def backward_record_masks_batch(
    index: ProvenanceIndex, dst: str, rows_batch, collect_hops: bool = False
):
    stack = _as_mask_batch(rows_batch, index.datasets[dst].n_rows)
    masks: Dict[str, np.ndarray] = {dst: stack}
    B = stack.shape[0]
    hops: List[List[Hop]] = [[] for _ in range(B)]
    for op in reversed(index.upstream_ops(dst)):
        if op.output_id not in masks or not masks[op.output_id].any():
            continue
        for k, in_id in enumerate(op.input_ids):
            contrib = op.tensor.backward_mask_batch(k, masks[op.output_id])
            if collect_hops:
                counts = contrib.sum(axis=1)
                for b in np.flatnonzero(counts):
                    hops[b].append(
                        Hop(op.op_id, op.info.op_name, op.info.category.value,
                            op.output_id, in_id, int(counts[b]))
                    )
            prev = masks.get(
                in_id, np.zeros((B, index.datasets[in_id].n_rows), dtype=bool)
            )
            masks[in_id] = prev | contrib
    if collect_hops:
        return masks, hops
    return masks


# ---------------------------------------------------------------------------
# Fused-kernel record walk (ROADMAP item 4)
# ---------------------------------------------------------------------------
def plane_store_budget(max_plane_bytes: int) -> int:
    """Device bytes a :class:`WalkPlaneStore` may keep: a quarter of the
    default device's ``bytes_limit``, or ``4 × max_plane_bytes`` where the
    device reports no memory (the CPU)."""
    stats = jax.devices()[0].memory_stats() or {}
    limit = stats.get("bytes_limit")
    return int(limit) // 4 if limit else 4 * max_plane_bytes


class WalkPlaneStore:
    """Least-recently-used fused-walk operands, resident on the device.

    Plane stacks (:func:`repro.kernels.ops.prepare_walk_planes`) are keyed
    by ``(direction, ((op_id, slot), ...))``, row maps
    (:func:`repro.kernels.ops.prepare_gather_walk`, both directions in one)
    by ``("gather", ((op_id, slot), ...))``.  The index is append-only and
    an op's tensor never changes (spilling swaps it for a fault proxy of
    the same content), so a chain's prepared operand stays exact for the
    index's life.  A store holds the operands of one ``use_pallas``
    routing.  It keeps its tally in the owner's ``counters``:
    ``walk_plane_hits``, ``walk_plane_misses``, ``walk_plane_evictions``
    and ``walk_plane_bytes``, the bytes resident.
    """

    def __init__(self, counters: Dict[str, int]) -> None:
        self.counters = counters
        self._stacks: "OrderedDict[tuple, object]" = OrderedDict()

    def get(self, key: tuple):
        prepared = self._stacks.get(key)
        if prepared is None:
            self.counters["walk_plane_misses"] += 1
            return None
        self._stacks.move_to_end(key)
        self.counters["walk_plane_hits"] += 1
        return prepared

    def keep(self, key: tuple, prepared, budget: int) -> None:
        """Keep ``prepared`` under ``budget`` bytes, evicting the least
        recently used; an operand larger than the budget is not kept."""
        if prepared.nbytes > budget:
            return
        self._stacks[key] = prepared
        self.counters["walk_plane_bytes"] += prepared.nbytes
        while self.counters["walk_plane_bytes"] > budget:
            _, old = self._stacks.popitem(last=False)
            self.counters["walk_plane_bytes"] -= old.nbytes
            self.counters["walk_plane_evictions"] += 1


def fused_walk_record_masks_batch(
    index: ProvenanceIndex,
    src: str,
    dst: str,
    rows_batch,
    direction: str = "fwd",
    use_pallas: Optional[bool] = None,
    max_plane_bytes: int = 256 << 20,
    counters: Optional[Dict[str, int]] = None,
    planes: Optional[WalkPlaneStore] = None,
    per_probe: bool = False,
) -> Optional[Union[np.ndarray, List[np.ndarray]]]:
    """``(B, n_dst)`` bool answered in ONE kernel launch, or None to fall back.

    The fused walk replaces the per-op pass only when the ``src``→``dst``
    dataflow is ONE linear chain: every op-slot that both receives mass
    from the upstream end and can pass it on to the downstream end must
    lie on the :func:`~repro.core.compose.path_tensors` chain.  Diamonds,
    self-joins and side entrances fail that audit and return None — the
    caller falls back to the full per-op walker (which sums over all
    paths).  A chain that passes is routed by structure first, then by
    size:

    1. a chain whose every hop is structured walks its composed row map
       (:func:`_gather_walk`, counted in ``counters["gather_walk"]``),
       whatever its dense planes would take;
    2. the fused :func:`repro.kernels.ops.batched_walk` takes an
       unstructured chain, or a structured one whose gather answer table
       would exceed :data:`repro.kernels.ops.GATHER_TABLE_BYTES` or the
       plane stack's own bytes (counted in
       ``counters["gather_walk_declined"]``), when the square-padded plane
       stack it streams fits ``max_plane_bytes``;
    3. any other chain returns None, and that refusal is counted in
       ``counters["fused_walk_oversize"]``, so a walk routed to the host
       for size is visible.

    ``direction="bwd"`` probes ``src`` (the downstream end) and answers at
    ``dst`` through the transposed planes of the reversed chain; forward
    and backward both return exactly the target dataset's mask stack of
    the corresponding full walker.  ``use_pallas=None`` is the
    kernel-launch guard: the fused Pallas kernel on TPU, the one-dispatch
    jnp oracle elsewhere.

    With a ``planes`` store, a chain that passed the audit and its route
    looks its device operand up there first: on a hit only the packed mask
    is put on the device; on a miss the planes are put, prepared and kept
    (:class:`WalkPlaneStore`, budget :func:`plane_store_budget`).

    The walk runs in the span ``walk.fused``, with the host-to-device copy
    of the packed mask (the row ids on the gather route) and, on a miss,
    the operand in ``walk.put`` and the wait for the answer in
    ``walk.wait`` (:mod:`repro.core.spans`); the gather route runs in
    ``walk.gather`` around both.  With a counter dict their nanoseconds
    accrue to ``fused_walk_ns``, ``gather_walk_ns``, ``walk_put_ns`` and
    ``walk_wait_ns``, and the bytes put to ``walk_put_bytes``.

    With ``per_probe`` the gather route answers a list of each probe's
    rows instead (``np.flatnonzero`` of each row of the stack), and makes
    no ``(B, n_dst)`` stack; the dense route answers the stack either way.
    """
    with span("walk.fused", counters, "fused_walk_ns"):
        return _fused_walk(index, src, dst, rows_batch, direction, use_pallas,
                           max_plane_bytes, counters, planes, per_probe)


def _fused_walk(index: ProvenanceIndex, src: str, dst: str, rows_batch,
                direction: str, use_pallas: Optional[bool],
                max_plane_bytes: int, counters: Optional[Dict[str, int]],
                planes: Optional[WalkPlaneStore], per_probe: bool):
    from repro.core.compose import path_tensors

    up, down = (src, dst) if direction == "fwd" else (dst, src)
    if up not in index.datasets or down not in index.datasets:
        return None
    try:
        chain = path_tensors(index, up, down)
    except KeyError:
        return None
    stack = _as_mask_batch(rows_batch, index.datasets[src].n_rows)
    if not chain:  # src == dst: the seed is the answer
        return stack.astype(bool, copy=True)

    # linearity audit: one forward and one backward closure over the
    # (topologically ordered) op list find every op-slot carrying mass from
    # `up` toward `down`; the chain is exact iff it covers all of them
    reach = {up}
    for op in index.ops:
        if any(d in reach for d in op.input_ids):
            reach.add(op.output_id)
    feeds = {down}
    for op in reversed(index.ops):
        if op.output_id in feeds:
            feeds.update(op.input_ids)
    relevant = {
        (op.op_id, k)
        for op in index.ops
        for k, in_id in enumerate(op.input_ids)
        if in_id in reach and op.output_id in feeds
    }
    if relevant != {(op.op_id, slot) for op, slot in chain}:
        return None

    from repro.kernels import ops as K

    # route by structure, then by size.  The fused kernel square-pads every
    # hop to one common dim, so its plane stack is sized before any
    # bitplane is made; a structured chain's answer table may take neither
    # the gather route's cap nor more than that stack
    n_max = max(
        max(op.tensor.n_in[slot], op.tensor.n_out) for op, slot in chain
    )
    plane_bytes = len(chain) * n_max * n_max // 8
    dense = plane_bytes <= max_plane_bytes
    # (a spilled tensor's proxy knows whether it is structured: nothing is
    # faulted back to route a chain whose operand is resident)
    if all(op.tensor.structured for op, _ in chain):
        table_bytes = K.GATHER_TABLE_BYTES
        if dense:
            table_bytes = min(table_bytes, plane_bytes)
        with span("walk.gather", counters, "gather_walk_ns"):
            out = _gather_walk(index, chain, stack, dst, direction,
                               use_pallas, max_plane_bytes, table_bytes,
                               counters, planes, per_probe)
        if out is not None:
            return out
        if dense and counters is not None:
            counters["gather_walk_declined"] += 1
    if not dense:
        if counters is not None:
            counters["fused_walk_oversize"] += 1
        return None
    return _dense_walk(index, chain, stack, dst, direction, use_pallas,
                       max_plane_bytes, counters, planes)


def _dense_walk(index: ProvenanceIndex, chain, stack: np.ndarray, dst: str,
                direction: str, use_pallas: Optional[bool],
                max_plane_bytes: int, counters: Optional[Dict[str, int]],
                planes: Optional[WalkPlaneStore]) -> np.ndarray:
    """The fused walk of a chain through its bit planes, in one
    :func:`repro.kernels.ops.batched_walk` launch; the operand is kept in
    ``planes`` under one key per direction."""
    from repro.kernels import ops as K

    key = (direction, tuple((op.op_id, slot) for op, slot in chain))
    prepared = planes.get(key) if planes is not None else None
    host_planes = []
    if prepared is None:
        if direction == "fwd":
            host_planes = [op.tensor.bitplane_fwd(slot) for op, slot in chain]
        else:
            host_planes = [op.tensor.bitplane_bwd(slot)
                           for op, slot in reversed(chain)]

    mask_bits = pack_bitplane(np.ascontiguousarray(stack))
    with span("walk.put", counters, "walk_put_ns"):
        mask_dev = jax.device_put(mask_bits)
        if prepared is None:
            prepared = K.prepare_walk_planes(
                [jax.device_put(p) for p in host_planes], use_pallas=use_pallas)
    if counters is not None:
        counters["walk_put_bytes"] += mask_bits.nbytes + sum(
            p.nbytes for p in host_planes)
    if host_planes and planes is not None:
        planes.keep(key, prepared, plane_store_budget(max_plane_bytes))
    out_bits, _counts = K.batched_walk_prepared(mask_dev, prepared)
    with span("walk.wait", counters, "walk_wait_ns"):
        out_host = np.asarray(out_bits)
    return unpack_bitplane(out_host, index.datasets[dst].n_rows)


def _gather_walk(index: ProvenanceIndex, chain, stack: np.ndarray, dst: str,
                 direction: str, use_pallas: Optional[bool],
                 max_plane_bytes: int, table_bytes: int,
                 counters: Optional[Dict[str, int]],
                 planes: Optional[WalkPlaneStore], per_probe: bool):
    """The fused walk of a chain of structured hops: its composed row map
    (:func:`repro.core.compose.chain_gather`), resident in ``planes`` under
    one key for both directions, probed by the probes' row ids
    (:func:`repro.kernels.ops.gather_walk`).  The mask stack crosses to the
    device as row ids and the answer comes back as rows, so nothing is
    packed on the host, and with ``per_probe`` the answer is each probe's
    rows, with no ``(B, n_dst)`` stack made.  None when the answer table
    would exceed ``table_bytes``: before any lookup or put where even a
    table one lane wide would.  With a counter dict, a plan adds its probe
    rows to ``gather_walk_rows``, the answer rows the table held to
    ``gather_walk_hits`` and the map's links to ``gather_walk_links``."""
    from repro.core.compose import chain_gather
    from repro.kernels import ops as K

    probe, rows = mask_rows(stack)
    # a few padded sizes, so that each compiles once
    q = max(K.GATHER_BLOCK_Q, 1 << (len(rows) - 1).bit_length())
    if q * K.LANES * 4 > table_bytes:
        return None
    key = ("gather", tuple((op.op_id, slot) for op, slot in chain))
    gmap = planes.get(key) if planes is not None else None
    queries = np.full(q, -1, dtype=np.int32)
    queries[:len(rows)] = rows
    put_bytes = queries.nbytes
    with span("walk.put", counters, "walk_put_ns"):
        queries_dev = jax.device_put(queries)
        if gmap is None:
            first, slot = chain[0]
            gmap = K.prepare_gather_walk(
                chain_gather(chain), first.tensor.n_in[slot],
                use_pallas=use_pallas)
            put_bytes += gmap.nbytes
            if planes is not None:
                planes.keep(key, gmap, plane_store_budget(max_plane_bytes))
    if counters is not None:
        counters["walk_put_bytes"] += put_bytes
    deg = (gmap.fwd if direction == "fwd" else gmap.bwd)[2]
    if q * deg * 4 > table_bytes:
        return None
    table_dev = K.gather_walk(queries_dev, gmap, direction)
    with span("walk.wait", counters, "walk_wait_ns"):
        table = np.asarray(table_dev)[:len(rows)]
    hit, col = np.nonzero(table >= 0)
    if counters is not None:
        counters["gather_walk"] += 1
        counters["gather_walk_rows"] += len(rows)
        counters["gather_walk_hits"] += len(hit)
        counters["gather_walk_links"] += gmap.links
    n_dst = index.datasets[dst].n_rows
    at, found = np.divmod(np.unique(probe[hit] * n_dst + table[hit, col]),
                          n_dst)
    if per_probe:
        # cut before each probe 0..B: the pieces between are probes 0..B-1
        cuts = np.searchsorted(at, np.arange(len(stack) + 1))
        return np.split(found, cuts)[1:-1]
    out = np.zeros((len(stack), n_dst), dtype=bool)
    out[at, found] = True
    return out


# ---------------------------------------------------------------------------
# Legacy Table-VII shims over repro.provenance (deprecated spellings)
# ---------------------------------------------------------------------------
_DEPRECATION_WARNED: Set[str] = set()


def _warn_deprecated(name: str, spelling: str) -> None:
    if name in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(name)
    warnings.warn(
        f"repro.core.query.{name} is deprecated; use "
        f"repro.provenance.prov(index){spelling}",
        DeprecationWarning,
        stacklevel=3,
    )


def _legacy_probe_is_batch(name: str, rows) -> bool:
    """The old :func:`is_probe_batch` guess, with an
    :class:`AmbiguousProbeWarning` on the spellings it cannot distinguish."""
    from repro.provenance import AmbiguousProbeWarning

    if isinstance(rows, (list, tuple)) and len(rows) == 0:
        warnings.warn(
            f"{name}: an empty probe [] is ambiguous (one empty probe set vs "
            "an empty batch) and takes the single-probe path; spell it "
            "prov(index)...rows([]) or .rows_batch([]) instead",
            AmbiguousProbeWarning,
            stacklevel=3,
        )
        return False
    if isinstance(rows, np.ndarray) and rows.ndim == 1 and rows.dtype != bool:
        warnings.warn(
            f"{name}: a 1-D integer ndarray probe is ambiguous (row indices "
            "vs a length-1 batch) and takes the single-probe (row-index) "
            "path; spell it prov(index)...rows(...) or .rows_batch(...) "
            "instead",
            AmbiguousProbeWarning,
            stacklevel=3,
        )
        return False
    return is_probe_batch(rows)


def _record_shim(index, name, spelling, start, rows, target, direction, how):
    from repro.provenance import prov

    _warn_deprecated(name, spelling)
    qb = prov(index).source(start)
    qb = qb.rows_batch(rows) if _legacy_probe_is_batch(name, rows) else qb.rows(rows)
    qb = qb.forward() if direction == "fwd" else qb.backward()
    if how:
        qb = qb.how()
    return qb.to(target).run()


def _cells_shim(index, name, spelling, start, rows, attrs, target, direction, how):
    from repro.provenance import prov

    _warn_deprecated(name, spelling)
    qb = prov(index).source(start)
    batched = _legacy_probe_is_batch(name, rows)
    qb = qb.rows_batch(rows) if batched else qb.rows(rows)
    if batched and is_probe_batch(attrs):
        qb = qb.attrs_batch(attrs)
    else:
        qb = qb.attrs(attrs)
    qb = qb.forward() if direction == "fwd" else qb.backward()
    if how:
        qb = qb.how()
    return qb.to(target).run()


def q1_forward(index: ProvenanceIndex, src: str, rows, dst: str):
    """Q1: records in ``dst`` derived from ``rows`` of ``src``.

    Deprecated shim — ``prov(index).source(src).rows(...).forward().to(dst)``.
    ``rows`` may be one probe set or a batch (list of sets); a batch returns
    a list of index arrays, answered in one vectorized pass.
    """
    return _record_shim(index, "q1_forward", ".source(src).rows(...).forward().to(dst)",
                        src, rows, dst, "fwd", how=False)


def q2_backward(index: ProvenanceIndex, dst: str, rows, src: str):
    """Q2: records in ``src`` that contributed to ``rows`` of ``dst``.

    Deprecated shim — ``prov(index).source(dst).rows(...).backward().to(src)``.
    """
    return _record_shim(index, "q2_backward", ".source(dst).rows(...).backward().to(src)",
                        dst, rows, src, "bwd", how=False)


def q5_forward_how(index: ProvenanceIndex, src: str, rows, dst: str):
    """Q5: Q1 plus the per-op :class:`Hop` trace.  Deprecated shim —
    ``prov(index).source(src).rows(...).forward().to(dst).how()``.  Batch
    probes (new) return one ``(records, hops)`` pair per probe."""
    return _record_shim(index, "q5_forward_how",
                        ".source(src).rows(...).forward().to(dst).how()",
                        src, rows, dst, "fwd", how=True)


def q6_backward_how(index: ProvenanceIndex, dst: str, rows, src: str):
    """Q6: Q2 plus the hop trace.  Deprecated shim —
    ``prov(index).source(dst).rows(...).backward().to(src).how()``."""
    return _record_shim(index, "q6_backward_how",
                        ".source(dst).rows(...).backward().to(src).how()",
                        dst, rows, src, "bwd", how=True)


# ---------------------------------------------------------------------------
# Attribute maps (Table VI bitsets -> per-op attr propagation)
#
# An attr mask is PACKED uint32 words; one op hop is a select-OR contraction
# of the packed mask against the op's memoized attribute bitplane.
# ---------------------------------------------------------------------------
def _attrs_forward(amap: AttrMap, attrs: np.ndarray, n_out_attrs: int) -> np.ndarray:
    """Map an input-attr mask to the output-attr mask through one op input."""
    attrs = np.asarray(attrs, dtype=bool)
    plane = amap.fwd_plane(attrs.shape[0], n_out_attrs)
    words = bitplane_or_reduce(pack_mask(attrs)[None, :], plane, attrs.shape[0])
    return unpack_bitplane(words, n_out_attrs)[0]


def _attrs_backward(amap: AttrMap, attrs: np.ndarray, n_in_attrs: int) -> np.ndarray:
    attrs = np.asarray(attrs, dtype=bool)
    plane = amap.bwd_plane(n_in_attrs, attrs.shape[0])
    words = bitplane_or_reduce(pack_mask(attrs)[None, :], plane, attrs.shape[0])
    return unpack_bitplane(words, n_in_attrs)[0]


# ---------------------------------------------------------------------------
# Attribute-level queries (Q3/Q4/Q7/Q8): (row-mask, packed-attr-words) terms
# ---------------------------------------------------------------------------
def _attr_propagate(
    index: ProvenanceIndex, start: str, rows, attrs, direction: str,
    collect_hops: bool = False,
):
    ds0 = index.datasets[start]
    terms: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {
        start: [(_as_mask(rows, ds0.n_rows), pack_mask(_as_mask(attrs, ds0.n_cols)))]
    }
    hops: List[Hop] = []
    ops = (
        index.downstream_ops(start)
        if direction == "fwd"
        else list(reversed(index.upstream_ops(start)))
    )
    for op in ops:
        out_ds = index.datasets[op.output_id]
        if direction == "fwd":
            for k, in_id in enumerate(op.input_ids):
                in_ds = index.datasets[in_id]
                plane = op.info.attr_maps[k].fwd_plane(in_ds.n_cols, out_ds.n_cols)
                for (rm, aw) in terms.get(in_id, []):
                    if not rm.any():
                        continue
                    new_rm = op.tensor.forward_mask(k, rm)
                    new_aw = bitplane_or_reduce(aw[None, :], plane, in_ds.n_cols)[0]
                    if new_rm.any() and new_aw.any():
                        terms.setdefault(op.output_id, []).append((new_rm, new_aw))
                        if collect_hops:
                            hops.append(Hop(op.op_id, op.info.op_name,
                                            op.info.category.value, in_id,
                                            op.output_id, int(new_rm.sum())))
        else:
            for (rm, aw) in terms.get(op.output_id, []):
                if not rm.any():
                    continue
                for k, in_id in enumerate(op.input_ids):
                    in_ds = index.datasets[in_id]
                    plane = op.info.attr_maps[k].bwd_plane(in_ds.n_cols, out_ds.n_cols)
                    new_rm = op.tensor.backward_mask(k, rm)
                    new_aw = bitplane_or_reduce(aw[None, :], plane, out_ds.n_cols)[0]
                    if new_rm.any() and new_aw.any():
                        terms.setdefault(in_id, []).append((new_rm, new_aw))
                        if collect_hops:
                            hops.append(Hop(op.op_id, op.info.op_name,
                                            op.info.category.value, op.output_id,
                                            in_id, int(new_rm.sum())))
    return terms, hops


def _ops_from_entries(index: ProvenanceIndex, entries, direction: str):
    """Ops reachable from ANY entry dataset, in traversal order.

    Op registration order is topological, so sorting the union by ``op_id``
    (descending for ``"bwd"``) reproduces the single-entry walk order
    exactly — a one-entry seed walks the identical op sequence as
    ``downstream_ops`` / ``reversed(upstream_ops)``.
    """
    by_id = {}
    for ds in entries:
        ops = (index.downstream_ops(ds) if direction == "fwd"
               else index.upstream_ops(ds))
        for op in ops:
            by_id[op.op_id] = op
    out = [by_id[i] for i in sorted(by_id)]
    if direction == "bwd":
        out.reverse()
    return out


def attr_propagate_terms_batch(
    index: ProvenanceIndex, entry_terms, direction: str,
    collect_hops: bool = False,
):
    """Term propagation seeded at ARBITRARY datasets (federated segments).

    ``entry_terms`` maps dataset id -> list of ``((B, n_rows) bool,
    (B, nw) uint32)`` already-packed terms (:func:`pack_bitplane` words).
    The per-op semantics are identical to :func:`_attr_propagate_batch` —
    a single-entry seed reproduces it term-for-term — but the walk covers
    every op reachable from ANY entry, so a federation can hand one member
    all of its boundary entries at once and read terms off every exit in
    one pass (hop traces then match a merged index's single walk instead
    of duplicating shared ops per entry/exit pair).

    Returns ``(terms, B, hops)`` with ``collect_hops``, else ``(terms, B)``.
    """
    terms: Dict[str, List[Tuple[np.ndarray, np.ndarray]]] = {
        ds: list(ts) for ds, ts in entry_terms.items() if ts
    }
    if not terms:
        raise ValueError("attr_propagate_terms_batch needs at least one "
                         "non-empty entry term list")
    B = next(iter(terms.values()))[0][0].shape[0]
    hops: List[List[Hop]] = [[] for _ in range(B)]

    def _trace(op, src_id, dst_id, new_rm, new_aw):
        counts = new_rm.sum(axis=1)
        live = counts.astype(bool) & new_aw.any(axis=1)
        for b in np.flatnonzero(live):
            hops[b].append(Hop(op.op_id, op.info.op_name, op.info.category.value,
                               src_id, dst_id, int(counts[b])))

    for op in _ops_from_entries(index, list(terms), direction):
        out_ds = index.datasets[op.output_id]
        if direction == "fwd":
            for k, in_id in enumerate(op.input_ids):
                in_ds = index.datasets[in_id]
                plane = op.info.attr_maps[k].fwd_plane(in_ds.n_cols, out_ds.n_cols)
                for (rm, aw) in terms.get(in_id, []):
                    if not rm.any():
                        continue
                    new_rm = op.tensor.forward_mask_batch(k, rm)
                    new_aw = bitplane_or_reduce(aw, plane, in_ds.n_cols)
                    if new_rm.any() and new_aw.any():
                        terms.setdefault(op.output_id, []).append((new_rm, new_aw))
                        if collect_hops:
                            _trace(op, in_id, op.output_id, new_rm, new_aw)
        else:
            for (rm, aw) in terms.get(op.output_id, []):
                if not rm.any():
                    continue
                for k, in_id in enumerate(op.input_ids):
                    in_ds = index.datasets[in_id]
                    plane = op.info.attr_maps[k].bwd_plane(in_ds.n_cols, out_ds.n_cols)
                    new_rm = op.tensor.backward_mask_batch(k, rm)
                    new_aw = bitplane_or_reduce(aw, plane, out_ds.n_cols)
                    if new_rm.any() and new_aw.any():
                        terms.setdefault(in_id, []).append((new_rm, new_aw))
                        if collect_hops:
                            _trace(op, op.output_id, in_id, new_rm, new_aw)
    if collect_hops:
        return terms, B, hops
    return terms, B


def _attr_propagate_batch(
    index: ProvenanceIndex, start: str, rows_batch, attrs_batch, direction: str,
    collect_hops: bool = False,
):
    """Batched term propagation: every term is ((B, n_rows) bool, (B, nw) u32).

    A term stays alive while ANY batch element is non-empty; per-element
    emptiness zeroes that element's masks, which contributes nothing to the
    final outer product — exactly the single-probe pruning, batched.

    With ``collect_hops`` the return gains a per-probe :class:`Hop` trace
    (``hops[b]``): a hop is recorded for probe b iff probe b's term survives
    the op with non-empty row AND attr masks — matching the single-probe
    :func:`_attr_propagate` trace exactly.
    """
    ds0 = index.datasets[start]
    rm0 = _as_mask_batch(rows_batch, ds0.n_rows)
    B = rm0.shape[0]
    am0 = _as_mask_batch(attrs_batch, ds0.n_cols) if is_probe_batch(attrs_batch) \
        else np.broadcast_to(_as_mask(attrs_batch, ds0.n_cols), (B, ds0.n_cols))
    entry = {start: [(rm0, pack_bitplane(np.ascontiguousarray(am0)))]}
    return attr_propagate_terms_batch(index, entry, direction,
                                      collect_hops=collect_hops)


def record_masks_terms_batch(
    index: ProvenanceIndex, entry_masks, direction: str,
    collect_hops: bool = False,
):
    """Record propagation seeded at ARBITRARY datasets (federated segments).

    ``entry_masks`` maps dataset id -> ``(B, n_rows)`` bool probe stacks.
    The multi-seed twin of :func:`forward_record_masks_batch` /
    :func:`backward_record_masks_batch`: one pass over every op reachable
    from any entry (registration order is topological), per-probe hop
    traces identical to the single-entry walkers.  Returns
    ``(masks, hops)`` with ``collect_hops``, else ``masks``.
    """
    masks: Dict[str, np.ndarray] = {
        ds: np.asarray(m, dtype=bool) for ds, m in entry_masks.items()
    }
    if not masks:
        raise ValueError("record_masks_terms_batch needs at least one entry")
    B = next(iter(masks.values())).shape[0]
    hops: List[List[Hop]] = [[] for _ in range(B)]
    for op in _ops_from_entries(index, list(masks), direction):
        if direction == "fwd":
            out_mask = masks.get(op.output_id,
                                 np.zeros((B, op.tensor.n_out), dtype=bool))
            for k, in_id in enumerate(op.input_ids):
                if in_id in masks and masks[in_id].any():
                    contrib = op.tensor.forward_mask_batch(k, masks[in_id])
                    if collect_hops:
                        counts = contrib.sum(axis=1)
                        for b in np.flatnonzero(counts):
                            hops[b].append(
                                Hop(op.op_id, op.info.op_name,
                                    op.info.category.value, in_id,
                                    op.output_id, int(counts[b]))
                            )
                    out_mask = out_mask | contrib
            masks[op.output_id] = out_mask
        else:
            if op.output_id not in masks or not masks[op.output_id].any():
                continue
            for k, in_id in enumerate(op.input_ids):
                contrib = op.tensor.backward_mask_batch(k, masks[op.output_id])
                if collect_hops:
                    counts = contrib.sum(axis=1)
                    for b in np.flatnonzero(counts):
                        hops[b].append(
                            Hop(op.op_id, op.info.op_name,
                                op.info.category.value, op.output_id, in_id,
                                int(counts[b]))
                        )
                prev = masks.get(
                    in_id,
                    np.zeros((B, index.datasets[in_id].n_rows), dtype=bool),
                )
                masks[in_id] = prev | contrib
    if collect_hops:
        return masks, hops
    return masks


def _cells(
    terms: List[Tuple[np.ndarray, np.ndarray]], n_rows: int, n_cols: int
) -> np.ndarray:
    """Union of (rows × attrs) products -> (n, 2) sorted unique cell list.

    Broadcasted outer product on PACKED attr words: scatter each term's packed
    attr mask into the rows its row-mask selects, then unpack once."""
    nw = max((n_cols + 31) // 32, 1)
    acc = np.zeros((n_rows, nw), dtype=np.uint32)
    for rm, aw in terms:
        acc[rm] |= aw[None, :]
    return np.argwhere(unpack_bitplane(acc, n_cols)).astype(np.int64)


def _cells_batch(
    terms: List[Tuple[np.ndarray, np.ndarray]], B: int, n_rows: int, n_cols: int
) -> List[np.ndarray]:
    nw = max((n_cols + 31) // 32, 1)
    acc = np.zeros((B, n_rows, nw), dtype=np.uint32)
    for rm, aw in terms:
        acc |= np.where(rm[:, :, None], aw[:, None, :], np.uint32(0))
    return [np.argwhere(unpack_bitplane(acc[b], n_cols)).astype(np.int64)
            for b in range(B)]


def q3_forward_attr(index, src: str, rows, attrs, dst: str):
    """Q3: attribute values (cells) of ``dst`` derived from the given cells.

    Deprecated shim —
    ``prov(index).source(src).rows(...).attrs(...).forward().to(dst)``.
    Batched when ``rows`` (and optionally ``attrs``) is a list of probe sets:
    returns one cell list per probe."""
    return _cells_shim(index, "q3_forward_attr",
                       ".source(src).rows(...).attrs(...).forward().to(dst)",
                       src, rows, attrs, dst, "fwd", how=False)


def q4_backward_attr(index, dst: str, rows, attrs, src: str):
    """Q4: source cells the given ``dst`` cells derive from.  Deprecated shim
    — ``prov(index).source(dst).rows(...).attrs(...).backward().to(src)``."""
    return _cells_shim(index, "q4_backward_attr",
                       ".source(dst).rows(...).attrs(...).backward().to(src)",
                       dst, rows, attrs, src, "bwd", how=False)


def q7_forward_attr_how(index, src: str, rows, attrs, dst: str):
    """Q7: Q3 plus the hop trace.  Deprecated shim — Q3's spelling + ``.how()``.
    Batch probes (new) return one ``(cells, hops)`` pair per probe."""
    return _cells_shim(index, "q7_forward_attr_how",
                       ".source(src).rows(...).attrs(...).forward().to(dst).how()",
                       src, rows, attrs, dst, "fwd", how=True)


def q8_backward_attr_how(index, dst: str, rows, attrs, src: str):
    """Q8: Q4 plus the hop trace.  Deprecated shim — Q4's spelling + ``.how()``."""
    return _cells_shim(index, "q8_backward_attr_how",
                       ".source(dst).rows(...).attrs(...).backward().to(src).how()",
                       dst, rows, attrs, src, "bwd", how=True)


# ---------------------------------------------------------------------------
# Q9: all transformations applied to a dataset (metadata only — no tensors)
# ---------------------------------------------------------------------------
def q9_all_transformations(index: ProvenanceIndex, dataset: str) -> List[Dict]:
    """Deprecated shim — ``prov(index).source(dataset).transformations()``."""
    from repro.provenance import prov

    _warn_deprecated("q9_all_transformations", ".source(dataset).transformations()")
    return prov(index).source(dataset).transformations().run()


# ---------------------------------------------------------------------------
# Q10/Q11: co-contributory and co-dependency (forward + backward combos)
# ---------------------------------------------------------------------------
def _pick_via(index: ProvenanceIndex, d1: str, d2: str, fwd_masks, b=None) -> Optional[str]:
    """The naive default: the last forward-reached dataset that d2 also feeds."""
    candidates = [
        d for d, m in fwd_masks.items()
        if d != d1 and (m[b].any() if b is not None else m.any())
        and index.path_exists(d2, d)
    ]
    return candidates[-1] if candidates else None


def q10_co_contributory(
    index: ProvenanceIndex, d1: str, rows, d2: str, via: Optional[str] = None
):
    """Records of ``d2`` used together with ``rows`` of ``d1`` to create new
    records (in ``via``; defaults to any common descendant).  Deprecated shim
    — ``prov(index).source(d1).rows(...).co_contributory(d2, via=via)``."""
    from repro.provenance import prov

    _warn_deprecated("q10_co_contributory",
                     ".source(d1).rows(...).co_contributory(d2, via=via)")
    qb = prov(index).source(d1)
    qb = (qb.rows_batch(rows)
          if _legacy_probe_is_batch("q10_co_contributory", rows) else qb.rows(rows))
    return qb.co_contributory(d2, via=via).run()


def q11_co_dependency(
    index: ProvenanceIndex, d2: str, rows, d1: str, d3: str
):
    """Records of ``d3`` lineage-dependent on the ``d1`` records that
    generated ``rows`` of ``d2``.  Deprecated shim —
    ``prov(index).source(d2).rows(...).co_dependency(d1, d3)``."""
    from repro.provenance import prov

    _warn_deprecated("q11_co_dependency", ".source(d2).rows(...).co_dependency(d1, d3)")
    qb = prov(index).source(d2)
    qb = (qb.rows_batch(rows)
          if _legacy_probe_is_batch("q11_co_dependency", rows) else qb.rows(rows))
    return qb.co_dependency(d1, d3).run()
