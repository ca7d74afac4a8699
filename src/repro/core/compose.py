"""Einstein-summation composition of provenance chains (paper §IV).

For whole-dataset lineage (the paper's fairness/consent audits), chaining
``slice → project`` per record is wasteful; the paper instead contracts the
tensors of consecutive operations:

    T_1 ⊗ T_2 ⊗ ... ⊗ T_n   (contracting out_dim(T_j) = in_dim(T_{j+1}))

Over binary relations this is the (OR, AND) boolean semiring.  We realize it
as bit-packed boolean matmul — the :mod:`repro.kernels.bitmatmul` Pallas
kernel on TPU, its jnp oracle elsewhere — giving one (|D_src| × |D_dst|)
relation bitplane for the whole dataflow path.

Two chain orders are supported and chosen by a flop model:

* forward  (src→dst):  R = R_1 · R_2 · ... · R_n, accumulating left-to-right;
* backward (dst→src):  transposed accumulation right-to-left.

The associativity freedom matters: intermediate relation widths vary by orders
of magnitude (a filter shrinks, a join blows up).  ``plan_chain`` does the
classic matrix-chain dynamic program on the (rows, cols/32-word) dims.
"""
from __future__ import annotations

import warnings
from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.core.pipeline import OpRecord, ProvenanceIndex
from repro.core.provtensor import ProvTensor, pack_bitplane, unpack_bitplane

try:  # host-side sparse composition backend (the hop-cache default off-TPU)
    import scipy.sparse as _sp
except ImportError:  # pragma: no cover - environment-dependent
    _sp = None

__all__ = [
    "path_tensors",
    "op_bitplane",
    "op_csr",
    "op_gather",
    "resolve_use_pallas",
    "compose_pair",
    "compose_pair_csr",
    "compose_gather",
    "chain_gather",
    "extend_tail",
    "extend_tail_csr",
    "extend_tail_bitplane",
    "compose_chain",
    "plan_chain",
    "dataset_lineage",
    "HAVE_SCIPY",
]

HAVE_SCIPY = _sp is not None


def resolve_use_pallas(use_pallas: Optional[bool]) -> bool:
    """Resolve the tri-state kernel flag from the platform.

    ``None`` — the default everywhere above the kernel layer — means
    "Pallas iff this process runs on TPU" (:func:`repro.kernels.ops.on_tpu`).
    Explicit ``True`` off-TPU still works (interpret-mode emulation, the
    parity-test path) but is deprecated as a routing choice — emulation is
    never the faster backend — and warns.
    """
    from repro.kernels import ops as K

    if use_pallas is None:
        return K.on_tpu()
    if use_pallas:
        if not K.on_tpu():
            warnings.warn(
                "use_pallas=True off-TPU runs kernels in interpret mode; "
                "pass use_pallas=None to let the kernel-launch guard pick "
                "the backend (Pallas on TPU, the jnp oracle elsewhere)",
                DeprecationWarning,
                stacklevel=3,
            )
    return bool(use_pallas)


def path_tensors(index: ProvenanceIndex, src: str, dst: str) -> List[Tuple[OpRecord, int]]:
    """The op chain linking ``src`` to ``dst``: [(op, input_slot), ...].

    Follows the (unique-producer) dataflow backward from ``dst`` and keeps the
    ops on a path that reaches ``src``.  For multi-input ops the slot records
    WHICH input lies on the path.

    The reachable-from-``src`` set is computed ONCE up front (one pass over
    the op list) instead of re-running ``index.path_exists`` per visited op —
    the old per-hop rescans made this O(depth²) in pipeline length.
    """
    reach = {src}
    for op in index.ops:
        if any(d in reach for d in op.input_ids):
            reach.add(op.output_id)
    chain: List[Tuple[OpRecord, int]] = []
    cur = dst
    while cur != src:
        if cur not in index.producer:
            raise KeyError(f"no dataflow path {src} -> {dst} (stuck at {cur})")
        op = index.ops[index.producer[cur]]
        slot = None
        for k, in_id in enumerate(op.input_ids):
            if in_id in reach:
                slot = k
                break
        if slot is None:
            raise KeyError(f"no dataflow path {src} -> {dst} (op {op.info.op_name})")
        chain.append((op, slot))
        cur = op.input_ids[slot]
    return list(reversed(chain))


def op_bitplane(t: ProvTensor, slot: int) -> np.ndarray:
    """R[i, o] forward bitplane of one op tensor for one input slot
    (memoized on the tensor — the hop-cache recomposes from these)."""
    return t.bitplane_fwd(slot)


_relation_bitplane = op_bitplane  # backward-compat alias


def op_csr(t: ProvTensor, slot: int):
    """The same forward relation as scipy CSR — zero-copy view over the
    tensor's bidirectional index (shares row_ptr/col_idx).

    float32 values keep the boolean semiring exact under composition: path
    counts are sums of positives, so ``> 0`` never misclassifies (an integer
    dtype could overflow and wrap a count to zero).
    """
    if _sp is None:
        raise ImportError("scipy is required for the CSR composition backend")
    c = t.fwd(slot)
    data = np.ones(c.nnz, dtype=np.float32)
    return _sp.csr_matrix((data, c.col_idx, c.row_ptr), shape=(c.n_rows, c.n_cols))


def compose_pair_csr(a, b):
    """(OR,AND)-compose two CSR relations: sparse matmul, then clamp the
    path counts back to the binary relation."""
    c = (a @ b).tocsr()
    c.data = np.ones_like(c.data)
    return c


def op_gather(t: ProvTensor, slot: int) -> Optional[np.ndarray]:
    """The op relation's implicit destination→source gather (int32
    ``(n_out,)``, -1 = no link) when the slot is structured, else None."""
    return t.slot_gather(slot)


def compose_gather(g_pre: np.ndarray, g_step: np.ndarray) -> np.ndarray:
    """Closed-form ``prefix ∘ step`` over gather relations: ONE ``np.take``.

    ``g_pre`` maps mid→src, ``g_step`` maps dst→mid; the composition maps
    dst→src, propagating the -1 "no link" sentinel through both hops.
    Gather∘gather stays a gather, so a whole identity/selection chain folds
    without ever leaving the implicit representation.
    """
    valid = g_step >= 0
    return np.where(valid, g_pre[np.where(valid, g_step, 0)], np.int32(-1))


def chain_gather(chain: Sequence[Tuple[object, int]]) -> Optional[np.ndarray]:
    """Fold a whole op chain of structured slots into one dst→src gather;
    None when any hop lacks structure (a multi-parent raw-COO relation).
    Identity hops are eliminated outright (no take at all)."""
    from repro.core.provtensor import SlotIdentity  # local: avoid wide import

    acc: Optional[np.ndarray] = None  # None = identity so far
    for op, slot in chain:
        s = op.tensor.slot_structure(slot)
        if s is None:
            return None
        if isinstance(s, SlotIdentity):
            continue
        g = op.tensor.slot_gather(slot)
        acc = g if acc is None else compose_gather(acc, g)
    if acc is None and chain:
        acc = np.arange(chain[-1][0].tensor.n_out, dtype=np.int32)
    return acc


def extend_tail_csr(rel, g: np.ndarray):
    """Closed-form ``prefix ∘ gather-step`` for a CSR prefix: a column
    gather, NOT a sparse matmul.

    ``rel`` is the composed (n_src × n_mid) forward relation; ``g`` maps
    dst→mid (int32 (n_dst,), -1 = no link).  Since every dst column of the
    result is exactly one mid column of the prefix (or empty), the extension
    is ``out[:, d] = rel[:, g[d]]`` — one ragged gather over the prefix's
    CSC columns, O(nnz_out), no flops.  This is what makes appending a
    structured op to a DENSE warm relation cheap: the whole-chain recompose
    it replaces pays a full spmm per hop.
    """
    if _sp is None:
        raise ImportError("scipy is required for the CSR composition backend")
    g = np.asarray(g, dtype=np.int64).reshape(-1)
    csc = rel.tocsc()
    n_src = rel.shape[0]
    n_dst = len(g)
    valid = g >= 0
    cols = g[valid]
    starts = csc.indptr[cols].astype(np.int64)
    degs = (csc.indptr[cols + 1] - csc.indptr[cols]).astype(np.int64)
    total = int(degs.sum())
    indptr = np.zeros(n_dst + 1, dtype=np.int64)
    indptr[1:][valid] = degs
    np.cumsum(indptr, out=indptr)
    if total:
        flat = np.repeat(starts - np.concatenate(([0], np.cumsum(degs)[:-1])),
                         degs) + np.arange(total)
        indices = csc.indices[flat]
        data = csc.data[flat].copy()
    else:
        indices = np.zeros(0, dtype=csc.indices.dtype)
        data = np.zeros(0, dtype=csc.data.dtype)
    out = _sp.csc_matrix((data, indices, indptr), shape=(n_src, n_dst))
    return out.tocsr()


def extend_tail_bitplane(plane: np.ndarray, g: np.ndarray,
                         n_mid: int) -> np.ndarray:
    """Closed-form ``prefix ∘ gather-step`` for a packed-bitplane prefix:
    a column take through the dst→mid gather, blocked so the transient
    dense unpack stays ~4 MB regardless of relation size."""
    g = np.asarray(g, dtype=np.int64).reshape(-1)
    n_src = plane.shape[0]
    n_dst = len(g)
    valid = g >= 0
    safe = np.where(valid, g, 0)
    out = np.empty((n_src, (n_dst + 31) // 32), dtype=np.uint32)
    block = max(1, (4 << 20) // max(n_mid + n_dst, 1))
    for lo in range(0, max(n_src, 1), block):
        hi = min(lo + block, n_src)
        if hi <= lo:
            break
        dense = unpack_bitplane(plane[lo:hi], n_mid)
        out[lo:hi] = pack_bitplane(dense[:, safe] & valid[None, :])
    return out


def extend_tail(rel, g: np.ndarray, backend: str,
                n_mid: Optional[int] = None):
    """Dispatch the closed-form one-step extension by prefix backend
    (``"csr"`` | ``"bitplane"``); structured prefixes use
    :func:`compose_gather` directly and never come through here."""
    if backend == "csr":
        return extend_tail_csr(rel, g)
    if backend == "bitplane":
        if n_mid is None:
            raise ValueError("bitplane extension needs n_mid")
        return extend_tail_bitplane(rel, g, n_mid)
    raise ValueError(f"unknown backend {backend!r}")


def compose_pair(a_bits: np.ndarray, b_bits: np.ndarray, n_mid: int,
                 use_pallas: Optional[bool] = None) -> np.ndarray:
    """(OR,AND)-compose packed relations A (R×mid) · B (mid×C) -> (R×C) packed.

    ``a_bits`` packs its columns (mid dim); ``b_bits`` is (mid, C/32).
    ``use_pallas=None`` lets :func:`repro.kernels.ops.bitmatmul` apply its
    kernel-launch guard (Pallas on TPU, jnp oracle elsewhere).
    """
    from repro.kernels import ops as K

    return np.asarray(K.bitmatmul(a_bits, b_bits, use_pallas=use_pallas))


def plan_chain(dims: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """DIMS-ONLY matrix-chain-order DP (legacy).  Kept for callers that only
    know shapes; :func:`compose_chain` now plans with the nnz-aware DP in
    :mod:`repro.core.costmodel` (``plan_chain_stats``), which costs merges by
    sparse-matmul work instead of dense dims.

    Input is relation shapes [(r0,c0),(r1,c1)..] where c_j == r_{j+1}.
    Returns the multiplication order as (i, j) merges over a working list —
    standard O(n^3) DP, n is tiny (pipeline length)."""
    n = len(dims)
    if n <= 1:
        return []
    p = [dims[0][0]] + [d[1] for d in dims]  # dimension vector
    INF = float("inf")
    cost = [[0.0] * n for _ in range(n)]
    split = [[0] * n for _ in range(n)]
    for length in range(2, n + 1):
        for i in range(0, n - length + 1):
            j = i + length - 1
            cost[i][j] = INF
            for k in range(i, j):
                c = cost[i][k] + cost[k + 1][j] + p[i] * p[k + 1] * p[j + 1]
                if c < cost[i][j]:
                    cost[i][j] = c
                    split[i][j] = k
    order: List[Tuple[int, int]] = []

    def emit(i: int, j: int) -> None:
        if i == j:
            return
        k = split[i][j]
        emit(i, k)
        emit(k + 1, j)
        order.append((i, k))  # merge [i..k] with [k+1..j]

    emit(0, n - 1)
    return order


def compose_chain(
    index: ProvenanceIndex,
    src: str,
    dst: str,
    use_pallas: Optional[bool] = None,
    optimize: bool = True,
) -> np.ndarray:
    """Packed (|src| × |dst|/32) relation bitplane for the whole path.

    ``use_pallas=None`` (default) applies the kernel-launch guard — see
    :func:`resolve_use_pallas`.  ``optimize=True`` applies the matrix-chain
    DP (associativity); otherwise left-to-right accumulation (the paper's
    literal chain)."""
    use_pallas = resolve_use_pallas(use_pallas)
    chain = path_tensors(index, src, dst)
    if not chain:
        n = index.datasets[src].n_rows
        return pack_bitplane(np.eye(n, dtype=bool))
    g = chain_gather(chain)
    if g is not None:
        # the whole path is structured: fold the gathers closed-form (one
        # take per non-identity hop) and expand to the packed plane once
        n_src = index.datasets[src].n_rows
        dense = np.zeros((n_src, len(g)), dtype=bool)
        dst_rows = np.flatnonzero(g >= 0)
        dense[g[dst_rows], dst_rows] = True
        return pack_bitplane(dense)
    planes = [_relation_bitplane(op.tensor, slot) for op, slot in chain]
    rowdims = [op.tensor.n_in[slot] for op, slot in chain]
    coldims = [op.tensor.n_out for op, _ in chain]

    if not optimize or len(planes) == 1:
        acc = planes[0]
        for j in range(1, len(planes)):
            acc = compose_pair(acc, planes[j], rowdims[j], use_pallas=use_pallas)
        return acc

    # Stats-propagating matrix-chain DP from the cost model, priced in THIS
    # executor's backend: the merges below run compose_pair (packed
    # bitplane), whose word-op cost scales with dims, so bitplane pricing —
    # which provably reduces to the classic dims DP — is the correct model
    # here.  The nnz-scaled spmm pricing binds where CSR composition
    # actually runs: CostModel.composed_estimate / the auto hop-cache.
    from repro.core.costmodel import RelStats, plan_chain_stats

    stats = [RelStats.from_slot(op.tensor, slot) for op, slot in chain]
    order = plan_chain_stats(stats, backend="bitplane")
    # working list of (plane, n_rows, n_cols)
    work: List[Optional[Tuple[np.ndarray, int, int]]] = [
        (planes[i], rowdims[i], coldims[i]) for i in range(len(planes))
    ]

    for (i, _k) in order:
        # merge segment starting at i with the next live segment to its right
        j = i + 1
        while work[j] is None:
            j += 1
        a, ra, ca = work[i]
        b, rb, cb = work[j]
        merged = compose_pair(a, b, ca, use_pallas=use_pallas)
        work[i] = (merged, ra, cb)
        work[j] = None
    final = next(w for w in work if w is not None)
    return final[0]


def dataset_lineage(
    index: ProvenanceIndex, src: str, dst: str, use_pallas: Optional[bool] = None
) -> np.ndarray:
    """Dense bool (|src|, |dst|) lineage relation for the whole dataset —
    the paper's einsum use case (fairness / consent audits)."""
    bits = compose_chain(index, src, dst, use_pallas=use_pallas)
    return unpack_bitplane(bits, index.datasets[dst].n_rows)
