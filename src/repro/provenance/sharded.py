"""Mesh-sharded provenance index (ROADMAP item 1).

One :class:`~repro.core.pipeline.ProvenanceIndex` holds the whole pipeline's
provenance on one host.  This module partitions that index across ``S``
shards by CONTIGUOUS OUTPUT-ROW RANGE — the same
:func:`~repro.core.provtensor.shard_ranges` layout for every dataset, every
op tensor, and every composed hop-cache relation — and re-runs the batched
mask walkers as per-shard work joined by two collectives:

* **forward hop** — probe masks are replicated (``(B, n_in)`` is the small
  side); each shard propagates through its row-sliced tensor
  (:meth:`~repro.core.provtensor.ProvTensor.slice_rows`) producing the
  ``(B, hi-lo)`` slice of the output mask; the full stack is the
  range-ordered CONCATENATION over shards (``all_gather`` on a mesh).
* **backward hop** — each shard scatters its local ``(B, hi-lo)`` output
  slice to the full input space; the answer is the OR over shards
  (``psum > 0`` on a mesh).

Because OR over the shard contributions IS the full relation, both joins
are byte-identical to the merged single-host walk — the differential parity
suite (``tests/test_sharded_parity.py``) pins this at 1/2/4/8 shards across
every plan kind.

Two execution engines share that contract:

* ``"collective"`` — real ``jax.shard_map`` collectives over a 1-D device
  mesh (:func:`~repro.launch.mesh.make_shard_mesh`; multi-device CPU via
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
* ``"numpy"`` — a sequential per-shard loop with the identical join
  algebra; the fallback wherever the host exposes fewer devices than
  shards, and the reference the parity suite compares against itself.

:class:`ShardedProvenanceIndex` is a VIEW over the base index — datasets,
DAG structure, and attribute maps are shared; only the per-op tensors are
re-dressed as :class:`ShardedTensor`.  The standard
:class:`~repro.provenance.session.QuerySession` therefore runs every plan
kind (record / cells / co-queries / how-traces) over the view unchanged,
and :class:`ShardedComposedIndex` gives it a hop-cache whose entries are
per-shard relation BLOCKS (``(n_src, hi-lo)`` each), composed right-to-left
from the dst-sliced last hop so intermediates stay shard-local, with the
per-shard storage backend chosen from SHARD-LOCAL nnz
(:meth:`~repro.core.costmodel.RelStats.from_slot_range`).

Federation seam — :meth:`ShardedProvenanceIndex.as_catalog` registers each
shard as a :class:`~repro.provenance.catalog.ProvCatalog` member holding its
composed ``src → dst`` block as ONE recorded op, stitched by range-alignment
links (``alignment[j] = j - lo`` inside the shard's range, ``-1`` outside)
into a full-width gather member.  Cross-shard forward/backward probes then
ride the PR 4 federation machinery — segment walk, multi-link OR, stitched
cross-relation cache — completely unchanged.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.compose import (
    HAVE_SCIPY,
    chain_gather,
    compose_pair_csr,
    op_csr,
    path_tensors,
)
from repro.core.costmodel import (
    DENSITY_THRESHOLD,
    CostModel,
    RelStats,
    compose_est,
)
from repro.core.pipeline import ProvenanceIndex
from repro.core.provtensor import ProvTensor, shard_ranges

__all__ = [
    "ShardedTensor",
    "ShardedProvenanceIndex",
    "ShardedComposedIndex",
]


# ---------------------------------------------------------------------------
# The shard_map collective engine
# ---------------------------------------------------------------------------
class _CollectiveEngine:
    """Batched mask hops as ``shard_map`` collectives over a 1-D mesh.

    Per (tensor, slot) the valid link pairs of every shard pad to one
    ``(S, L)`` block (the mesh needs equal block sizes); a forward hop is a
    per-device gather+scatter followed by ``all_gather``, a backward hop a
    scatter into the full input space followed by ``psum``.  Compiled
    executables memoize on the shape tuple."""

    def __init__(self, mesh, axis: str = "shards") -> None:
        self.mesh = mesh
        self.axis = axis
        self._fwd = {}
        self._bwd = {}

    @property
    def n_devices(self) -> int:
        return int(np.prod(self.mesh.devices.shape))

    @staticmethod
    def _shard_map(fn, **kwargs):
        # The outputs are replicated BY CONSTRUCTION (all_gather / psum),
        # but the static replication checker cannot see through the
        # scatter ops, so it must be disabled.
        import jax

        return jax.shard_map(fn, **kwargs, check_vma=False)

    def _padded(self, st: "ShardedTensor", inp: int):
        """(out_idx, in_idx, valid) int32/bool ``(S, L)`` blocks + widths."""
        cache = st._collective
        if inp not in cache:
            pairs = []
            for shard in st.shards:
                out, inn = shard._slot_pairs(inp)
                out = np.asarray(out, dtype=np.int32)
                inn = np.asarray(inn, dtype=np.int32)
                keep = (out >= 0) & (inn >= 0)
                pairs.append((out[keep], inn[keep]))
            S = len(pairs)
            L = max(1, max(len(o) for o, _ in pairs))
            out_idx = np.zeros((S, L), dtype=np.int32)
            in_idx = np.zeros((S, L), dtype=np.int32)
            valid = np.zeros((S, L), dtype=bool)
            for s, (o, i) in enumerate(pairs):
                out_idx[s, : len(o)] = o
                in_idx[s, : len(o)] = i
                valid[s, : len(o)] = True
            widths = [hi - lo for lo, hi in st.ranges]
            cache[inp] = (out_idx, in_idx, valid, widths, max(max(widths), 1))
        return cache[inp]

    def _fwd_fn(self, S: int, L: int, Pw: int, B: int, n_in: int):
        key = (S, L, Pw, B, n_in)
        if key not in self._fwd:
            import jax
            import jax.numpy as jnp
            from jax.sharding import PartitionSpec as P

            axis = self.axis

            def local(masks, out_idx, in_idx, valid):
                o, i, v = out_idx[0], in_idx[0], valid[0]
                vals = masks[:, i] & v[None, :]
                loc = jnp.zeros((B, Pw), dtype=bool).at[:, o].max(vals)
                return jax.lax.all_gather(loc, axis)

            f = self._shard_map(
                local, mesh=self.mesh,
                in_specs=(P(), P(axis), P(axis), P(axis)),
                out_specs=P())
            self._fwd[key] = jax.jit(f)
        return self._fwd[key]

    def _bwd_fn(self, S: int, L: int, Pw: int, B: int, n_in: int):
        key = (S, L, Pw, B, n_in)
        if key not in self._bwd:
            import jax
            import jax.numpy as jnp
            from jax.sharding import PartitionSpec as P

            axis = self.axis

            def local(local_masks, out_idx, in_idx, valid):
                lm, o, i, v = local_masks[0], out_idx[0], in_idx[0], valid[0]
                vals = lm[:, o] & v[None, :]
                contrib = jnp.zeros((B, n_in), dtype=bool).at[:, i].max(vals)
                return jax.lax.psum(contrib.astype(jnp.int32), axis) > 0

            f = self._shard_map(
                local, mesh=self.mesh,
                in_specs=(P(axis), P(axis), P(axis), P(axis)),
                out_specs=P())
            self._bwd[key] = jax.jit(f)
        return self._bwd[key]

    def forward(self, st: "ShardedTensor", inp: int,
                masks: np.ndarray) -> np.ndarray:
        out_idx, in_idx, valid, widths, Pw = self._padded(st, inp)
        S, L = out_idx.shape
        B = masks.shape[0]
        fn = self._fwd_fn(S, L, Pw, B, st.n_in[inp])
        gathered = np.asarray(fn(masks, out_idx, in_idx, valid))  # (S, B, Pw)
        return np.concatenate(
            [gathered[s, :, :w] for s, w in enumerate(widths)], axis=1)

    def backward(self, st: "ShardedTensor", inp: int,
                 masks: np.ndarray) -> np.ndarray:
        out_idx, in_idx, valid, widths, Pw = self._padded(st, inp)
        S, L = out_idx.shape
        B = masks.shape[0]
        local = np.zeros((S, B, Pw), dtype=bool)
        for s, (lo, hi) in enumerate(st.ranges):
            local[s, :, : hi - lo] = masks[:, lo:hi]
        fn = self._bwd_fn(S, L, Pw, B, st.n_in[inp])
        return np.asarray(fn(local, out_idx, in_idx, valid))


# ---------------------------------------------------------------------------
# Row-range-sharded tensors and the op/index views over them
# ---------------------------------------------------------------------------
class ShardedTensor:
    """One op tensor partitioned into row-range shards, answering the full
    :class:`ProvTensor` mask surface through the shard join algebra.
    Slot statistics and lazy mirrors delegate to the base tensor (they
    describe the SAME relation)."""

    def __init__(self, base: ProvTensor, n_shards: int,
                 engine: Optional[_CollectiveEngine] = None) -> None:
        self.base = base
        self.n_shards = int(n_shards)
        self.ranges = shard_ranges(base.n_out, n_shards)
        self.shards = [base.slice_rows(lo, hi) for lo, hi in self.ranges]
        self.engine = engine
        self._collective: Dict = {}      # engine pads, keyed by slot

    # -- delegated shape / statistics / mirrors ------------------------------
    @property
    def n_out(self) -> int:
        return self.base.n_out

    @property
    def n_in(self) -> tuple:
        return self.base.n_in

    @property
    def k(self) -> int:
        return self.base.k

    @property
    def structured(self) -> bool:
        return self.base.structured

    @property
    def nnz(self) -> int:
        return self.base.nnz

    @property
    def coo(self) -> np.ndarray:
        return self.base.coo

    def slot_structure(self, inp: int):
        return self.base.slot_structure(inp)

    def slot_gather(self, inp: int):
        return self.base.slot_gather(inp)

    def slot_nnz(self, inp: int) -> int:
        return self.base.slot_nnz(inp)

    def slot_nnz_range(self, inp: int, lo: int, hi: int) -> int:
        return self.base.slot_nnz_range(inp, lo, hi)

    def slot_shape(self, inp: int) -> tuple:
        return self.base.slot_shape(inp)

    def slot_density(self, inp: int) -> float:
        return self.base.slot_density(inp)

    def _slot_pairs(self, inp: int):
        return self.base._slot_pairs(inp)

    def fwd(self, inp: int):
        return self.base.fwd(inp)

    def bwd(self, inp: int):
        return self.base.bwd(inp)

    def bitplane_fwd(self, inp: int) -> np.ndarray:
        return self.base.bitplane_fwd(inp)

    def bitplane_bwd(self, inp: int) -> np.ndarray:
        return self.base.bitplane_bwd(inp)

    def nbytes(self, include_index: bool = True) -> int:
        return self.base.nbytes(include_index)

    # -- the sharded mask hops ----------------------------------------------
    def forward_mask_batch(self, inp: int, in_masks: np.ndarray) -> np.ndarray:
        masks = np.asarray(in_masks, dtype=bool)
        if self.engine is not None:
            return self.engine.forward(self, inp, masks)
        return np.concatenate(
            [t.forward_mask_batch(inp, masks) for t in self.shards], axis=1)

    def backward_mask_batch(self, inp: int, out_masks: np.ndarray) -> np.ndarray:
        masks = np.asarray(out_masks, dtype=bool)
        if self.engine is not None:
            return self.engine.backward(self, inp, masks)
        out = np.zeros((masks.shape[0], self.n_in[inp]), dtype=bool)
        for (lo, hi), t in zip(self.ranges, self.shards):
            out |= t.backward_mask_batch(inp, masks[:, lo:hi])
        return out

    def forward_mask(self, inp: int, in_mask: np.ndarray) -> np.ndarray:
        return self.forward_mask_batch(
            inp, np.asarray(in_mask, dtype=bool)[None, :])[0]

    def backward_mask(self, inp: int, out_mask: np.ndarray) -> np.ndarray:
        return self.backward_mask_batch(
            inp, np.asarray(out_mask, dtype=bool)[None, :])[0]

    def forward_rows(self, inp: int, rows) -> np.ndarray:
        pieces = [t.forward_rows(inp, rows) + lo
                  for (lo, _), t in zip(self.ranges, self.shards)]
        return np.unique(np.concatenate(pieces)) if pieces else \
            np.zeros(0, dtype=np.int64)

    def backward_rows(self, inp: int, rows) -> np.ndarray:
        rows = np.asarray(list(rows) if not isinstance(rows, np.ndarray)
                          else rows)
        if rows.dtype == bool:
            rows = np.flatnonzero(rows)
        rows = rows.astype(np.int64).reshape(-1)
        rows = np.where(rows < 0, rows + self.n_out, rows)
        pieces = []
        for (lo, hi), t in zip(self.ranges, self.shards):
            local = rows[(rows >= lo) & (rows < hi)] - lo
            pieces.append(t.backward_rows(inp, local))
        return np.unique(np.concatenate(pieces)) if pieces else \
            np.zeros(0, dtype=np.int64)

    def __repr__(self) -> str:
        return (f"ShardedTensor(n_out={self.n_out}, n_in={self.n_in}, "
                f"shards={self.n_shards}, "
                f"engine={'collective' if self.engine else 'numpy'})")


@dataclasses.dataclass
class _ShardedOp:
    """Op-record view: same identity/metadata, sharded tensor."""

    op_id: int
    info: object
    tensor: ShardedTensor
    input_ids: List[str]
    output_id: str


# ---------------------------------------------------------------------------
# The sharded hop-cache
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class _ShardBlock:
    """One shard's ``(n_src, hi-lo)`` slice of a composed relation."""

    kind: str               # "csr" (scipy) | "dense" (bool ndarray)
    mat: object
    lo: int
    hi: int
    nnz: int
    _fwd_t: object = None   # (width, n_src) CSR mirror for forward probes

    def fwd_t(self):
        """The transposed CSR mirror — forward probes as a row-major spmm
        (CSC-orientation products are several times slower in scipy)."""
        if self._fwd_t is None:
            self._fwd_t = self.mat.T.tocsr()
        return self._fwd_t

    def nbytes(self) -> int:
        if self.kind == "dense":
            return int(self.mat.nbytes)
        return int(self.mat.data.nbytes + self.mat.indices.nbytes
                   + self.mat.indptr.nbytes)


@dataclasses.dataclass
class _ShardEntry:
    blocks: List[_ShardBlock]
    rows: int               # n_src
    cols: int               # n_dst
    nbytes: int


def _dense_rel(tensor: ProvTensor, slot: int) -> np.ndarray:
    """Dense bool (n_in, n_out) relation of one slot — the scipy-free
    composition fallback (small indexes only)."""
    out, inn = tensor._slot_pairs(slot)
    valid = (np.asarray(out) >= 0) & (np.asarray(inn) >= 0)
    dense = np.zeros((tensor.n_in[slot], tensor.n_out), dtype=bool)
    dense[np.asarray(inn)[valid], np.asarray(out)[valid]] = True
    return dense


class ShardedComposedIndex:
    """Hop-cache over a :class:`ShardedProvenanceIndex`: each ``(src, dst)``
    relation is held as per-shard column blocks.

    Blocks compose RIGHT-TO-LEFT from the dst-row-sliced last hop, so every
    intermediate is ``(n_i, hi-lo)`` — per-shard compose work scales with
    the shard's slice, not the full relation.  The per-shard storage backend
    (scipy CSR vs dense bool) follows the cost model's SHARD-LOCAL density
    estimate (:meth:`RelStats.from_slot_range` folded through
    :func:`compose_est`), so a shard whose range is dense can go dense while
    its sparse neighbors stay CSR.  Probes join exactly like the walkers:
    forward concatenates block answers in range order, backward ORs them.

    Same planner surface as :class:`~repro.core.hopcache.ComposedIndex`
    (``probe_forward`` / ``probe_backward`` / ``contains`` /
    ``memory_budget_bytes`` / ``costmodel`` / ``stats``), so
    ``QuerySession`` routes through it unchanged.  Append-safe for the same
    reason the merged hop-cache is: one producer per dataset means recorded
    appends cannot alter an existing pair's relation.
    """

    def __init__(self, sharded: "ShardedProvenanceIndex",
                 memory_budget_bytes: int = 64 << 20) -> None:
        self.sharded = sharded
        self.index = sharded          # planner surface parity (stats/name)
        self.memory_budget_bytes = int(memory_budget_bytes)
        self.costmodel = CostModel(sharded)
        self._cache: "OrderedDict[Tuple[str, str], _ShardEntry]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # -- composition ---------------------------------------------------------
    def _shard_chain_est(self, chain, lo: int, hi: int) -> RelStats:
        """Estimated stats of the composed relation restricted to dst rows
        ``[lo, hi)``: shard-local stats for the final hop, full-slot stats
        folded in for the upstream hops."""
        last_op, last_slot = chain[-1]
        acc = RelStats.from_slot_range(last_op.tensor, last_slot, lo, hi)
        for op, slot in reversed(chain[:-1]):
            acc = compose_est(RelStats.from_slot(op.tensor, slot), acc)
        return acc

    def _compose_block(self, chain, n_src: int, lo: int, hi: int,
                       g: Optional[np.ndarray]) -> _ShardBlock:
        width = hi - lo
        if g is not None:
            # fully structured chain: the closed-form dst→src gather, sliced
            # to this shard's window — O(width) work, no matmul at all
            gs = g[lo:hi]
            dst_local = np.flatnonzero(gs >= 0)
            src_rows = gs[dst_local]
            nnz = len(dst_local)
            if HAVE_SCIPY:
                import scipy.sparse as sp

                mat = sp.csr_matrix(
                    (np.ones(nnz, dtype=np.float32), (src_rows, dst_local)),
                    shape=(n_src, width))
                return _ShardBlock("csr", mat, lo, hi, nnz)
            dense = np.zeros((n_src, width), dtype=bool)
            dense[src_rows, dst_local] = True
            return _ShardBlock("dense", dense, lo, hi, nnz)
        est = self._shard_chain_est(chain, lo, hi)
        want_dense = (not HAVE_SCIPY) or est.density >= DENSITY_THRESHOLD
        last_op, last_slot = chain[-1]
        sliced = last_op.tensor.base.slice_rows(lo, hi) \
            if isinstance(last_op.tensor, ShardedTensor) \
            else last_op.tensor.slice_rows(lo, hi)
        if want_dense:
            acc = _dense_rel(sliced, last_slot)
            for op, slot in reversed(chain[:-1]):
                step = _dense_rel(
                    op.tensor.base if isinstance(op.tensor, ShardedTensor)
                    else op.tensor, slot)
                acc = (step.astype(np.uint8) @ acc.astype(np.uint8)) > 0
            return _ShardBlock("dense", acc, lo, hi,
                               int(np.count_nonzero(acc)))
        acc = op_csr(sliced, last_slot)
        for op, slot in reversed(chain[:-1]):
            base = op.tensor.base if isinstance(op.tensor, ShardedTensor) \
                else op.tensor
            acc = compose_pair_csr(op_csr(base, slot), acc)
        return _ShardBlock("csr", acc, lo, hi, int(acc.nnz))

    def _entry(self, src: str, dst: str) -> Optional[_ShardEntry]:
        key = (src, dst)
        entry = self._cache.get(key)
        if entry is not None:
            self.hits += 1
            self._cache.move_to_end(key)
            return entry
        base = self.sharded.base
        if src not in base.datasets or dst not in base.datasets:
            raise KeyError(f"unknown dataset in relation {src!r} -> {dst!r}")
        self.misses += 1
        n_src = base.datasets[src].n_rows
        n_dst = base.datasets[dst].n_rows
        try:
            chain = path_tensors(base, src, dst)
        except KeyError:
            return None
        ranges = shard_ranges(n_dst, self.sharded.n_shards)
        if not chain:           # src == dst: identity, sliced per shard
            g = np.arange(n_dst, dtype=np.int32)
        else:
            g = chain_gather(chain)
        blocks = [self._compose_block(chain, n_src, lo, hi, g)
                  for lo, hi in ranges]
        entry = _ShardEntry(blocks=blocks, rows=n_src, cols=n_dst,
                            nbytes=sum(b.nbytes() for b in blocks))
        self._cache[key] = entry
        self._bytes += entry.nbytes
        while self._bytes > self.memory_budget_bytes and len(self._cache) > 1:
            _, evicted = self._cache.popitem(last=False)
            self._bytes -= evicted.nbytes
            self.evictions += 1
        return entry

    # -- planner surface -----------------------------------------------------
    def contains(self, src: str, dst: str) -> bool:
        return (src, dst) in self._cache

    def probe_forward(self, masks, src: str, dst: str) -> np.ndarray:
        """(B, |src|) bool -> (B, |dst|): per-shard block probes concatenated
        in range order.  No path -> all-empty (the walkers' convention).
        The probe-mask transpose/float conversion is hoisted out of the
        per-block loop — it is the replicated input every shard shares."""
        masks = np.atleast_2d(np.asarray(masks, dtype=bool))
        entry = self._entry(src, dst)
        if entry is None:
            return np.zeros(
                (masks.shape[0],
                 self.sharded.base.datasets[dst].n_rows), dtype=bool)
        m_t = np.ascontiguousarray(masks.T, dtype=np.float32)
        return np.concatenate(
            [self._block_forward(b, m_t) for b in entry.blocks], axis=1)

    def probe_backward(self, masks, dst: str, src: str) -> np.ndarray:
        """(B, |dst|) bool -> (B, |src|): per-shard block probes OR-reduced."""
        masks = np.atleast_2d(np.asarray(masks, dtype=bool))
        entry = self._entry(src, dst)
        if entry is None:
            return np.zeros(
                (masks.shape[0],
                 self.sharded.base.datasets[src].n_rows), dtype=bool)
        m_t = np.ascontiguousarray(masks.T, dtype=np.float32)
        out = np.zeros((masks.shape[0], entry.rows), dtype=bool)
        for b in entry.blocks:
            out |= self._block_backward(b, m_t[b.lo: b.hi])
        return out

    @staticmethod
    def _block_forward(b: _ShardBlock, m_t: np.ndarray) -> np.ndarray:
        """``m_t``: the (n_src, B) float32 pre-transposed probe masks."""
        if b.kind == "dense":
            return (m_t.T @ b.mat) > 0
        return np.asarray((b.fwd_t() @ m_t).T) > 0

    @staticmethod
    def _block_backward(b: _ShardBlock, local_t: np.ndarray) -> np.ndarray:
        """``local_t``: this shard's (width, B) float32 output-slice masks."""
        if b.kind == "dense":
            return (local_t.T @ b.mat.T) > 0
        return np.asarray((b.mat @ local_t).T) > 0

    def relation_csr(self, src: str, dst: str):
        """The full composed relation reassembled from the shard blocks
        (scipy CSR) — parity checks and the federation hook."""
        if not HAVE_SCIPY:
            raise ImportError("relation_csr requires scipy")
        import scipy.sparse as sp

        entry = self._entry(src, dst)
        if entry is None:
            return sp.csr_matrix((self.sharded.base.datasets[src].n_rows,
                                  self.sharded.base.datasets[dst].n_rows),
                                 dtype=np.float32)
        mats = []
        for b in entry.blocks:
            mats.append(sp.csr_matrix(b.mat, dtype=np.float32)
                        if b.kind == "dense" else b.mat)
        return sp.hstack(mats, format="csr")

    def stats(self) -> Dict[str, object]:
        per_kind = {"csr": 0, "dense": 0}
        for entry in self._cache.values():
            for b in entry.blocks:
                per_kind[b.kind] += 1
        return {
            "index": self.sharded.name,
            "n_shards": self.sharded.n_shards,
            "entries": len(self._cache),
            "blocks_csr": per_kind["csr"],
            "blocks_dense": per_kind["dense"],
            "bytes": self._bytes,
            "budget_bytes": self.memory_budget_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
        }


# ---------------------------------------------------------------------------
# The sharded index view
# ---------------------------------------------------------------------------
class ShardedProvenanceIndex:
    """Row-range-sharded view over a :class:`ProvenanceIndex`.

    ``engine="auto"`` runs ``shard_map`` collectives when the host mesh has
    at least ``n_shards`` devices, else the sequential per-shard engine
    (identical answers).  The view tracks base appends: ops recorded on the
    base after construction are wrapped on next access."""

    def __init__(self, base: ProvenanceIndex, n_shards: int, *,
                 engine: str = "auto", mesh=None) -> None:
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.base = base
        self.n_shards = int(n_shards)
        self.engine_name, self._engine = self._make_engine(engine, mesh)
        self._wrapped: List[_ShardedOp] = []
        self._composed: Optional[ShardedComposedIndex] = None
        self._session = None

    def _make_engine(self, engine: str, mesh):
        if engine == "numpy":
            return "numpy", None
        if engine not in ("auto", "collective"):
            raise ValueError(f"unknown engine {engine!r}")
        if mesh is None:
            from repro.launch.mesh import make_shard_mesh

            mesh = make_shard_mesh(self.n_shards)
        if mesh is None:
            if engine == "collective":
                raise RuntimeError(
                    f"collective engine needs >= {self.n_shards} devices "
                    "(set XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "before jax initializes)")
            return "numpy", None
        return "collective", _CollectiveEngine(mesh)

    # -- view plumbing -------------------------------------------------------
    @property
    def name(self) -> str:
        return f"{self.base.name}@shard{self.n_shards}"

    @property
    def datasets(self):
        return self.base.datasets

    @property
    def producer(self):
        return self.base.producer

    @property
    def consumers(self):
        return self.base.consumers

    @property
    def version(self) -> int:
        return self.base.version

    @property
    def ops(self) -> List[_ShardedOp]:
        for op in self.base.ops[len(self._wrapped):]:
            self._wrapped.append(_ShardedOp(
                op_id=op.op_id,
                info=op.info,
                tensor=ShardedTensor(op.tensor, self.n_shards, self._engine),
                input_ids=list(op.input_ids),
                output_id=op.output_id,
            ))
        return self._wrapped

    def _wrap(self, base_ops) -> List[_ShardedOp]:
        ops = self.ops
        return [ops[op.op_id] for op in base_ops]

    def downstream_ops(self, dataset_id: str) -> List[_ShardedOp]:
        return self._wrap(self.base.downstream_ops(dataset_id))

    def upstream_ops(self, dataset_id: str) -> List[_ShardedOp]:
        return self._wrap(self.base.upstream_ops(dataset_id))

    def path_exists(self, src: str, dst: str) -> bool:
        return self.base.path_exists(src, dst)

    def sources(self) -> List[str]:
        return self.base.sources()

    def sinks(self) -> List[str]:
        return self.base.sinks()

    def ranges(self, dataset_id: str) -> List[Tuple[int, int]]:
        """This dataset's shard layout — the partitioning contract every
        tensor slice, hop-cache block, and catalog link follows."""
        return shard_ranges(self.base.datasets[dataset_id].n_rows,
                            self.n_shards)

    def composed(self, **kwargs) -> ShardedComposedIndex:
        if self._composed is None:
            self._composed = ShardedComposedIndex(self, **kwargs)
        elif kwargs:
            raise ValueError("composed() already configured; use composed()")
        return self._composed

    def session(self, **kwargs):
        from repro.provenance.session import QuerySession

        if self._session is None:
            self._session = QuerySession(self, **kwargs)
        elif kwargs:
            raise ValueError("session() already configured; use session()")
        return self._session

    def stats(self) -> Dict[str, object]:
        out = self.base.stats()
        out["n_shards"] = self.n_shards
        out["engine"] = self.engine_name
        return out

    def __repr__(self) -> str:
        return (f"ShardedProvenanceIndex({self.base.name!r}, "
                f"n_shards={self.n_shards}, engine={self.engine_name})")

    # -- federation seam -----------------------------------------------------
    def as_catalog(self, src: str, dst: str, *,
                   root: str = "root", gather: str = "gather"):
        """Register the shards of the composed ``src → dst`` relation as
        :class:`~repro.provenance.catalog.ProvCatalog` members stitched by
        range-alignment links.

        Member graph (acyclic, so federation routing accepts it)::

            root/src --identity--> shard{s}/src --op--> shard{s}/dst@local
                 shard{s}/dst@local --alignment--> gather/dst

        Each shard member is a real single-op :class:`ProvenanceIndex`
        whose tensor is that shard's composed relation block; the
        range alignment maps global dst row ``j`` to shard-local ``j - lo``
        inside ``[lo, hi)`` and ``-1`` outside.  Forward probes from
        ``root/src`` fan out over the identity links, answer per shard, and
        OR into ``gather/dst`` over the S alignment links; backward probes
        ride the same links in reverse — all through the unchanged PR 4
        federation machinery (including its stitched cross-relation cache).
        """
        from repro.core.opcat import AttrMap, CaptureInfo, OpCategory
        from repro.dataprep.table import Table
        from repro.provenance.catalog import ProvCatalog

        if src == dst:
            raise ValueError("as_catalog needs distinct src and dst datasets")
        base = self.base
        n_src = base.datasets[src].n_rows
        n_dst = base.datasets[dst].n_rows

        def _placeholder(n: int) -> Table:
            return Table(columns=["_row"], data=np.zeros((n, 1), np.float32),
                         null=None, index=None)

        src_table = base.datasets[src].table or _placeholder(n_src)
        catalog = ProvCatalog(f"{base.name}-sharded")
        root_idx = ProvenanceIndex(root)
        root_idx.add_source(src, src_table)
        catalog.register(root, root_idx)
        gather_idx = ProvenanceIndex(gather)
        gather_idx.add_source(dst, base.datasets[dst].table
                              or _placeholder(n_dst))
        catalog.register(gather, gather_idx)

        entry = self.composed()._entry(src, dst)
        if entry is None:
            raise KeyError(f"no dataflow path {src} -> {dst}")
        local_ds = f"{dst}@local"
        for s, block in enumerate(entry.blocks):
            lo, hi = block.lo, block.hi
            member = ProvenanceIndex(f"shard{s}")
            member.add_source(src, src_table)
            if block.kind == "dense":
                src_rows, dst_local = np.nonzero(block.mat)
            else:
                coo = block.mat.tocoo()
                src_rows, dst_local = coo.row, coo.col
            links = np.stack([dst_local.astype(np.int32),
                              src_rows.astype(np.int32)], axis=1)
            info = CaptureInfo(
                op_name=f"shard{s}:{src}->{dst}",
                category=OpCategory.HAUGMENT,
                contextual=False,
                n_out=hi - lo,
                n_in=[n_src],
                links=links,
                attr_maps=[AttrMap(kind="identity")],
            )
            member.record([src], local_ds, _placeholder(hi - lo), info)
            catalog.register(f"shard{s}", member)
            catalog.link(f"{root}/{src}", f"shard{s}/{src}")
            alignment = np.full(n_dst, -1, dtype=np.int64)
            alignment[lo:hi] = np.arange(hi - lo, dtype=np.int64)
            catalog.link(f"shard{s}/{local_ds}", f"{gather}/{dst}",
                         alignment=alignment)
        return catalog
