"""Composed hop-cache: memoized multi-hop lineage relations (paper §III-D/§IV).

Answering Q1/Q2/Q10/Q11 between DISTANT datasets by walking the op DAG costs
one CSR probe per hop per query.  The Einstein-summation machinery of
:mod:`repro.core.compose` can instead contract the whole path into ONE
composed relation; this module memoizes those relations so repeated /
batched queries between the same dataset pair become a single batched probe.

Design points:

* **Per-entry backends.**  Every cached relation carries its own
  representation tag (:class:`_Entry`): ``csr`` (scipy sparse boolean
  matmul — composition cost scales with nnz), ``bitplane`` (packed uint32
  planes through :func:`compose_pair` — the :mod:`repro.kernels` bitmatmul,
  Pallas on TPU), or ``structured`` (an IMPLICIT gather: one int32 array
  mapping each destination row to its ≤1 source row, ``None`` for the pure
  identity).  ``backend="auto"`` (the default) picks per pair by
  the cost model's density threshold
  (:data:`repro.core.costmodel.DENSITY_THRESHOLD`) and CONVERTS an
  accumulation that densifies past it — a filter-heavy 0.1%-dense path stays
  CSR while a join blow-up rides the packed planes, in one cache.
  ``backend="csr"`` / ``backend="bitplane"`` force a uniform representation.
* **Closed-form composition algebra.**  In ``auto`` mode, ops whose slot
  relation is structured (:meth:`ProvTensor.slot_structure` — identities,
  selections, gathers, append blocks, join sides) compose WITHOUT spmm:
  an identity step is eliminated outright (the accumulation is reused
  unchanged, whatever its backend); gather∘gather — and therefore
  selection∘selection — is ONE ``np.take``; append's sibling-branch union
  distributes over its disjoint blocks and stays a gather.  A composed
  chain of selections is cached as ONE int32 array — its byte accounting
  reflects the implicit form, not a CSR.  Only a genuinely multi-parent
  step (raw-COO links) or an overlapping-branch union densifies the
  accumulation into csr/bitplane, from where the old algebra takes over.
* **Multi-path exact** — ``relation(src, dst)`` accumulates over the op DAG
  in topological order, UNIONING the contributions of every input slot whose
  dataset is reachable from ``src``.  On DAGs where ``src`` reaches ``dst``
  through multiple paths (a diamond: one source feeding two branches
  re-joined downstream) the composed relation sums over ALL paths, exactly
  matching the hop-walking engine — not just the unique producer chain.
* **Lazy + incremental** — every intermediate ``(src, mid)`` accumulation is
  cached, so a later query to a further dataset reuses the cached prefix and
  composes only the new suffix.
* **Eviction-bounded** — an LRU keyed on ``(src, dst)`` with a byte budget
  (``memory_budget_bytes``), honoring the paper's minimal-memory goal: the
  cache trades recompute for memory and can be sized down to nothing.
  Overwriting an existing key first releases the old entry's bytes.
* **Fast backward probes** — bitplane entries lazily materialize a
  TRANSPOSED plane (bytes accounted against the budget), so a backward probe
  select-ORs just the probe's set rows (the same
  :func:`bitplane_or_reduce` contraction as forward probes) costing
  O(probe nnz × words) per probe instead of the old scan of every relation
  row per probe.
* **Append-safe AND stream-native** — the op DAG is append-only (one
  producer per dataset, enforced by ``ProvenanceIndex.record``), so
  composed relations between existing datasets stay exact when new ops are
  recorded and the cache is kept across version bumps.  Beyond that,
  ``_sync`` drains newly-recorded ops INCREMENTALLY: for every source
  dataset the cache has been probed through, a new op with a structured
  tail (identity / filter / gather / append — the common capture output)
  EXTENDS the warm composed relation by one closed-form step
  (:func:`~repro.core.compose.extend_tail` — a take for structured
  prefixes, a column gather for dense ones) instead of leaving the next
  probe to recompose the chain; a cold multi-hop miss with a dense prefix
  is gated by :func:`~repro.core.costmodel.extend_vs_recompose` between
  stepwise extension and fold-the-tail-first recomposition.  ``extends`` /
  ``recomposes`` counters in :meth:`stats` expose which maintenance path
  ran.
* **Spill-backed eviction** — with a ``spill=`` policy
  (:mod:`repro.core.spill`), LRU eviction past the byte budget's high
  watermark serializes entries to the compact on-disk log (structured
  gathers as one int array, CSR as its index/indptr/data triple, bitplanes
  as the packed words) instead of dropping them; a probe of a spilled pair
  FAULTS it back transparently (one memory-mapped read, counted in
  ``rehydrations``) rather than recomposing the chain.  Without ``spill=``
  eviction behaves exactly as before (drop at the budget).

When NO path exists, the probe methods answer empty (matching the walking
engine); ``relation`` itself raises ``KeyError``.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.core.compose import (
    HAVE_SCIPY,
    compose_gather,
    compose_pair,
    compose_pair_csr,
    extend_tail_bitplane,
    extend_tail_csr,
    op_bitplane,
    op_csr,
    resolve_use_pallas,
)
from repro.core.costmodel import (
    CostModel,
    RelStats,
    extend_vs_recompose,
    pick_backend,
)
from repro.core.pipeline import ProvenanceIndex
from repro.core.provtensor import (
    SlotIdentity,
    bitplane_or_reduce,
    bitplane_popcount,
    pack_bitplane,
    unpack_bitplane,
)
from repro.core.spill import resolve_spill

__all__ = ["ComposedIndex"]


@dataclasses.dataclass
class _Entry:
    """One cached composed relation, tagged with its representation.

    ``structured`` entries hold the relation implicitly: ``rel`` is an int32
    ``(cols,)`` gather mapping each destination row to its (at most one)
    source row, ``-1`` = no link — or ``None`` for the pure identity
    (``rows == cols``), which costs nothing at all."""

    backend: str              # "csr" | "bitplane" | "structured"
    rel: object               # scipy CSR (float32 ones), packed uint32 plane,
                              # or int32 gather (None = identity)
    rows: int                 # |src|
    cols: int                 # |dst|
    nnz: int
    relT: Optional[np.ndarray] = None  # lazy (cols, ⌈rows/32⌉) transposed plane

    @property
    def density(self) -> float:
        cells = self.rows * self.cols
        return self.nnz / cells if cells else 0.0

    def nbytes(self) -> int:
        if self.backend == "structured":
            total = 0 if self.rel is None else int(self.rel.nbytes)
        elif self.backend == "csr":
            r = self.rel
            total = int(r.data.nbytes + r.indices.nbytes + r.indptr.nbytes)
        else:
            total = int(self.rel.nbytes)
        if self.relT is not None:
            total += int(self.relT.nbytes)
        return total

    def gather(self) -> np.ndarray:
        """The structured entry's (cols,) destination→source map, with the
        identity materialized on demand."""
        if self.rel is not None:
            return self.rel
        return np.arange(self.cols, dtype=np.int32)


class ComposedIndex:
    """Memoized composed-relation store + batched probe engine over one
    :class:`ProvenanceIndex`."""

    def __init__(
        self,
        index: ProvenanceIndex,
        memory_budget_bytes: int = 64 << 20,
        backend: Optional[str] = None,
        use_pallas: Optional[bool] = None,
        spill=None,
        extend_eager: bool = True,
    ) -> None:
        # tri-state kernel flag: None -> Pallas iff on TPU.  The backend
        # stays density-picked everywhere: structured and sparse relations
        # keep their compact forms, and only dense entries ride the packed
        # planes (and, on TPU, the bitmatmul kernel)
        use_pallas = resolve_use_pallas(use_pallas)
        if backend is None:
            backend = "auto"
        if backend not in ("auto", "csr", "bitplane"):
            raise ValueError(f"unknown backend {backend!r}")
        if backend == "csr" and not HAVE_SCIPY:
            raise ImportError("backend='csr' requires scipy")
        self.index = index
        self.backend = backend
        self.memory_budget_bytes = int(memory_budget_bytes)
        self.use_pallas = use_pallas
        self.extend_eager = bool(extend_eager)
        self.costmodel = CostModel(index)
        self._cache: "OrderedDict[Tuple[str, str], _Entry]" = OrderedDict()
        self._bytes = 0
        self._version = index.version
        self._ops_seen = len(index.ops)
        # probed-through sources: reach sets maintained incrementally so a
        # 1M-op stream never re-runs the O(ops) reachability scan per probe
        self._reach: Dict[str, set] = {}
        self._spill = resolve_spill(spill)
        self._spill_store = self._spill.ensure_store() if self._spill else None
        # keys whose entry is on disk and NOT resident; plus what the disk
        # copy holds (backend, nnz) so an unchanged re-eviction skips the
        # write (composed relations are immutable under the append-only DAG)
        self._spilled: "OrderedDict[Tuple[str, str], bool]" = OrderedDict()
        self._store_meta: Dict[Tuple[str, str], Tuple[str, int]] = {}
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.conversions = 0
        self.extends = 0
        self.recomposes = 0
        self.spills = 0
        self.rehydrations = 0

    # -- cache plumbing -----------------------------------------------------
    def _sync(self) -> None:
        """Reconcile with the index after writes — incrementally.

        The op DAG is APPEND-ONLY (every dataset has exactly one producer —
        ``ProvenanceIndex.record`` rejects duplicate output ids — and a new
        op can only produce a NEW dataset, never splice a path between two
        existing ones), so composed relations between existing datasets stay
        exact across version bumps and the cache is KEPT.

        Ops recorded since the last sync are drained ONCE: every tracked
        source's reach set absorbs them, and (``extend_eager``, auto
        backend) a new op whose on-path slots are all structured and whose
        on-path prefixes are all RAM-resident EXTENDS the warm composed
        relations by one closed-form step right now — the next probe of the
        new dataset is a pure cache hit instead of a chain recompose.  Ops
        whose tensors are spilled, or whose prefixes are cold, are left for
        the lazy path (which faults / rebuilds on demand): eager
        maintenance must never pull cold state back in.
        """
        n = len(self.index.ops)
        if n > self._ops_seen:
            for op in self.index.ops[self._ops_seen:n]:
                self._absorb_op(op)
            self._ops_seen = n
        self._version = self.index.version

    def _absorb_op(self, op) -> None:
        for src, reach in self._reach.items():
            slots = [k for k, d in enumerate(op.input_ids) if d in reach]
            if not slots:
                continue
            reach.add(op.output_id)
            if not (self.extend_eager and self.backend == "auto"):
                continue
            if not getattr(op.tensor, "structured", False):
                continue
            prefixes = {}
            for k in slots:
                d = op.input_ids[k]
                if d == src:
                    prefixes[k] = None
                else:
                    e = self._cache.get((src, d))
                    if e is None:
                        break  # cold/partial prefix: a partial union is wrong
                    prefixes[k] = e
            if len(prefixes) != len(slots):
                continue
            # the extension reads the gather slot: fault a spilled tensor
            # back NOW (one ~KB memmap read, LRU-linear during a sync drain)
            # — skipping instead would leave the relation to a full
            # recompose over every op appended since the last probe
            op.tensor.resident()
            acc: Optional[_Entry] = None
            for k in slots:
                contrib = self._extend(prefixes[k], op, k)
                acc = contrib if acc is None else self._union(acc, contrib)
            self._insert((src, op.output_id), self._settle(acc))
            self.extends += 1

    def _reach_set(self, src: str) -> set:
        """Datasets reachable from ``src`` — computed by ONE full op scan on
        the first probe through ``src``, then maintained per appended op by
        ``_sync`` (the O(ops)-per-miss rescan this replaces was the
        streaming bottleneck)."""
        reach = self._reach.get(src)
        if reach is None:
            reach = {src}
            for op in self.index.ops:
                if any(d in reach for d in op.input_ids):
                    reach.add(op.output_id)
            self._reach[src] = reach
        return reach

    def _evict_over_budget(self) -> None:
        if self._spill is None:
            while (self._bytes > self.memory_budget_bytes
                   and len(self._cache) > 1):
                _, evicted = self._cache.popitem(last=False)
                self._bytes -= evicted.nbytes()
                self.evictions += 1
            return
        # spill tier: watermark hysteresis — start evicting past high,
        # spill LRU entries to disk down to low, so an append stream pays
        # one burst of writes per crossing instead of one per insert
        high = self.memory_budget_bytes * self._spill.high_watermark
        low = self.memory_budget_bytes * self._spill.low_watermark
        if self._bytes <= high:
            return
        while self._bytes > low and len(self._cache) > 1:
            key, evicted = self._cache.popitem(last=False)
            self._bytes -= evicted.nbytes()
            self.evictions += 1
            self._spill_entry(key, evicted)

    def _spill_entry(self, key: Tuple[str, str], entry: _Entry) -> None:
        entry.relT = None  # lazily rebuilt after fault; never serialized
        if self._store_meta.get(key) != (entry.backend, entry.nnz):
            meta = {"backend": entry.backend, "rows": entry.rows,
                    "cols": entry.cols, "nnz": entry.nnz,
                    "identity": entry.backend == "structured"
                    and entry.rel is None}
            if entry.backend == "structured":
                arrays = {} if entry.rel is None else {"gather": entry.rel}
            elif entry.backend == "csr":
                arrays = {"data": entry.rel.data, "indices": entry.rel.indices,
                          "indptr": entry.rel.indptr}
            else:
                arrays = {"plane": entry.rel}
            self._spill_store.put(("rel", self.index.name) + key, arrays, meta)
            self._store_meta[key] = (entry.backend, entry.nnz)
        self._spilled[key] = True
        self.spills += 1

    def _fault(self, key: Tuple[str, str]) -> Optional[_Entry]:
        """Rehydrate one spilled composed relation: arrays come back as
        read-only memmap views (page-cache-backed, byte-identical to what
        was evicted), the entry re-enters the LRU as MRU."""
        try:
            meta, arrays = self._spill_store.get(("rel", self.index.name)
                                                 + key)
        except KeyError:
            self._spilled.pop(key, None)
            self._store_meta.pop(key, None)
            return None  # dropped by a disk budget: rebuild from scratch
        backend = meta["backend"]
        rows, cols, nnz = int(meta["rows"]), int(meta["cols"]), int(meta["nnz"])
        if backend == "structured":
            rel = None if meta["identity"] else np.asarray(arrays["gather"])
            entry = _Entry("structured", rel, rows, cols, nnz)
        elif backend == "csr":
            import scipy.sparse as sp

            rel = sp.csr_matrix(
                (arrays["data"], arrays["indices"], arrays["indptr"]),
                shape=(rows, cols))
            entry = _Entry("csr", rel, rows, cols, nnz)
        else:
            entry = _Entry("bitplane", np.asarray(arrays["plane"]),
                           rows, cols, nnz)
        self._spilled.pop(key, None)
        self.rehydrations += 1
        self._insert(key, entry)
        if key not in self._cache:
            self._spilled[key] = True  # declined (over budget); disk copy stays
        return entry

    def _insert(self, key: Tuple[str, str], entry: _Entry) -> None:
        nbytes = entry.nbytes()
        if nbytes > self.memory_budget_bytes:
            # larger than the whole budget: with a spill tier, park it on
            # disk (a memmap fault beats recomposing the chain); without
            # one, serve uncached — the seed behavior
            if self._spill_store is not None:
                self._spill_entry(key, entry)
            return
        old = self._cache.pop(key, None)
        if old is not None:
            # overwrite releases the old entry's bytes FIRST — re-inserting a
            # key must not double-count and force spurious evictions
            self._bytes -= old.nbytes()
        self._cache[key] = entry
        self._bytes += nbytes
        self._spilled.pop(key, None)  # resident again; disk copy kept as-is
        self._evict_over_budget()

    def _lookup(self, key: Tuple[str, str]) -> Optional[_Entry]:
        entry = self._cache.get(key)
        if entry is not None:
            self._cache.move_to_end(key)
            return entry
        if self._spilled and key in self._spilled:
            return self._fault(key)
        return None

    def _peek(self, key: Tuple[str, str]) -> bool:
        """Composed and answerable without recomposition (resident OR
        spilled) — no LRU touch, no fault, no composition."""
        return key in self._cache or key in self._spilled

    # -- backend primitives ---------------------------------------------------
    def _resolve_backend(self, density: float) -> str:
        """Representation for a relation of the given density (auto mode:
        the cost model's threshold; forced modes: the forced backend)."""
        if self.backend != "auto":
            return self.backend
        return pick_backend(density, HAVE_SCIPY)

    def _identity_entry(self, n: int) -> _Entry:
        if self.backend == "auto":
            # the src == dst relation IS the identity: store nothing
            return _Entry("structured", None, n, n, n)
        density = 1.0 / n if n else 0.0
        backend = self._resolve_backend(density)
        if backend == "csr":
            import scipy.sparse as sp

            return _Entry("csr", sp.identity(n, dtype=np.float32, format="csr"),
                          n, n, n)
        words = np.zeros((n, max((n + 31) // 32, 1)), dtype=np.uint32)
        i = np.arange(n)
        words[i, i // 32] = np.left_shift(np.uint32(1), (i % 32).astype(np.uint32))
        return _Entry("bitplane", words, n, n, n)

    def _step_rel(self, op, slot: int, backend: str):
        return op_csr(op.tensor, slot) if backend == "csr" \
            else op_bitplane(op.tensor, slot)

    @staticmethod
    def _structured_pairs(entry: _Entry):
        """Valid (source_row, dest_row) link pairs of a structured entry."""
        if entry.rel is None:
            i = np.arange(entry.rows, dtype=np.int32)
            return i, i
        dst = np.flatnonzero(entry.rel >= 0).astype(np.int32)
        return entry.rel[dst], dst

    def _to_bitplane(self, entry: _Entry) -> _Entry:
        if entry.backend == "bitplane":
            return entry
        self.conversions += 1
        if entry.backend == "structured":
            src, dst = self._structured_pairs(entry)
            dense = np.zeros((entry.rows, entry.cols), dtype=bool)
            dense[src, dst] = True
        else:
            dense = np.asarray(entry.rel.toarray()) > 0
        return _Entry("bitplane", pack_bitplane(dense),
                      entry.rows, entry.cols, entry.nnz)

    def _to_csr(self, entry: _Entry) -> _Entry:
        if entry.backend == "csr":
            return entry
        import scipy.sparse as sp

        self.conversions += 1
        if entry.backend == "structured":
            src, dst = self._structured_pairs(entry)
            rel = sp.csr_matrix(
                (np.ones(len(dst), dtype=np.float32), (src, dst)),
                shape=(entry.rows, entry.cols))
        else:
            dense = unpack_bitplane(entry.rel, entry.cols)
            rel = sp.csr_matrix(dense.astype(np.float32))
        return _Entry("csr", rel, entry.rows, entry.cols, entry.nnz)

    def _densify(self, entry: _Entry) -> _Entry:
        """A structured entry leaving the closed-form algebra (overlapping
        union, unstructured step): the representation the density picks."""
        if entry.backend != "structured":
            return entry
        return self._to_csr(entry) \
            if pick_backend(entry.density, HAVE_SCIPY) == "csr" \
            else self._to_bitplane(entry)

    def _structured_step_entry(self, op, slot: int) -> _Entry:
        t = op.tensor
        s = t.slot_structure(slot)
        rel = None if isinstance(s, SlotIdentity) else t.slot_gather(slot)
        return _Entry("structured", rel, t.n_in[slot], t.n_out,
                      t.slot_nnz(slot))

    def _extend(self, prefix: Optional[_Entry], op, slot: int) -> _Entry:
        """``prefix ∘ op[slot]`` as a fresh entry (prefix None = identity).

        Closed forms first (``auto`` mode): an identity step is ELIMINATED —
        the result reuses the prefix's relation unchanged, whatever its
        backend; a structured prefix composed with a structured step
        (gather∘gather, so also selection∘selection) is ONE ``np.take``;
        only an unstructured step densifies the prefix and falls back to
        spmm / packed-plane contraction."""
        t = op.tensor
        s = t.slot_structure(slot) if self.backend == "auto" else None
        if prefix is None:
            if s is not None:
                return self._structured_step_entry(op, slot)
            backend = self._resolve_backend(t.slot_density(slot))
            return _Entry(backend, self._step_rel(op, slot, backend),
                          t.n_in[slot], t.n_out, t.slot_nnz(slot))
        if isinstance(s, SlotIdentity):
            # identity elimination: prefix ∘ I = prefix.  The relation is
            # COPIED (a memcpy, still no spmm/bitmatmul): both entries live
            # in the cache under their own keys, and aliased arrays would
            # make the budget double-count bytes and eviction free nothing.
            rel = prefix.rel if prefix.rel is None else prefix.rel.copy()
            return _Entry(prefix.backend, rel, prefix.rows, t.n_out,
                          prefix.nnz)
        if prefix.backend == "structured":
            if prefix.rel is None:
                # identity prefix: the step's own relation is the result
                return self._extend(None, op, slot)
            if s is not None:
                g_step = t.slot_gather(slot)            # (n_out,) → |mid|
                valid = g_step >= 0
                g_new = np.where(valid,
                                 prefix.rel[np.where(valid, g_step, 0)],
                                 np.int32(-1))
                return _Entry("structured", g_new, prefix.rows, t.n_out,
                              int(np.count_nonzero(g_new >= 0)))
            prefix = self._densify(prefix)
        if s is not None:
            # DENSE prefix ∘ structured step: the closed-form tail extension
            # (a column gather, no matmul) — the streaming append fast path
            g_step = t.slot_gather(slot)
            if prefix.backend == "csr":
                rel = extend_tail_csr(prefix.rel, g_step)
                return _Entry("csr", rel, prefix.rows, t.n_out, int(rel.nnz))
            rel = extend_tail_bitplane(prefix.rel, g_step, prefix.cols)
            return _Entry("bitplane", rel, prefix.rows, t.n_out,
                          bitplane_popcount(rel))
        rows = prefix.rows
        step = self._step_rel(op, slot, prefix.backend)
        if prefix.backend == "csr":
            rel = compose_pair_csr(prefix.rel, step)
            nnz = int(rel.nnz)
        else:
            rel = compose_pair(prefix.rel, step, t.n_in[slot],
                               use_pallas=self.use_pallas)
            nnz = bitplane_popcount(rel)
        return _Entry(prefix.backend, rel, rows, t.n_out, nnz)

    def _union(self, a: _Entry, b: _Entry) -> _Entry:
        """(OR)-union two relations — the sum over parallel DAG paths.

        Two structured gathers whose links never disagree stay structured —
        append's sibling branches land in DISJOINT destination blocks (the
        block-append distribution), so their union is still one gather.
        Everything else densifies; mixed representations meet on the packed
        plane (the denser side)."""
        if a.backend == "structured" and b.backend == "structured":
            ga, gb = a.gather(), b.gather()
            both = (ga >= 0) & (gb >= 0)
            if not both.any() or np.array_equal(ga[both], gb[both]):
                g = np.where(ga >= 0, ga, gb)
                return _Entry("structured", g, a.rows, a.cols,
                              int(np.count_nonzero(g >= 0)))
        a, b = self._densify(a), self._densify(b)
        if a.backend != b.backend:
            a, b = self._to_bitplane(a), self._to_bitplane(b)
        if a.backend == "csr":
            rel = (a.rel + b.rel).tocsr()
            rel.data = np.ones_like(rel.data)
            return _Entry("csr", rel, a.rows, a.cols, int(rel.nnz))
        rel = np.bitwise_or(a.rel, b.rel)
        return _Entry("bitplane", rel, a.rows, a.cols, bitplane_popcount(rel))

    def _compose_entries(self, a: _Entry, b: _Entry) -> _Entry:
        """Generic ``a ∘ b`` over already-composed entries (``a`` maps
        X→Y, ``b`` maps Y→Z) — the recompose path's fold primitive.  The
        same closed forms as :meth:`_extend` apply: identity elimination
        (copying, per the no-aliasing budget rule), gather∘gather as one
        take, dense∘gather as the column-gather tail extension; only
        dense∘dense pays a matmul."""
        if a.backend == "structured" and a.rel is None:
            rel = b.rel
            if rel is not None:
                rel = rel.copy()
            return _Entry(b.backend, rel, a.rows, b.cols, b.nnz)
        if b.backend == "structured" and b.rel is None:
            rel = a.rel if a.rel is None else a.rel.copy()
            return _Entry(a.backend, rel, a.rows, b.cols, a.nnz)
        if b.backend == "structured":
            if a.backend == "structured":
                g = compose_gather(a.rel, b.rel)
                return _Entry("structured", g, a.rows, b.cols,
                              int(np.count_nonzero(g >= 0)))
            if a.backend == "csr":
                rel = extend_tail_csr(a.rel, b.rel)
                return _Entry("csr", rel, a.rows, b.cols, int(rel.nnz))
            rel = extend_tail_bitplane(a.rel, b.rel, a.cols)
            return _Entry("bitplane", rel, a.rows, b.cols,
                          bitplane_popcount(rel))
        a = self._densify(a)
        if a.backend != b.backend:
            b = self._to_csr(b) if a.backend == "csr" else self._to_bitplane(b)
        if a.backend == "csr":
            rel = compose_pair_csr(a.rel, b.rel)
            return _Entry("csr", rel, a.rows, b.cols, int(rel.nnz))
        rel = compose_pair(a.rel, b.rel, b.rows, use_pallas=self.use_pallas)
        return _Entry("bitplane", rel, a.rows, b.cols, bitplane_popcount(rel))

    def _settle(self, entry: _Entry) -> _Entry:
        """auto mode: convert an accumulation whose observed density crossed
        the cost model's threshold (densification → packed plane, and back).
        Structured entries never settle — the implicit form beats both."""
        if self.backend != "auto" or entry.backend == "structured":
            return entry
        want = pick_backend(entry.density, HAVE_SCIPY)
        if want == entry.backend:
            return entry
        return self._to_bitplane(entry) if want == "bitplane" \
            else self._to_csr(entry)

    # -- the composed relation ----------------------------------------------
    def _pending_ops(self, src: str, dst: str, reach: set) -> List[object]:
        """Ops that must run to compose ``(src, dst)``: backward DFS from
        ``dst``, stopping at ``src`` and at datasets whose ``(src, ·)``
        relation is already composed (resident or spilled) — returned in
        topological (op-id) order.  For a one-op append onto a warm chain
        this is a SINGLE op, independent of pipeline depth; the seed path
        rescanned the whole DAG region per miss."""
        pending: Dict[int, object] = {}
        visited = set()
        stack = [dst]
        while stack:
            d = stack.pop()
            if d == src or d in visited:
                continue
            visited.add(d)
            if self._peek((src, d)):
                continue
            op = self.index.ops[self.index.producer[d]]
            pending[op.op_id] = op
            for in_id in op.input_ids:
                if in_id in reach:
                    stack.append(in_id)
        return [pending[i] for i in sorted(pending)]

    @staticmethod
    def _linear_tail(pending: List[object], reach: set):
        """``(base_dataset, [(op, slot), ...])`` when the pending ops form
        one single-parent chain (each op exactly one on-path input, chained
        consecutively) — the shape :func:`extend_vs_recompose` prices —
        else None."""
        steps = []
        base = None
        prev_out = None
        for op in pending:
            slots = [k for k, d in enumerate(op.input_ids) if d in reach]
            if len(slots) != 1:
                return None
            in_id = op.input_ids[slots[0]]
            if prev_out is None:
                base = in_id
            elif in_id != prev_out:
                return None
            prev_out = op.output_id
            steps.append((op, slots[0]))
        return base, steps

    def _step_entry(self, op, slot: int) -> _Entry:
        """One op slot's own relation as an entry (the tail fold's leaves)."""
        t = op.tensor
        if self.backend == "auto" and t.slot_structure(slot) is not None:
            return self._structured_step_entry(op, slot)
        backend = self._resolve_backend(t.slot_density(slot))
        return _Entry(backend, self._step_rel(op, slot, backend),
                      t.n_in[slot], t.n_out, t.slot_nnz(slot))

    def _relation_entry(self, src: str, dst: str) -> _Entry:
        self._sync()
        cached = self._lookup((src, dst))
        if cached is not None:
            self.hits += 1
            return cached
        self.misses += 1
        if src == dst:
            entry = self._identity_entry(self.index.datasets[src].n_rows)
            self._insert((src, dst), entry)
            return entry
        reach = self._reach_set(src)
        if dst not in reach:
            raise KeyError(f"no dataflow path {src} -> {dst}")
        pending = self._pending_ops(src, dst, reach)
        pending_out = {op.output_id for op in pending}
        # Resolve every boundary prefix FIRST (cached (src, ·) relations the
        # pending ops compose onto), before any insert below can evict one.
        # local holds live references, so cascading evictions during the
        # build cannot invalidate them.
        local: Dict[str, Optional[_Entry]] = {src: None}  # None = identity
        for op in pending:
            for in_id in op.input_ids:
                if (in_id in reach and in_id != src
                        and in_id not in pending_out and in_id not in local):
                    hit = self._lookup((src, in_id))
                    if hit is not None:
                        self.hits += 1
                    else:
                        # evicted (no spill tier) between peek and resolve:
                        # rebuild the prefix recursively
                        hit = self._relation_entry(src, in_id)
                    local[in_id] = hit
        # Cost-model gate (dense warm prefix, multi-step tail): fold the
        # tail FIRST in the chain DP's order and apply it to the prefix
        # once, when that beats dragging the full-width prefix through
        # every hop.  Intermediates are NOT cached on this path — the gate
        # chose it precisely because they would be expensive dead weight.
        if len(pending) >= 2:
            lin = self._linear_tail(pending, reach)
            if lin is not None and lin[0] in local:
                base, steps = lin
                prefix = local[base]
                if prefix is not None and prefix.backend != "structured":
                    pstats = RelStats(prefix.rows, prefix.cols, prefix.nnz,
                                      structured=False)
                    tstats = [RelStats.from_slot(op.tensor, k)
                              for op, k in steps]
                    verdict = extend_vs_recompose(pstats, tstats,
                                                  have_scipy=HAVE_SCIPY)
                    if verdict["strategy"] == "recompose":
                        entries = [self._step_entry(op, k) for op, k in steps]
                        for (i, _k) in verdict["tail_order"]:
                            j = i + 1
                            while entries[j] is None:
                                j += 1
                            entries[i] = self._compose_entries(entries[i],
                                                               entries[j])
                            entries[j] = None
                        folded = next(e for e in entries if e is not None)
                        acc = self._settle(self._compose_entries(prefix,
                                                                 folded))
                        self.recomposes += 1
                        self._insert((src, dst), acc)
                        return acc
        # Stepwise accumulation in topo order: UNION over on-path input
        # slots of (prefix ∘ slot step); every intermediate (src, mid) is
        # cached so later further-dataset queries reuse the prefix.
        rels = local
        for op in pending:
            acc: Optional[_Entry] = None
            for k, in_id in enumerate(op.input_ids):
                if in_id not in rels:
                    continue  # input unreachable from src: contributes nothing
                contrib = self._extend(rels[in_id], op, k)
                acc = contrib if acc is None else self._union(acc, contrib)
            if acc is None:
                continue
            acc = self._settle(acc)
            rels[op.output_id] = acc
            self._insert((src, op.output_id), acc)
        if len(pending) == 1:
            self.extends += 1
        elif len(pending) > 1:
            self.recomposes += 1
        if dst not in rels or rels[dst] is None:
            raise KeyError(f"no dataflow path {src} -> {dst}")
        return rels[dst]

    def relation(self, src: str, dst: str):
        """The composed ``src`` → ``dst`` relation, from cache or composed
        incrementally: scipy CSR or packed bitplane per the entry's backend
        (see :meth:`relation_backend`); a ``structured`` entry answers a
        COPY of its int32 destination→source gather array (identity chains
        materialize the arange) — a copy because the cached gather may BE an
        op tensor's own capture payload, and handing out the live array
        would let a caller corrupt the recorded provenance.  Callers that
        need a uniform matrix regardless of backend use
        :meth:`relation_csr`.

        Accumulates over the op DAG in topological order restricted to ops
        that lie on some ``src`` → ``dst`` path: each op's output relation is
        the UNION over its input slots of (input relation ∘ slot step), so
        multi-path DAGs (diamonds, self-joins) compose exactly.  Every
        intermediate ``(src, mid)`` accumulation is cached — later queries
        to further datasets reuse the prefix.
        """
        entry = self._relation_entry(src, dst)
        if entry.backend == "structured":
            return entry.gather().copy()
        return entry.rel

    def relation_backend(self, src: str, dst: str) -> str:
        """Which representation the (composed-on-demand) relation uses."""
        return self._relation_entry(src, dst).backend

    def relation_csr(self, src: str, dst: str):
        """The composed relation as scipy CSR regardless of the entry's
        backend — the federation's cross-index composition hook (a
        :class:`~repro.provenance.catalog.BoundaryHandle` grants exactly
        this read for boundary-ancestor pairs).  Bitplane entries convert
        TRANSIENTLY: the cache entry, its backend tag, and the conversion
        counter are untouched."""
        if not HAVE_SCIPY:
            raise ImportError("relation_csr requires scipy")
        entry = self._relation_entry(src, dst)
        if entry.backend == "csr":
            # a COPY: handing out the live cached arrays would let a
            # "read-only" BoundaryHandle corrupt the index's private cache
            return entry.rel.copy()
        if entry.backend == "structured":
            import scipy.sparse as sp

            src_rows, dst_rows = self._structured_pairs(entry)
            return sp.csr_matrix(
                (np.ones(len(dst_rows), dtype=np.float32), (src_rows, dst_rows)),
                shape=(entry.rows, entry.cols))
        import scipy.sparse as sp

        # unpack in row blocks: a large packed plane must not transiently
        # materialize the full (rows, cols) dense array (32x the packed
        # bytes) just to re-sparsify it
        step = max(1, (4 << 20) // max(entry.cols, 1))
        blocks = [
            sp.csr_matrix(unpack_bitplane(entry.rel[i : i + step], entry.cols))
            for i in range(0, max(entry.rows, 1), step)
        ]
        rel = blocks[0] if len(blocks) == 1 else sp.vstack(blocks, format="csr")
        return rel.astype(np.float32)

    # -- batched probes -------------------------------------------------------
    def _probe_masks(self, rows, n: int) -> Tuple[np.ndarray, bool]:
        from repro.core.query import _as_mask, _as_mask_batch, is_probe_batch

        if is_probe_batch(rows):
            return _as_mask_batch(rows, n), True
        return _as_mask(rows, n)[None, :], False

    def _try_relation(self, src: str, dst: str) -> Optional[_Entry]:
        """``_relation_entry`` for probes: no dataflow path -> None (probes
        answer empty, matching the walking engine; ``relation`` itself still
        raises so relation-materializing callers get the loud error)."""
        try:
            return self._relation_entry(src, dst)
        except KeyError:
            return None

    def _entry_relT(self, key: Tuple[str, str], entry: _Entry) -> np.ndarray:
        """The transposed plane of a bitplane entry, materialized lazily and
        accounted against the byte budget (recomposed if later evicted).

        A CACHED entry only retains its transposed plane when rel+relT still
        fit the budget — ``_insert`` guarantees post-insert ``_bytes`` never
        exceeds the budget, and a sole over-budget entry could never be
        evicted (the eviction loop keeps one entry); otherwise the plane is
        served transiently.
        """
        if entry.relT is not None:
            return entry.relT
        dense = unpack_bitplane(entry.rel, entry.cols)
        relT = pack_bitplane(np.ascontiguousarray(dense.T))
        if self._cache.get(key) is not entry:
            entry.relT = relT       # transient entry: lives only this call
        elif entry.nbytes() + relT.nbytes <= self.memory_budget_bytes:
            entry.relT = relT
            self._bytes += int(relT.nbytes)
            self._evict_over_budget()
        return relT

    def _forward_probe(self, masks: np.ndarray, src: str, dst: str) -> np.ndarray:
        """(B, |src|) bool -> (B, |dst|) bool through the composed relation."""
        entry = self._try_relation(src, dst)
        if entry is None:
            return np.zeros(
                (masks.shape[0], self.index.datasets[dst].n_rows), dtype=bool)
        if entry.backend == "structured":
            # one take along the gather: out[b, d] = masks[b, g[d]]
            if entry.rel is None:
                return masks[:, : entry.cols].copy()
            g = entry.rel
            valid = g >= 0
            return masks[:, : entry.rows][:, np.where(valid, g, 0)] & valid[None, :]
        if entry.backend == "csr":
            return np.asarray(masks.astype(np.float32) @ entry.rel) > 0
        if self.use_pallas:
            from repro.kernels import ops as K

            words = np.asarray(K.bitplane_probe(pack_bitplane(masks), entry.rel))
        else:
            words = bitplane_or_reduce(pack_bitplane(masks), entry.rel, entry.rows)
        return unpack_bitplane(words, entry.cols)

    def _backward_probe(self, masks: np.ndarray, src: str, dst: str) -> np.ndarray:
        """(B, |dst|) bool -> (B, |src|) bool: rows of the composed relation
        intersecting each probe set.

        Bitplane entries answer through the lazily-cached TRANSPOSED plane:
        selecting a probe's set rows from ``relT`` and OR-reducing them
        costs O(probe nnz × words) per probe, instead of the old full
        scan of every relation row per probe — it is the exact mirror of
        the forward select-OR, so both directions share
        :func:`bitplane_or_reduce`.
        """
        entry = self._try_relation(src, dst)
        if entry is None:
            return np.zeros(
                (masks.shape[0], self.index.datasets[src].n_rows), dtype=bool)
        if entry.backend == "structured":
            # one scatter through the gather: out[b, g[d]] |= masks[b, d]
            if entry.rel is None:
                return masks[:, : entry.rows].copy()
            g = entry.rel
            out = np.zeros((masks.shape[0], entry.rows), dtype=bool)
            sel = masks[:, : entry.cols] & (g >= 0)[None, :]
            bs, ds = np.nonzero(sel)
            out[bs, g[ds]] = True
            return out
        if entry.backend == "csr":
            return (entry.rel @ masks.astype(np.float32).T).T > 0
        relT = self._entry_relT((src, dst), entry)
        words = bitplane_or_reduce(
            pack_bitplane(masks[:, : entry.cols]), relT, entry.cols)
        return unpack_bitplane(words, entry.rows)

    # -- mask-stack probes (the QuerySession entry points) ---------------------
    def contains(self, src: str, dst: str) -> bool:
        """Whether the ``src`` → ``dst`` relation is already composed (no LRU
        touch, no composition) — the planner's routing test.  A SPILLED
        entry counts: faulting it back is one mmap read, far cheaper than
        the walk/recompose the router would otherwise pick."""
        self._sync()
        return (src, dst) in self._cache or (src, dst) in self._spilled

    def residency(self, src: str, dst: str) -> Optional[str]:
        """Where the composed ``(src, dst)`` relation lives right now:
        ``"ram"``, ``"spilled"``, or None (not composed).  No LRU touch, no
        fault — the EXPLAIN surface reads this."""
        self._sync()
        if (src, dst) in self._cache:
            return "ram"
        if (src, dst) in self._spilled:
            return "spilled"
        return None

    def probe_forward(self, masks, src: str, dst: str) -> np.ndarray:
        """(B, |src|) bool mask stack -> (B, |dst|) bool via the composed
        relation.  No path -> all-empty (matching the walking engine)."""
        return self._forward_probe(np.asarray(masks, dtype=bool), src, dst)

    def probe_backward(self, masks, dst: str, src: str) -> np.ndarray:
        """(B, |dst|) bool mask stack -> (B, |src|) bool: relation rows
        intersecting each probe set."""
        return self._backward_probe(np.asarray(masks, dtype=bool), src, dst)

    def q1_forward(self, src: str, rows, dst: str):
        """Q1 via ONE batched probe of the composed relation (no DAG walk)."""
        masks, batched = self._probe_masks(rows, self.index.datasets[src].n_rows)
        out = self._forward_probe(masks, src, dst)
        res = [np.flatnonzero(m) for m in out]
        return res if batched else res[0]

    def q2_backward(self, dst: str, rows, src: str):
        """Q2: src rows whose composed relation row intersects the probe set."""
        masks, batched = self._probe_masks(rows, self.index.datasets[dst].n_rows)
        out = self._backward_probe(masks, src, dst)
        res = [np.flatnonzero(m) for m in out]
        return res if batched else res[0]

    def q10_co_contributory(self, d1: str, rows, d2: str, via: str):
        """Records of ``d2`` co-contributing with ``rows`` of ``d1`` into
        ``via`` — two composed probes, zero DAG hops."""
        from repro.core.query import is_probe_batch

        batched = is_probe_batch(rows)
        via_rows = self.q1_forward(d1, rows, via)
        res = self.q2_backward(via, via_rows if batched else [via_rows], d2)
        return res if batched else res[0]

    def q11_co_dependency(self, d2: str, rows, d1: str, d3: str):
        from repro.core.query import is_probe_batch

        batched = is_probe_batch(rows)
        back = self.q2_backward(d2, rows if batched else [rows], d1)
        res = self.q1_forward(d1, back, d3)
        return res if batched else res[0]

    # -- impact invalidation --------------------------------------------------
    def stale_entries(self, datasets) -> List[Tuple[str, str, str]]:
        """Composed entries (resident or spilled) whose ``src`` → ``dst``
        DAG region intersects ``datasets``, as ``(src, dst, residency)``
        triples — exactly the relations an erasure/rewrite of those
        datasets' rows leaves stale.  A relation is stale when some
        affected dataset lies ON a ``src`` → ``dst`` path (endpoints
        included): the composed product sums over every such path, so a
        mid-chain rewrite poisons it even when both endpoints survive.
        Enumeration only — nothing is dropped, no LRU touch, no fault."""
        self._sync()
        affected = [d for d in set(datasets) if d in self.index.datasets]
        if not affected:
            return []
        keys = [(k, "ram") for k in self._cache]
        keys += [(k, "spilled") for k in self._spilled]
        out = []
        for (src, dst), residency in keys:
            if any(self.index.path_exists(src, m)
                   and self.index.path_exists(m, dst) for m in affected):
                out.append((src, dst, residency))
        return out

    def invalidate_datasets(self, datasets) -> List[Tuple[str, str, str]]:
        """Drop every :meth:`stale_entries` entry: resident entries leave
        the LRU (their bytes released), on-disk payloads are DELETED from
        the spill store.  Returns the dropped triples.  The append-only-DAG
        keep-on-append policy is untouched — this is the escape hatch for
        REWRITES (erasure, what-if rebuilds), where recorded history itself
        changes and cached compositions over it must not survive."""
        dropped = self.stale_entries(datasets)
        for src, dst, _residency in dropped:
            key = (src, dst)
            entry = self._cache.pop(key, None)
            if entry is not None:
                self._bytes -= entry.nbytes()
            self._spilled.pop(key, None)
            # a resident entry may ALSO hold a stale disk copy (spilled
            # once, faulted back): _store_meta remembers it — delete both
            if key in self._store_meta:
                del self._store_meta[key]
                if self._spill_store is not None:
                    self._spill_store.delete(("rel", self.index.name) + key)
        return dropped

    # -- introspection --------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        per_backend = {"csr": 0, "bitplane": 0, "structured": 0}
        for entry in self._cache.values():
            per_backend[entry.backend] += 1
        out = {
            "index": self.index.name,
            "backend": self.backend,
            "entries": len(self._cache),
            "entries_csr": per_backend["csr"],
            "entries_bitplane": per_backend["bitplane"],
            "entries_structured": per_backend["structured"],
            "bytes": self._bytes,
            "budget_bytes": self.memory_budget_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "conversions": self.conversions,
            "extends": self.extends,
            "recomposes": self.recomposes,
            "spills": self.spills,
            "rehydrations": self.rehydrations,
            "spilled_entries": len(self._spilled),
        }
        if self._spill_store is not None:
            out["spill"] = self._spill_store.stats()
        return out
