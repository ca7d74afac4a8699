"""Serving lineage recorder: request-level provenance of served responses.

A serving stack runs its model however it likes; :class:`ServeEngine`
records what it served.  :meth:`ServeEngine.record` takes one batch's
``(B, n_new)`` response tokens and traces every response row back to its
REQUEST RECORD — record-level why-provenance of the serving path, captured
with the same ProvTensor machinery as the data pipeline (each generated
token derives from its request row: an identity-tensor-per-step collapsed
to one HAUGMENT link).

The engine owns its OWN :class:`ProvenanceIndex` (the serving pipeline's)
and a :class:`~repro.provenance.catalog.ProvCatalog` around it.  Upstream
data-preparation provenance attaches through ``upstream=``:

* a :class:`~repro.provenance.catalog.BoundaryHandle` minted by
  ``prep_index.export(dataset_id)`` — the engine registers the read-only
  capability, never the prep index object itself, and links each recorded
  request batch to boundary rows through the ``request_ids`` alignment;
* or ``(catalog, "name/dataset")`` — the engine registers its serving
  index into an EXISTING catalog and uses that qualified ref as the
  boundary.

``record(...)`` registers the (response -> request) op;
:meth:`response_lineage` / :meth:`response_lineage_batch` compile to
:class:`QueryPlan`\\ s — serving-local targets route through the index's
shared ``QuerySession`` (ONE composed relation per endpoint pair), upstream
targets route through the catalog's
:class:`~repro.provenance.federation.FederatedSession`, tracing responses
all the way back to raw prep sources across the boundary.

The legacy ``prov_index=`` attach — handing the engine the whole prep
index to record into — is DEPRECATED (it grants the serving tier mutation
rights over data-prep provenance): it still works, wrapped in a
single-entry catalog, and warns once per process.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import List, Optional, Set, Tuple

import numpy as np

from repro.core.opcat import AttrMap, CaptureInfo, OpCategory
from repro.core.pipeline import ProvenanceIndex
from repro.dataprep.table import Table
from repro.provenance.catalog import (
    BoundaryHandle,
    ProvCatalog,
    qualify,
    split_ref,
)

__all__ = ["ServeEngine", "GenerationResult"]

_DEPRECATION_WARNED: Set[str] = set()


def _warn_once(key: str, message: str) -> None:
    """Once-per-process deprecation (the q1-q11 shim pattern), attributed
    to the caller's ``ServeEngine(...)`` line."""
    if key in _DEPRECATION_WARNED:
        return
    _DEPRECATION_WARNED.add(key)
    warnings.warn(message, DeprecationWarning, stacklevel=3)


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray        # (B, n_new)
    request_ids: np.ndarray   # (B,) provenance: emitted row -> request row
    # set once the batch is recorded into the engine's index:
    request_dataset: Optional[str] = None
    response_dataset: Optional[str] = None


class ServeEngine:
    """The serving index, its catalog, and lineage queries over both."""

    def __init__(self, name: str = "serve", *, upstream=None,
                 prov_index: Optional[ProvenanceIndex] = None) -> None:
        self._n_generations = 0
        self._upstream: Optional[Tuple[str, str]] = None  # (member, boundary ds)
        if prov_index is not None:
            if upstream is not None:
                raise ValueError(
                    "pass either upstream= (catalog attach) or the deprecated "
                    "prov_index=, not both")
            _warn_once(
                "prov_index",
                "ServeEngine(prov_index=...) is deprecated: handing the "
                "serving tier the whole data-prep index grants it record() "
                "rights over prep provenance.  Attach upstream lineage with "
                "upstream=prep_index.export(dataset_id) (a read-only "
                "BoundaryHandle) or upstream=(catalog, 'name/dataset') "
                "instead; the passed index is wrapped in a single-entry "
                "catalog for now.",
            )
            self.prov = prov_index
            self._serve_name = "serve"
            self.catalog = ProvCatalog(name)
            self.catalog.register(self._serve_name, self.prov)
            return
        self.prov = ProvenanceIndex(name)
        if upstream is None:
            self._serve_name = "serve"
            self.catalog = ProvCatalog(name)
            self.catalog.register(self._serve_name, self.prov)
        elif isinstance(upstream, BoundaryHandle):
            self._serve_name = "serve"
            self.catalog = ProvCatalog(name)
            up_name = upstream.index_name
            if not up_name or "/" in up_name or up_name == self._serve_name:
                up_name = "upstream"
            self.catalog.register(up_name, upstream)
            self.catalog.register(self._serve_name, self.prov)
            self._upstream = (up_name, upstream.boundary)
        elif (isinstance(upstream, tuple) and len(upstream) == 2
                and isinstance(upstream[0], ProvCatalog)):
            catalog, ref = upstream
            catalog.datasets[ref]   # resolve the DATASET now: a typo'd ref
                                    # must fail here, not at first record()
            serve_name, i = "serve", 2
            while serve_name in catalog.members:
                serve_name, i = f"serve{i}", i + 1
            catalog.register(serve_name, self.prov)
            self.catalog = catalog
            self._serve_name = serve_name
            self._upstream = split_ref(ref)
        else:
            raise TypeError(
                f"upstream= takes a BoundaryHandle or (ProvCatalog, "
                f"'name/dataset'), got {type(upstream).__name__}")

    @property
    def session(self):
        """The engine's (index-shared) provenance QuerySession."""
        return self.prov.session()

    @property
    def federation(self):
        """The catalog's shared FederatedSession (cross-index lineage)."""
        return self.catalog.session()

    # -- provenance capture ----------------------------------------------------
    def record(self, tokens, *, prompt_len: int, request_ids=None,
               request_source: Optional[str] = None) -> GenerationResult:
        """Record one served batch: the (response row -> request row)
        HAUGMENT op over ``tokens``, the ``(B, n_new)`` response tokens the
        caller's model produced for ``B`` requests of ``prompt_len`` prompt
        tokens each.

        Without ``request_source`` the batch's requests become a fresh
        ``requests@N`` dataset and its responses ``responses@N``.  With
        ``request_source`` the responses link to rows of an EXISTING
        dataset of the serving index (``request_ids`` are row indices into
        it).  With an ``upstream=`` attach and no ``request_source``, the
        fresh request dataset is LINKED to the upstream boundary through
        the ``request_ids`` row alignment (each request row came from that
        boundary row; ``-1`` marks a request with no upstream origin) —
        lineage then continues into the data-prep pipeline across the
        federation."""
        tokens = np.asarray(tokens)
        if tokens.ndim != 2:
            raise ValueError(
                f"tokens must be (B, n_new), got shape {tokens.shape}")
        b, n_new = tokens.shape
        request_ids_given = request_ids is not None
        if request_ids is None:
            request_ids = np.arange(b, dtype=np.int64)
        result = GenerationResult(tokens=tokens,
                                  request_ids=np.asarray(request_ids))
        # unique per INDEX, not per engine: several engines may share one
        # prov index (the documented pattern), or the index may already hold
        # earlier generations
        gid = self._n_generations
        while (f"responses@{gid}" in self.prov.datasets
               or f"requests@{gid}" in self.prov.datasets):
            gid += 1
        self._n_generations = gid + 1
        if request_source is None:
            alignment = None
            if self._upstream is not None:
                # the boundary link is a lineage ASSERTION — never fabricate
                # it from the arange() default, and validate the alignment
                # BEFORE any index mutation so a bad batch can't leave an
                # orphan requests@N dataset behind
                if not request_ids_given:
                    raise ValueError(
                        "upstream-attached engines need explicit request_ids "
                        "(rows of the boundary dataset, -1 for requests with "
                        "no upstream origin) to record provenance")
                up_name, boundary = self._upstream
                n_up = self.catalog.datasets[qualify(up_name, boundary)].n_rows
                alignment = np.asarray(result.request_ids, np.int64)
                if alignment.size and (alignment.max() >= n_up
                                       or alignment.min() < -1):
                    raise ValueError(
                        f"request_ids must be rows of the boundary dataset "
                        f"{qualify(up_name, boundary)!r} (in [-1, {n_up})), "
                        f"got range [{alignment.min()}, {alignment.max()}]")
            req_ds = f"requests@{gid}"
            self.prov.add_source(req_ds, Table.from_columns({
                "request_id": np.asarray(result.request_ids, np.float32),
                "prompt_len": np.full(b, prompt_len, np.float32),
            }))
            src_rows = np.arange(b, dtype=np.int32)
            if alignment is not None:
                self.catalog.link(
                    qualify(up_name, boundary),
                    qualify(self._serve_name, req_ds),
                    alignment=alignment,
                )
        else:
            if request_source not in self.prov.datasets:
                raise KeyError(f"unknown request dataset {request_source!r}")
            req_ds = request_source
            src_rows = np.asarray(result.request_ids, dtype=np.int32)
        resp_ds = f"responses@{gid}"
        self.prov.record(
            [req_ds], resp_ds,
            Table.from_columns({
                "request_id": np.asarray(result.request_ids, np.float32),
                "n_tokens": np.full(b, n_new, np.float32),
            }),
            CaptureInfo(op_name="generate", category=OpCategory.HAUGMENT,
                        contextual=False, n_out=b,
                        n_in=[self.prov.datasets[req_ds].n_rows],
                        src_rows=src_rows,
                        attr_maps=[AttrMap(kind="identity")],
                        params={"n_new": n_new, "prompt_len": prompt_len}),
            keep_output=True,
        )
        result.request_dataset = req_ds
        result.response_dataset = resp_ds
        return result

    # -- lineage queries (shared session / federation) ---------------------------
    def _lineage_target(self, dst: str) -> Tuple[bool, str]:
        """Resolve a lineage target dataset: ``(federated?, ref)``.

        Accepts a dataset of the serving index (local plan), a qualified
        catalog ref (``"prep/raw"``), or a bare dataset of the attached
        upstream member (auto-qualified)."""
        if dst in self.prov.datasets:
            return False, dst
        if "/" in dst and dst in self.catalog.datasets:
            return True, dst
        if self._upstream is not None:
            ref = qualify(self._upstream[0], dst)
            if ref in self.catalog.datasets:
                return True, ref
        raise KeyError(
            f"unknown lineage target {dst!r}: not a serving dataset, a "
            f"qualified catalog ref, or an upstream dataset")

    def _lineage_builder(self, result: GenerationResult, dst: str):
        from repro.provenance import prov

        if result.response_dataset is None:
            raise ValueError("batch was not recorded (ServeEngine.record)")
        federated, ref = self._lineage_target(dst)
        if not federated:
            qb = prov(self.prov).source(result.response_dataset)
            return qb.backward().to(ref), self.session
        qb = prov(self.catalog).source(
            qualify(self._serve_name, result.response_dataset))
        return qb.backward().to(ref), self.federation

    def response_lineage(self, result: GenerationResult, rows=None,
                         upstream: Optional[str] = None) -> np.ndarray:
        """Rows of ``upstream`` (default: the request dataset) that the given
        response rows derive from.  Serving-local targets probe ONE shared
        composed relation; upstream targets cross the boundary through the
        catalog's FederatedSession (plan split + mask stitch), so a response
        token traces to raw prep sources without the engine ever holding the
        prep index."""
        if rows is None:
            rows = np.ones(result.tokens.shape[0], dtype=bool)
        qb, sess = self._lineage_builder(
            result, upstream if upstream is not None else result.request_dataset)
        return qb.rows(rows).run(sess)

    def response_lineage_batch(self, result: GenerationResult, rows_batch,
                               upstream: Optional[str] = None) -> List[np.ndarray]:
        """Per-request lineage for MANY probe sets in one fused pass — one
        packed probe per member segment, even across the boundary."""
        qb, sess = self._lineage_builder(
            result, upstream if upstream is not None else result.request_dataset)
        return qb.rows_batch(rows_batch).run(sess)

    def erasure_impact(self, rows, source: Optional[str] = None,
                       apply: bool = False):
        """Deletion-propagation plan for erasing ``rows`` of ``source`` —
        the serving tier's GDPR entry point.

        ``source`` defaults to the attached upstream boundary dataset;
        bare names are resolved like lineage targets (serving dataset
        first, then the upstream member).  The closure crosses every
        boundary link downstream — an upstream erasure reaches through
        request batches into recorded responses — and the returned
        :class:`~repro.provenance.impact.RecomputePlan` lists affected
        datasets in execution order plus the stale composed relations
        (member hop-caches AND the catalog's stitched cross-relations).
        ``apply=True`` drops those stale entries before returning."""
        from repro.provenance.impact import apply_invalidations, erasure_plan

        if source is None:
            if self._upstream is None:
                raise ValueError(
                    "no upstream provenance attached; pass source=")
            ref = qualify(*self._upstream)
        else:
            _, ref = self._lineage_target(source)
            if "/" not in ref:
                ref = qualify(self._serve_name, ref)
        plan = erasure_plan(self.catalog, ref, rows)
        if apply:
            apply_invalidations(self.catalog, plan)
        return plan

    # -- serving-tier integration -------------------------------------------------
    def as_backend(self) -> "_EngineBackend":
        """This engine as a :class:`~repro.serve.tier.ServingTier` backend.

        Plans execute through the catalog's shared ``FederatedSession`` —
        serving-local probes delegate to the engine's own ``QuerySession``
        (single-member plans always do), upstream targets split and stitch
        across the boundary, and ``run_many`` fuses either kind across
        requests.  Bare (unqualified) refs naming serving-index datasets
        are qualified with the engine's member name in ``prepare`` so
        tenants can submit ``responses@0 -> requests@0`` probes without
        knowing the catalog layout — and so capability scopes and fuse
        buckets see one canonical spelling per dataset.
        """
        return _EngineBackend(self)


class _EngineBackend:
    """Tier backend adapter over one engine's federation session."""

    def __init__(self, engine: ServeEngine) -> None:
        self._engine = engine

    def _qualify_ref(self, ref: Optional[str]) -> Optional[str]:
        if ref is None or "/" in ref:
            return ref
        if ref in self._engine.prov.datasets:
            return qualify(self._engine._serve_name, ref)
        return ref      # unknown bare ref: let the session raise its error

    def prepare(self, plan):
        refs = {r: self._qualify_ref(r) for r in plan.refs()}
        if all(k == v for k, v in refs.items()):
            return plan
        sub = lambda r: refs.get(r, r) if r is not None else None  # noqa: E731
        return dataclasses.replace(
            plan, source=sub(plan.source), target=sub(plan.target),
            via=sub(plan.via), anchor=sub(plan.anchor))

    def run_many(self, plans) -> List:
        return self._engine.federation.run_many(plans)

    def stats(self):
        return self._engine.federation.stats()
