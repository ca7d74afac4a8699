"""Serving: the lineage recorder and the async query tier.

* :class:`~repro.serve.engine.ServeEngine` — records each served batch's
  (response -> request) provenance and answers lineage over it, across an
  upstream data-prep boundary when one is attached;
* :class:`~repro.serve.tier.ServingTier` — the async micro-batching front
  door that fuses lineage queries across requests and tenants into single
  ``run_many`` passes, with bounded admission and per-tenant capability
  scoping (:mod:`repro.serve.admission`).
"""
from repro.serve.admission import (
    AdmissionError,
    QueueFullError,
    TenantOverloadError,
    TenantScope,
    TierClosedError,
)
from repro.serve.engine import GenerationResult, ServeEngine
from repro.serve.tier import ServingTier

__all__ = [
    "ServeEngine",
    "GenerationResult",
    "ServingTier",
    "TenantScope",
    "AdmissionError",
    "QueueFullError",
    "TenantOverloadError",
    "TierClosedError",
]
