"""Batched serving with request-level lineage through the serving tier.

    PYTHONPATH=src python examples/serve_with_lineage.py

Records a batch of served requests: the serving stack's own model
produced the response tokens (a seeded stand-in array here), and the
engine records each response row's request row.  The engine owns its OWN
provenance index wrapped in a single-entry :class:`ProvCatalog`
(``engine.catalog``): serving-local lineage routes through the index's
shared ``QuerySession``, and the same catalog is where an upstream
data-prep boundary attaches (``upstream=prep_index.export(...)`` — see
``examples/federated_lineage.py`` for the cross-index trace-to-source
flow).  The legacy ``prov_index=`` attach is deprecated.

Per-request lineage probes are served through the async micro-batching
:class:`~repro.serve.tier.ServingTier` (``engine.as_backend()``):
concurrent tenants submit single-probe plans, the tier coalesces them by
fuse key into fused ``run_many`` passes, and admission scopes each tenant
to a capability ref set.
"""
import numpy as np

from repro.provenance import prov
from repro.serve import ServingTier
from repro.serve.engine import ServeEngine

B, SP, NEW, VOCAB = 4, 8, 6, 32_000
rng = np.random.default_rng(1)
# the (B, NEW) response tokens of B prompts of SP tokens each, as the
# serving stack's model would return them
tokens = rng.integers(1, VOCAB, (B, NEW)).astype(np.int32)

engine = ServeEngine()
# the engine's serving index is registered in its catalog under "serve";
# the shared session's cost model routes per plan — cheap adjacent
# (response -> request) hops stay on the walk, and sustained probe demand
# against a distant pair amortizes a composition and flips to the hop-cache
print("catalog:", engine.catalog)
result = engine.record(tokens, prompt_len=SP,
                       request_ids=np.array([101, 102, 103, 104]))
print("served tokens:\n", result.tokens)
print("recorded:", result.request_dataset, "->", result.response_dataset)

# --- per-request lineage through the shared session ----------------------------
print("\nQ2: response row 2 derives from request row:",
      engine.response_lineage(result, rows=[2]),
      "(request_id", int(result.request_ids[2]), ")")

# batched per-request lineage: every response row traced in ONE fused probe
per_request = engine.response_lineage_batch(result, [[i] for i in range(B)])
print("Q2 batch: response row -> request row:",
      {i: r.tolist() for i, r in enumerate(per_request)})

# --- the same probes, served: the async micro-batching tier --------------------
# many tenants each trace THEIR response row; same-shape plans coalesce
# into fused passes (bare serving-local refs are qualified by the backend)
with ServingTier(engine.as_backend(), max_batch=16, max_wait_ms=2.0) as tier:
    futs = [
        tier.submit_nowait(
            f"tenant-{i % 2}",
            prov(engine.prov).source(result.response_dataset).rows([i])
            .backward().to(result.request_dataset).plan())
        for i in range(B)
    ]
    served = [f.result(timeout=60.0) for f in futs]
assert all(s.tolist() == r.tolist() for s, r in zip(served, per_request))
stats = tier.stats()
print("tier: served", stats["tier"]["completed"], "probes in",
      stats["tier"]["batches"], "fused batch(es), max width",
      stats["tier"]["max_batch_seen"])

# forward plans run through the same session/composed relations — spelled
# either against the index or against the catalog with a qualified ref
print("Q1: request row 0 produced response rows:",
      prov(engine.prov).source(result.request_dataset).rows([0])
      .forward().to(result.response_dataset).run(engine.session))
print("Q1 (catalog ref):",
      prov(engine.catalog).source(f"serve/{result.request_dataset}").rows([0])
      .forward().to(f"serve/{result.response_dataset}").run())

print("\nsession stats (shared composed relations):", engine.session.stats())
print("federation stats (single-entry catalog):",
      engine.federation.stats()["federation"])
print("provenance bytes for the serving path:", engine.prov.prov_nbytes())
