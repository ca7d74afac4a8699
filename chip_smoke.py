#!/usr/bin/env python3
"""Bring-up smoke of the capture -> prov(...) -> QuerySession -> ServingTier
path on a TPU, at the paper's own data sizes.

Run from the root of a checkout::

    python3 chip_smoke.py                # phases (a)-(d) on one chip
    python3 chip_smoke.py --four-chips   # only phase (e), on four chips

Every phase checks its answers byte for byte against a host reference
session (the per-op walker: no hop-cache, no fused kernel):

(a) the Table VIII use cases (German 1000x21, Compas 7214x53, Census
    32561x15) captured through ``track``; batched forward and backward
    record plans (B=64), one cells plan and one how plan each through
    ``index.session()``; plus the forced-bitplane hop-cache (the Pallas
    bitmatmul on TPU) for every use case whose source-to-sink plane fits
    the hop-cache budget; and Compas's record plans through the
    benchmark's session (no hop-cache): every hop is structured, so the
    gather walk must answer each from the chain's row map (``gather_walk``);
(b) the TPC-DI SF3 join (362,342 x 390,978 rows): batched trace-to-source
    (B=64) both ways; its planes are far over the fused walk's cap, so the
    gather walk answers each plan from the join's row map on the device
    (``gather_walk``, and ``fused_walk_oversize`` for a refusal); it also
    reports which hop-cache entries hold what;
(c) the fused K-hop walk at its plane cap: a deep chain with n=16384 and
    K=8 (256 MiB of planes), its hops held as explicit COO so that no row
    map can answer, B=64 both ways; the dense fused kernel must answer
    (``fused_walk`` and no ``gather_walk``), and the per-hop kernels it
    replaces must give the same frontier;
(d) the serving tier: 256 single-row lineage plans from 4 tenants through
    ``ServingTier(session, max_batch=32)`` over the Census index;
(e) ``--four-chips`` only: ``ShardedProvenanceIndex(n_shards=4,
    engine="collective")`` over the Census and deep-chain indexes.

Each phase prints one line (sizes, wall seconds including set-up, session
and hop-cache counters, kernel launch counts).  The last line is one JSON
object, ``{"ok": true, "device": {...}}``.  The script exits non-zero, and
prints no such line, when no TPU is present, when a phase disagrees with
its reference, or when no compiled Pallas kernel launched.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.core.hopcache import ComposedIndex  # noqa: E402
from repro.core.pipeline import ProvenanceIndex  # noqa: E402
from repro.dataprep.tracked import track  # noqa: E402
from repro.dataprep.usecases import (  # noqa: E402
    USECASES,
    build_deep_chain,
    make_tpcdi_join_inputs,
)
from repro.kernels import ops as K  # noqa: E402
from repro.provenance import (  # noqa: E402
    QuerySession,
    ShardedProvenanceIndex,
    prov,
)
from repro.serve import ServingTier  # noqa: E402

N_PROBES = 64
PROBE_ROWS = 4


class Mismatch(AssertionError):
    """A phase's answer differs from its host reference."""


def same(a, b) -> bool:
    """Byte-for-byte equality of query results (arrays, lists, tuples,
    hop records)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return a.dtype == b.dtype and a.shape == b.shape and \
            a.tobytes() == b.tobytes()
    if isinstance(a, (list, tuple)):
        return (type(a) is type(b) and len(a) == len(b)
                and all(same(x, y) for x, y in zip(a, b)))
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and same(dataclasses.astuple(a),
                                           dataclasses.astuple(b))
    return a == b


def check(what: str, got, want) -> None:
    if not same(got, want):
        raise Mismatch(f"{what}: answer differs from the host reference")


def reference(index) -> QuerySession:
    """The host reference: the per-op walker alone."""
    return QuerySession(index, use_hopcache=False, fused_walk=False)


def probe_sets(rng, n: int, count: int = N_PROBES, size: int = PROBE_ROWS):
    return [np.sort(rng.choice(n, size=min(size, n), replace=False))
            for _ in range(count)]


def record_plans(index, src: str, dst: str, rng, count: int = N_PROBES):
    """A batched forward plan src->dst and a batched backward plan
    dst->src, ``count`` seeded probe sets each."""
    n_src = index.datasets[src].n_rows
    n_dst = index.datasets[dst].n_rows
    return {
        "fwd": prov(index).source(src).rows_batch(
            probe_sets(rng, n_src, count)).forward().to(dst).plan(),
        "bwd": prov(index).source(dst).rows_batch(
            probe_sets(rng, n_dst, count)).backward().to(src).plan(),
    }


def session_counters(session) -> dict:
    return {k: v for k, v in session.counters.items() if v}


def hopcache_counters(composed) -> dict:
    keep = ("entries", "entries_structured", "entries_csr",
            "entries_bitplane", "bytes", "hits", "misses")
    st = composed.stats()
    return {k: st[k] for k in keep}


def plane_bytes(index, src: str, dst: str) -> int:
    """Bytes of the square-padded plane stack the fused walk would stream
    for the src->dst chain (the quantity its size cap compares)."""
    from repro.core.compose import path_tensors

    chain = path_tensors(index, src, dst)
    n_max = max(max(op.tensor.n_in[s], op.tensor.n_out) for op, s in chain)
    return len(chain) * n_max * n_max // 8


ROUTE_COUNTERS = ("fused_walk", "gather_walk", "gather_walk_declined",
                  "fused_walk_oversize")


def routes(session) -> dict:
    return {k: session.counters[k] for k in ROUTE_COUNTERS}


def check_gather_route(index, plans: dict, ref, fused_walk=None) -> dict:
    """The fwd and bwd record plans through the benchmark's Compas
    session (no hop-cache); with the fused walk on (on a TPU by default),
    the gather walk must answer both."""
    walk = QuerySession(index, use_hopcache=False, fused_walk=fused_walk)
    for kind in ("fwd", "bwd"):
        check(f"gather route {kind}", walk.run(plans[kind]),
              ref.run(plans[kind]))
    routed = routes(walk)
    on = K.on_tpu() if fused_walk is None else fused_walk
    if on and routed != {"fused_walk": 2, "gather_walk": 2,
                         "gather_walk_declined": 0,
                         "fused_walk_oversize": 0}:
        raise RuntimeError(
            f"the gather walk did not answer every record plan: {routed}")
    return routed


def capture_usecase(name: str, seed: int = 0):
    """Capture one Table VIII pipeline; returns (index, source, sink)."""
    make, run = USECASES[name]
    index = ProvenanceIndex(name)
    sink = run(index, make())
    return index, f"{name}_src", sink.dataset_id


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def phase_usecases(names=("german", "compas", "census"), seed: int = 0,
                   count: int = N_PROBES) -> tuple:
    """(a): record, cells and how plans on the Table VIII use cases."""
    rng = np.random.default_rng(seed)
    out, indexes = {}, {}
    for name in names:
        index, src, sink = capture_usecase(name, seed)
        indexes[name] = (index, src, sink)
        sess, ref = index.session(), reference(index)
        plans = record_plans(index, src, sink, rng, count)
        probe = probe_sets(rng, index.datasets[src].n_rows, 1)[0]
        plans["cells"] = prov(index).source(src).rows(probe).attrs(
            [0, 1]).forward().to(sink).plan()
        plans["how"] = prov(index).source(src).rows(probe).forward().to(
            sink).how().plan()
        for kind, plan in plans.items():
            check(f"{name} {kind}", sess.run(plan), ref.run(plan))
        line = {
            "rows": index.datasets[src].n_rows,
            "cols": index.datasets[src].n_cols,
            "sink_rows": index.datasets[sink].n_rows,
            "ops": len(index.ops),
            "session": session_counters(sess),
            "hopcache": hopcache_counters(sess.composed),
        }
        if name == "compas":
            line["gather_route"] = check_gather_route(index, plans, ref)
        # the forced-bitplane hop-cache composes and probes packed planes
        # (bitmatmul); only where the composed plane fits its budget
        bp = ComposedIndex(index, backend="bitplane")
        need = index.datasets[src].n_rows * \
            -(-index.datasets[sink].n_rows // 32) * 4
        if need <= bp.memory_budget_bytes:
            check(f"{name} bitplane fwd",
                  bp.q1_forward(src, plans["fwd"].rows, sink),
                  ref.run(plans["fwd"]))
            check(f"{name} bitplane bwd",
                  bp.q2_backward(sink, plans["bwd"].rows, src),
                  ref.run(plans["bwd"]))
            line["bitplane_hopcache"] = hopcache_counters(bp)
        else:
            line["bitplane_hopcache"] = (
                f"skipped: plane {need} B > budget {bp.memory_budget_bytes} B")
        out[name] = line
    return out, indexes


def phase_tpcdi(scale: int = 3, seed: int = 0, count: int = N_PROBES) -> dict:
    """(b): batched trace-to-source over the TPC-DI join, both ways."""
    left, right = make_tpcdi_join_inputs(scale)
    index = ProvenanceIndex(f"tpcdi-sf{scale}")
    sink = track(left, index, "tpcdi_left").join(
        track(right, index, "tpcdi_right"), on="key", how="inner").mark_sink()
    sess, ref = index.session(), reference(index)
    rng = np.random.default_rng(seed)
    plans = record_plans(index, "tpcdi_left", sink.dataset_id, rng, count)
    n_sink = index.datasets[sink.dataset_id].n_rows
    plans["bwd_right"] = prov(index).source(sink.dataset_id).rows_batch(
        probe_sets(rng, n_sink, count)).backward().to("tpcdi_right").plan()
    for kind, plan in plans.items():
        check(f"tpcdi {kind}", sess.run(plan), ref.run(plan))
    routed = {k: sess.counters[k] for k in ("gather_walk",
                                            "fused_walk_oversize")}
    # at every Table XI scale factor the join's planes are far over the
    # fused walk's cap, so on the chip its row map must answer every plan
    if K.on_tpu() and routed != {"gather_walk": len(plans),
                                 "fused_walk_oversize": 0}:
        raise RuntimeError(
            f"the gather walk did not answer every plan: {sess.counters}")
    # the session walks single-hop joins; probing the hop-cache directly
    # shows which backend its density rule picks for each side of the join
    check("tpcdi hop-cache fwd", sess.composed.q1_forward(
        "tpcdi_left", plans["fwd"].rows, sink.dataset_id),
        ref.run(plans["fwd"]))
    check("tpcdi hop-cache bwd", sess.composed.q2_backward(
        sink.dataset_id, plans["bwd_right"].rows, "tpcdi_right"),
        ref.run(plans["bwd_right"]))
    return {
        "scale": scale,
        "left_rows": left.n_rows,
        "right_rows": right.n_rows,
        "join_rows": n_sink,
        "fused_plane_bytes": plane_bytes(index, "tpcdi_right",
                                         sink.dataset_id),
        **routed,
        "session": session_counters(sess),
        "hopcache": hopcache_counters(sess.composed),
    }


def phase_fused_walk(n: int = 16384, n_ops: int = 8, seed: int = 0,
                     count: int = N_PROBES, fused_walk=None) -> dict:
    """(c): the fused K-hop walk at its plane cap, both ways."""
    index, sink = build_deep_chain(seed=seed, n=n, n_ops=n_ops)
    for op in index.ops:  # the same relations, with no structure
        op.tensor = op.tensor.as_coo()
    sess = QuerySession(index, use_hopcache=False, fused_walk=fused_walk)
    ref = reference(index)
    rng = np.random.default_rng(seed)
    plans = record_plans(index, "chain_src", sink, rng, count)
    for kind, plan in plans.items():
        check(f"deep chain {kind}", sess.run(plan), ref.run(plan))
    if routes(sess) != {"fused_walk": len(plans), "gather_walk": 0,
                        "gather_walk_declined": 0, "fused_walk_oversize": 0}:
        raise RuntimeError(
            f"fused walk did not answer every plan: {sess.counters}")
    # the per-hop baseline the fused kernel replaces (bitmatmul, bitset_rank
    # and lineage_gather per hop) must give the same frontier and counts
    from repro.core.compose import path_tensors
    from repro.core.provtensor import pack_bitplane

    planes = [op.tensor.bitplane_fwd(slot)
              for op, slot in path_tensors(index, "chain_src", sink)]
    mask = np.zeros((count, n), dtype=bool)
    for b, rows in enumerate(plans["fwd"].rows):
        mask[b, rows] = True
    fused = K.batched_walk(pack_bitplane(mask), planes)
    unfused = K.batched_walk_unfused(pack_bitplane(mask), planes)
    check("deep chain unfused baseline", [np.asarray(x) for x in unfused],
          [np.asarray(x) for x in fused])
    return {
        "rows": n,
        "hops": len(index.ops),
        "plane_bytes": plane_bytes(index, "chain_src", sink),
        "session": session_counters(sess),
    }


def phase_serving(index, src: str, sink: str, n_requests: int = 256,
                  n_tenants: int = 4, max_batch: int = 32,
                  seed: int = 0) -> dict:
    """(d): single-row lineage plans from several tenants through the
    serving tier; every answer must equal ``session.run``."""
    sess = index.session()
    rng = np.random.default_rng(seed)
    n_src = index.datasets[src].n_rows
    n_dst = index.datasets[sink].n_rows
    plans = []
    for i in range(n_requests):
        if i % 2:
            plans.append(prov(index).source(sink).rows(
                [int(rng.integers(n_dst))]).backward().to(src).plan())
        else:
            plans.append(prov(index).source(src).rows(
                [int(rng.integers(n_src))]).forward().to(sink).plan())
    with ServingTier(sess, max_batch=max_batch) as tier:
        futures = []
        for t in range(n_tenants):
            futures += tier.submit_many_nowait(f"tenant{t}",
                                               plans[t::n_tenants])
        got = [f.result(timeout=600) for f in futures]
        tier_counters = dict(tier.counters)
    order = [p for t in range(n_tenants) for p in plans[t::n_tenants]]
    for i, (plan, answer) in enumerate(zip(order, got)):
        check(f"serving request {i}", answer, sess.run(plan))
    return {
        "requests": n_requests,
        "tenants": n_tenants,
        "max_batch": max_batch,
        "tier": {k: v for k, v in tier_counters.items() if v},
        "session": session_counters(sess),
        "hopcache": hopcache_counters(sess.composed),
    }


def phase_sharded(indexes, n_shards: int = 4, seed: int = 0,
                  count: int = N_PROBES) -> dict:
    """(e): the collective sharded engine against the unsharded walker."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (index, src, sink) in indexes.items():
        view = ShardedProvenanceIndex(index, n_shards=n_shards,
                                      engine="collective")
        sess = view.session(use_hopcache=False, fused_walk=False)
        ref = reference(index)
        for kind, plan in record_plans(index, src, sink, rng, count).items():
            check(f"{name} sharded {kind}", sess.run(plan), ref.run(plan))
        out[name] = {"rows": index.datasets[src].n_rows,
                     "ops": len(index.ops), "engine": view.engine_name,
                     "session": session_counters(sess)}
    return out


def run_phase(label: str, fn, *args, **kwargs):
    K.reset_launch_counts()
    t0 = time.perf_counter()
    result = fn(*args, **kwargs)
    line = result[0] if isinstance(result, tuple) else result
    print(f"phase {label}: " + json.dumps({
        "seconds": round(time.perf_counter() - t0, 3),
        **line,
        "launches": K.launch_counts(),
        "pallas_launches": K.pallas_launch_counts(),
    }, default=str), flush=True)
    return result, K.pallas_launch_counts()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded collective engine on 4 chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(ROOT)

    if args.four_chips:
        indexes = {"census": capture_usecase("census")}
        index, sink = build_deep_chain(n=16384, n_ops=8)
        indexes["deep_chain"] = (index, "chain_src", sink)
        run_phase("e.sharded", phase_sharded, indexes, n_shards=4)
    else:
        pallas: dict = {}
        (_, indexes), lc = run_phase("a.usecases", phase_usecases)
        for k, v in lc.items():
            pallas[k] = pallas.get(k, 0) + v
        for label, fn, a in (
                ("b.tpcdi_sf3", phase_tpcdi, ()),
                ("c.fused_walk", phase_fused_walk, ()),
                ("d.serving", phase_serving, indexes["census"])):
            _, lc = run_phase(label, fn, *a)
            for k, v in lc.items():
                pallas[k] = pallas.get(k, 0) + v
        missing = [k for k in ("bitmatmul", "batched_walk", "gather_walk")
                   if not pallas.get(k)]
        if missing:
            print(f"chip_smoke: no compiled Pallas launch of {missing}",
                  file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
