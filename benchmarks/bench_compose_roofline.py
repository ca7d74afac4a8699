"""Roofline of the PAPER'S TECHNIQUE at pod scale (hillclimb cell #3).

The dataset-level audit of §IV — 'which source records reach the training
set' — at production size: a packed lineage relation between 4.2M corpus
documents and 131k packed sequences, sharded row-wise over the data axes of
the 16x16 mesh.  Three lowered programs are analyzed (hloanal terms):

  audit      AND + popcount + psum        (the backward_frontier/audit path)
  compose32  (OR,AND)-matmul, f32 unpack  (naive composition step)
  composebf  (OR,AND)-matmul, bf16 unpack (halved traffic, same result)

plus the Pallas bitplane kernel terms (repro.kernels).  The machine numbers
(peak FLOPs / HBM / VPU word-op rate) come from the cost model's active
:class:`~repro.core.costmodel.Constants` — the TPU-v5e defaults until a
calibration file overrides them — so this bench and the query router can
never disagree about the machine.

The ``kernels`` section is MEASURED, not analytic: the fused
:func:`repro.kernels.ops.batched_walk` against its per-hop unfused baseline
(``bitmatmul`` + ``bitset_rank`` + ``lineage_gather`` per hop) on a K-hop
chain, with the K×3 → 1 launch reduction asserted off the kernel layer's
dispatch counters.  ``--quick`` runs ONLY this measured section (no
512-device mesh lowering) and merges it into ``BENCH_query.json``.

    PYTHONPATH=src python -m benchmarks.bench_compose_roofline [--quick]
"""
import os
import sys

if "--quick" not in sys.argv:
    # full mode lowers against the 16x16 production mesh on host
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")

import json
import time

import numpy as np

from repro.core import costmodel

N_DOCS = 4_194_304        # 4M corpus documents
N_SEQ = 131_072           # packed sequences (the training set's row space)
DW = N_SEQ // 32          # packed words per doc row


def _spec(mesh, *axes):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P(*axes))


def lower_audit(mesh):
    import jax
    import jax.numpy as jnp

    rel = jax.ShapeDtypeStruct((N_DOCS, DW), jnp.uint32)
    mask = jax.ShapeDtypeStruct((DW,), jnp.uint32)
    group = jax.ShapeDtypeStruct((N_DOCS,), jnp.int32)

    def audit(rel, group, mask):
        hit_words = rel & mask[None, :]
        hits = jax.lax.population_count(hit_words).astype(jnp.int32).sum(axis=1) > 0
        onehot = jax.nn.one_hot(group, 8, dtype=jnp.int32)
        return (hits.astype(jnp.int32)[:, None] * onehot).sum(axis=0)

    with jax.set_mesh(mesh):
        return jax.jit(
            audit,
            in_shardings=(_spec(mesh, "data", None), _spec(mesh, "data"),
                          _spec(mesh, None)),
            out_shardings=_spec(mesh, None),
        ).lower(rel, group, mask).compile()


def lower_compose(mesh, unpack_dtype):
    import jax
    import jax.numpy as jnp

    # one composition hop: sequences->batches relation applied to the
    # doc->sequence relation: (N_DOCS, N_SEQ) x (N_SEQ, N_BATCH)
    n_batch_w = 1024 // 32
    a = jax.ShapeDtypeStruct((N_DOCS, DW), jnp.uint32)
    b = jax.ShapeDtypeStruct((N_SEQ, n_batch_w), jnp.uint32)

    def compose(a_bits, b_bits):
        shifts = jnp.arange(32, dtype=jnp.uint32)
        au = ((a_bits[:, :, None] >> shifts) & 1).reshape(N_DOCS, DW * 32)
        bu = ((b_bits[:, :, None] >> shifts) & 1).reshape(N_SEQ, n_batch_w * 32)
        c = (au.astype(unpack_dtype) @ bu.astype(unpack_dtype)) > 0
        cw = (c.reshape(N_DOCS, n_batch_w, 32).astype(jnp.uint32)
              << shifts[None, None, :]).sum(axis=-1, dtype=jnp.uint32)
        return cw

    with jax.set_mesh(mesh):
        return jax.jit(
            compose,
            in_shardings=(_spec(mesh, "data", None), _spec(mesh, None, None)),
            out_shardings=_spec(mesh, "data", None),
        ).lower(a, b).compile()


def _pack(rng, rows, cols, density):
    import jax.numpy as jnp

    from repro.kernels import ref

    return np.asarray(ref.pack_bits(jnp.asarray(rng.random((rows, cols)) < density)))


def _median_ms(fn, reps=5, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e3


def bench_kernels(n: int = 512, batch: int = 64, hops: int = 6,
                  reps: int = 5) -> dict:
    """MEASURED fused-vs-unfused walk on a K-hop chain.

    Both paths run through their kernel-launch guard (``use_pallas=None``:
    Pallas on TPU, the jnp oracles on hosts); the K×3 → 1 launch reduction
    is asserted exactly off :func:`repro.kernels.ops.launch_counts`, and
    the two results are byte-compared before timing.
    """
    from repro.kernels import ops as K

    rng = np.random.default_rng(7)
    planes = [_pack(rng, n, n, 0.02) for _ in range(hops)]
    mask = _pack(rng, batch, n, 0.05)

    def run_fused():
        out, counts = K.batched_walk(mask, planes, use_pallas=None)
        return np.asarray(out), np.asarray(counts)

    def run_unfused():
        out, counts = K.batched_walk_unfused(mask, planes, use_pallas=None)
        return np.asarray(out), np.asarray(counts)

    # launch accounting: one probe each, counted exactly
    K.reset_launch_counts()
    fused_out = run_fused()
    lc = K.launch_counts()
    launches_fused = sum(lc.values())
    assert launches_fused == 1, lc
    K.reset_launch_counts()
    unfused_out = run_unfused()
    lc = K.launch_counts()
    launches_unfused = sum(lc.values())
    assert launches_unfused == 3 * hops, lc
    assert np.array_equal(fused_out[0], unfused_out[0])
    assert np.array_equal(fused_out[1], unfused_out[1])

    fused_ms = _median_ms(run_fused, reps=reps)
    unfused_ms = _median_ms(run_unfused, reps=reps)
    section = {
        "n": n, "batch": batch, "hops": hops,
        "fused_ms": fused_ms, "unfused_ms": unfused_ms,
        "speedup": unfused_ms / fused_ms if fused_ms else float("inf"),
        "launches_fused": launches_fused,
        "launches_unfused": launches_unfused,
        "constants": costmodel.constants_provenance(),
    }
    print(f"\n== Fused batched walk: n={n}, B={batch}, K={hops} hops ==")
    print(f"unfused (3 launches/hop): {unfused_ms:8.2f} ms  "
          f"({launches_unfused} launches)")
    print(f"fused   (1 launch total): {fused_ms:8.2f} ms  "
          f"({launches_fused} launch)   speedup {section['speedup']:.1f}x")
    return section


def run(quick: bool = False):
    costmodel.maybe_load_calibration()
    c = costmodel.active_constants()
    kernels = bench_kernels() if quick else bench_kernels(reps=7)
    if quick:
        # the mesh-lowered variants force a 512-device host platform and a
        # multi-minute compile; quick mode reports the measured section only
        return {"kernels": kernels}

    from repro.launch.hloanal import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    import jax.numpy as jnp

    mesh = make_production_mesh()
    n_chips = 256
    rows = []
    for name, builder in [
        ("audit", lambda: lower_audit(mesh)),
        ("compose_f32", lambda: lower_compose(mesh, jnp.float32)),
        ("compose_bf16", lambda: lower_compose(mesh, jnp.bfloat16)),
    ]:
        compiled = builder()
        h = analyze_hlo(compiled.as_text())
        t_c = h.dot_flops / c.peak_flops
        t_m = h.traffic_bytes / c.hbm_bw
        t_x = h.collective_bytes / c.link_bw
        rows.append({"variant": name, "t_compute_s": t_c, "t_memory_s": t_m,
                     "t_collective_s": t_x,
                     "dominant": max([("compute", t_c), ("memory", t_m),
                                      ("collective", t_x)], key=lambda kv: kv[1])[0]})

    # analytic Pallas bitplane kernel terms for the same compose hop
    word_ops = (N_DOCS / n_chips) * N_SEQ * (1024 // 32)   # m*k*nw per device
    t_vpu = word_ops / c.vpu_word_ops
    bytes_hbm = ((N_DOCS / n_chips) * DW * 4               # A shard read
                 + N_SEQ * (1024 // 32) * 4                # B read (fits VMEM? no: streamed)
                 + (N_DOCS / n_chips) * (1024 // 32) * 4)  # C write
    rows.append({"variant": "compose_pallas(analytic)",
                 "t_compute_s": t_vpu, "t_memory_s": bytes_hbm / c.hbm_bw,
                 "t_collective_s": 0.0,
                 "dominant": "compute" if t_vpu > bytes_hbm / c.hbm_bw else "memory"})

    print("\n== Paper-technique roofline: 4.2M docs x 131k sequences, 16x16 mesh ==")
    print(f"{'variant':26s} {'compute':>10s} {'memory':>10s} {'collective':>11s} {'dominant':>9s}")
    for r in rows:
        print(f"{r['variant']:26s} {r['t_compute_s']:10.4f} {r['t_memory_s']:10.4f} "
              f"{r['t_collective_s']:11.4f} {r['dominant']:>9s}")
    return {"table": "compose_roofline", "rows": rows, "kernels": kernels,
            "machine": {"peak_flops": c.peak_flops, "hbm_bw": c.hbm_bw,
                        "link_bw": c.link_bw, "vpu_word_ops": c.vpu_word_ops,
                        "source": c.source}}


def _merge_trajectory(results: dict) -> None:
    """``BENCH_query.json`` belongs to bench_query.py; this bench only
    merges its ``kernels`` section (creating the file if needed)."""
    path = os.path.abspath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir,
        "BENCH_query.json"))
    data = {}
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    data["kernels"] = results["kernels"]
    with open(path, "w") as f:
        json.dump(data, f, indent=1, default=float)
    print(f"wrote {path} (kernels section)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="measured kernels section only (no 512-device mesh "
                    "lowering) — still merges into BENCH_query.json")
    args = ap.parse_args()
    _merge_trajectory(run(quick=args.quick))
