PY ?= python
TIMEOUT ?= 900

.PHONY: test test-fast test-sharded test-kernels bench-query bench-quick \
        bench-serving bench-serving-quick bench-stream bench-stream-quick \
        bench-impact bench-impact-quick bench-roofline bench-roofline-quick ci

# tier-1 verify (ROADMAP.md): the whole suite, stop at first failure
test:
	timeout $(TIMEOUT) env PYTHONPATH=src $(PY) -m pytest -x -q

# quick signal: the provenance core only (no model/trainer substrate) —
# incl. the structured-representation parity suite, so representation-layer
# regressions fail in this cheap lane before the full suite runs
test-fast:
	timeout 300 env PYTHONPATH=src $(PY) -m pytest -x -q \
	  tests/test_provtensor.py tests/test_schema.py tests/test_queries.py \
	  tests/test_query_parity.py tests/test_structured.py \
	  tests/test_compose.py tests/test_recompute.py

# kernel lane: Pallas-vs-oracle parity (interpret mode), compiles for a
# described v5e chip, the fused batched-walk grid, launch accounting, and
# calibration round-trips
test-kernels:
	timeout $(TIMEOUT) env PYTHONPATH=src $(PY) -m pytest -x -q \
	  tests/test_kernels.py tests/test_backend_parity.py tests/test_tpu_compile.py

# the CI multi-device lane locally: 8 forced host CPU devices so the
# shard_map collective walkers and mesh integration paths really execute
test-sharded:
	timeout $(TIMEOUT) env PYTHONPATH=src JAX_PLATFORMS=cpu \
	  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) -m pytest -x -q tests/test_sharded_parity.py \
	  tests/test_federation.py tests/test_integration_sharded.py

bench-query:
	env PYTHONPATH=src $(PY) benchmarks/bench_query.py

# reduced configuration (small chain, 1 rep) — the CI smoke step; still
# exercises every section incl. cost-model routing and writes BENCH_query.json
bench-quick:
	env PYTHONPATH=src $(PY) benchmarks/bench_query.py --quick

# serving tier vs sync per-request loop (saturation + Poisson open loop);
# merges the `serving` section into BENCH_query.json
bench-serving:
	env PYTHONPATH=src $(PY) benchmarks/bench_serving.py

bench-serving-quick:
	env PYTHONPATH=src $(PY) benchmarks/bench_serving.py --quick

# streaming capture: incremental extension vs recompose + bounded-residency
# append stream; merges the `stream` section into BENCH_query.json
bench-stream:
	env PYTHONPATH=src $(PY) benchmarks/bench_stream.py

bench-stream-quick:
	env PYTHONPATH=src $(PY) benchmarks/bench_stream.py --quick

# impact analysis: erasure closure vs per-row loop, what-if replay vs full
# re-run (>= 5x at n=100k), federated cells vs merged; merges the `impact`
# section into BENCH_query.json
bench-impact:
	env PYTHONPATH=src $(PY) benchmarks/bench_impact.py

bench-impact-quick:
	env PYTHONPATH=src $(PY) benchmarks/bench_impact.py --quick

# pod-scale roofline (512 forced host devices) + the MEASURED fused-walk
# kernels section; --quick skips the mesh lowering and merges only the
# `kernels` section into BENCH_query.json
bench-roofline:
	env PYTHONPATH=src $(PY) -m benchmarks.bench_compose_roofline

bench-roofline-quick:
	env PYTHONPATH=src $(PY) -m benchmarks.bench_compose_roofline --quick

# mirrors .github/workflows/ci.yml
ci:
	$(PY) -m compileall -q src
	$(MAKE) test
