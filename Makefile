PY ?= python
TIMEOUT ?= 900

.PHONY: test test-fast test-sharded test-kernels ci

# tier-1 verify (ROADMAP.md): the whole suite, stop at first failure
test:
	timeout $(TIMEOUT) env PYTHONPATH=src $(PY) -m pytest -x -q

# quick signal: the provenance core only — incl. the structured-representation
# parity suite, so representation-layer regressions fail in this cheap lane
# before the full suite runs
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
# shard_map collective walkers really execute
test-sharded:
	timeout $(TIMEOUT) env PYTHONPATH=src JAX_PLATFORMS=cpu \
	  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	  $(PY) -m pytest -x -q tests/test_sharded_parity.py \
	  tests/test_federation.py

# mirrors .github/workflows/ci.yml
ci:
	$(PY) -m compileall -q src
	$(MAKE) test
