"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology and refuses what the chip would refuse
(block tiling, unsupported primitives, scoped VMEM, device memory), which
interpret-mode parity tests never see.  Shapes are the widths the chip
smoke (``chip_smoke.py``) runs: the Table VIII use cases, the fused walk
at its 256 MiB plane cap, and the gather walk over the Compas chain and
the TPC-DI SF3 join.  Nothing runs, so these say nothing about results or
times.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as K

HBM_BYTES = 16 * 10**9  # one TPU v5e chip


@pytest.fixture(scope="module")
def topo():
    # keep the compiler's logs out of the temp dir
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip: keep the cache off around it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _words(n: int) -> int:
    return -(-n // 32)


def _check(compiled) -> None:
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is in
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("m,k,n", [
    (1000, 1000, 1000),      # German: composing two hop planes
    (7214, 7214, 6907),      # Compas: hop plane onto its sink
    (64, 7214, 6907),        # Compas: a B=64 bitplane probe
    (64, 32561, 32561),      # Census width: a B=64 bitplane probe
])
def test_bitmatmul_compiles_for_v5e(one_chip, no_compile_cache, m, k, n):
    a = jax.ShapeDtypeStruct((m, _words(k)), jnp.uint32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, _words(n)), jnp.uint32, sharding=one_chip)
    compiled = K._bitmatmul_kernel_call.lower(
        a, b, block_m=256, block_nw=128, block_k=512, interpret=False,
    ).compile()
    _check(compiled)


# the fused walk in its two steps: the plane operand prepared once, then
# walked with only the mask padded
WALK_WIDTHS = [
    (64, 4, 1000),           # German
    (64, 7, 7214),           # Compas: nw 256
    (64, 8, 16384),          # deep chain at the 256 MiB plane cap: nw 512
]


def _nw(n: int) -> int:
    return -(-_words(n) // 128) * 128


@pytest.mark.parametrize("b,hops,n", WALK_WIDTHS)
def test_walk_plane_prepare_compiles_for_v5e(one_chip, no_compile_cache, b,
                                             hops, n):
    plane = jax.ShapeDtypeStruct((n, _words(n)), jnp.uint32,
                                 sharding=one_chip)
    compiled = K._prepare_walk_stack.lower(
        *([plane] * hops), interpret=False).compile()
    assert compiled.out_info.shape == (hops, 32 * _nw(n), _nw(n))
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("b,hops,n", WALK_WIDTHS)
def test_batched_walk_compiles_for_v5e(one_chip, no_compile_cache, b, hops,
                                       n):
    mask = jax.ShapeDtypeStruct((b, _words(n)), jnp.uint32, sharding=one_chip)
    stacked = jax.ShapeDtypeStruct((hops, 32 * _nw(n), _nw(n)), jnp.int32,
                                   sharding=one_chip)
    compiled = K._walk_stack.lower(
        mask, stacked, out_words=_words(n), block_b=128, block_k=512,
        block_nw=128, interpret=False,
    ).compile()
    _check(compiled)


def _compile_lineage_gather(one_chip, q, rows, nnz, max_deg, name):
    """Compile :func:`repro.kernels.ops.lineage_gather`'s launch: ``q``
    queries against a CSR of ``rows`` rows and ``nnz`` links."""
    from repro.kernels.lineage_gather import VALUE_TAIL

    def spec(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    compiled = K._lineage_gather_call.lower(
        spec((q,)), spec((rows + 1,)),
        spec((1, -(-nnz // 128) * 128 + VALUE_TAIL)),
        max_deg=max_deg, interpret=False, name=name,
    ).compile()
    _check(compiled)


@pytest.mark.parametrize("n_probe,n_links,max_deg", [
    (390978, 362342, 128),    # TPC-DI SF3 fwd: dimension rows, inverse map
    (362342, 362342, 128),    # TPC-DI SF3 bwd: joined rows, the map
    # the widest table the route's cap admits at its smallest Q: one key's
    # segment across 524,288 lanes, 512 grid steps of 8 chunks
    (390978, 362342, K.GATHER_TABLE_BYTES // (4 * K.GATHER_BLOCK_Q)),
])
def test_gather_walk_compiles_for_v5e(one_chip, no_compile_cache, n_probe,
                                      n_links, max_deg):
    q = 64 * 4 if max_deg == 128 else K.GATHER_BLOCK_Q  # B = 64 probes of 4
    _compile_lineage_gather(one_chip, q, n_probe, n_links, max_deg,
                            "gather_walk")


@pytest.mark.parametrize("q", [
    K.GATHER_BLOCK_Q,         # up to 128 probe rows: the serving tier's plans
    64 * 4,                   # B = 64 probes of 4
])
@pytest.mark.parametrize("n_probe", [
    7214,                     # Compas fwd: source rows, the inverse map
    6907,                     # Compas bwd: sink rows, the map
])
def test_gather_walk_compiles_for_v5e_at_compas_shapes(one_chip,
                                                       no_compile_cache,
                                                       n_probe, q):
    _compile_lineage_gather(one_chip, q, n_probe, 6907, 128, "gather_walk")


@pytest.mark.parametrize("rows,nnz,max_deg", [
    (64, 64 * 16384, 16384),  # unfused walk: a B=64 frontier at n=16384
    (32561, 32561, 128),      # Census width: one link per row
])
def test_lineage_gather_compiles_for_v5e(one_chip, no_compile_cache, rows,
                                         nnz, max_deg):
    _compile_lineage_gather(one_chip, 128, rows, nnz, max_deg, None)


@pytest.mark.parametrize("words", [
    64 * 16384 // 32,         # unfused walk: a B=64 frontier at n=16384
    _words(32561),            # one Census-width bitset
])
def test_bitset_rank_compiles_for_v5e(one_chip, no_compile_cache, words):
    from repro.kernels.bitset_rank import bitset_rank_pallas

    w = -(-words // 128) * 128
    compiled = bitset_rank_pallas.lower(
        jax.ShapeDtypeStruct((1, w), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((128, 1), jnp.int32, sharding=one_chip),
        block_q=128, chunk=128 if w % 512 else 512,
    ).compile()
    _check(compiled)
