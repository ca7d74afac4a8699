"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle.

All kernels are integer/boolean — assertions are EXACT equality.
"""
import contextlib
import functools
from collections import defaultdict

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.kernels import ops as K
from repro.kernels import ref as R


# ---------------------------------------------------------------------------
# bitmatmul: (OR,AND) boolean-semiring matmul on packed bitplanes
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [
    (1, 1, 1), (8, 32, 32), (7, 33, 9), (40, 70, 50),
    (64, 256, 128), (130, 300, 257),
])
@pytest.mark.parametrize("density", [0.02, 0.3])
def test_bitmatmul_sweep(m, k, n, density):
    rng = np.random.default_rng(m * 1000 + k + n)
    A = rng.random((m, k)) < density
    B = rng.random((k, n)) < density
    a_b = R.pack_bits(jnp.asarray(A))
    b_b = R.pack_bits(jnp.asarray(B))
    got = K.bitmatmul(a_b, b_b, block_m=8, block_nw=8, block_k=32, interpret=True)
    want = R.bitmatmul_ref(a_b, b_b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # semantic check against dense boolean matmul
    dense = R.unpack_bits(want, n)
    np.testing.assert_array_equal(np.asarray(dense),
                                  (A.astype(int) @ B.astype(int)) > 0)


def test_bitmatmul_identity():
    n = 96
    eye = np.eye(n, dtype=bool)
    rng = np.random.default_rng(0)
    Bm = rng.random((n, 40)) < 0.2
    a_b = R.pack_bits(jnp.asarray(eye))
    b_b = R.pack_bits(jnp.asarray(Bm))
    got = K.bitmatmul(a_b, b_b, block_m=8, block_nw=8, block_k=32, interpret=True)
    np.testing.assert_array_equal(np.asarray(R.unpack_bits(got, 40)), Bm)


@given(st.integers(1, 40), st.integers(1, 70), st.integers(1, 40),
       st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_bitmatmul_property(m, k, n, seed):
    rng = np.random.default_rng(seed)
    A = rng.random((m, k)) < 0.2
    B = rng.random((k, n)) < 0.2
    got = K.bitmatmul(R.pack_bits(jnp.asarray(A)), R.pack_bits(jnp.asarray(B)),
                      block_m=8, block_nw=8, block_k=32, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(R.unpack_bits(got, n)), (A.astype(int) @ B.astype(int)) > 0)


# ---------------------------------------------------------------------------
# lineage_gather: batched CSR probe
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_rows,n_cols,nnz,max_deg", [
    (4, 5, 6, 3), (16, 16, 40, 8), (100, 50, 300, 16), (33, 7, 90, 33),
])
def test_lineage_gather_sweep(n_rows, n_cols, nnz, max_deg):
    rng = np.random.default_rng(nnz)
    rows = np.sort(rng.integers(0, n_rows, nnz)).astype(np.int32)
    cols = rng.integers(0, n_cols, nnz).astype(np.int32)
    counts = np.bincount(rows, minlength=n_rows)
    row_ptr = np.zeros(n_rows + 1, np.int32)
    np.cumsum(counts, out=row_ptr[1:])
    md = int(max(counts.max(), 1))
    md = min(md, max_deg) if max_deg else md
    queries = rng.integers(0, n_rows, 37).astype(np.int32)
    got = K.lineage_gather(row_ptr, cols, queries, max_deg=md,
                           block_q=16, interpret=True)
    colp = jnp.concatenate([jnp.asarray(cols), jnp.full((md,), -1, jnp.int32)])
    want = R.lineage_gather_ref(jnp.asarray(queries), jnp.asarray(row_ptr),
                                colp, max_deg=md)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_lineage_gather_matches_host_csr():
    from repro.core.provtensor import CSR
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 20, 60)
    cols = rng.integers(0, 30, 60)
    csr = CSR.from_pairs(rows, cols, 20, 30)
    qs = np.arange(20, dtype=np.int32)
    md = int(np.diff(csr.row_ptr).max())
    got = np.asarray(K.lineage_gather(csr.row_ptr, csr.col_idx, qs,
                                      max_deg=md, block_q=4, interpret=True))
    for i, q in enumerate(qs):
        want = sorted(csr.neighbors(q).tolist())
        have = sorted(x for x in got[i].tolist() if x >= 0)
        assert have == want


# ---------------------------------------------------------------------------
# bitset_rank: batched inclusive rank
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_bits", [1, 31, 32, 33, 100, 1000])
def test_bitset_rank_sweep(n_bits):
    from repro.core.schema import Bitset
    rng = np.random.default_rng(n_bits)
    bits = rng.random(n_bits) < 0.4
    b = Bitset.from_bits(bits)
    pos = np.concatenate([np.arange(n_bits), [-1]]).astype(np.int32)
    got = np.asarray(K.bitset_rank(b.words, pos, block_q=8, interpret=True))
    want = np.array([b.rank(int(p)) if p >= 0 else 0 for p in pos])
    np.testing.assert_array_equal(got, want)


@given(st.lists(st.booleans(), min_size=1, max_size=200), st.integers(0, 2**31 - 1))
@settings(max_examples=30, deadline=None)
def test_bitset_rank_property(bits, seed):
    from repro.core.schema import Bitset
    b = Bitset.from_bits(bits)
    rng = np.random.default_rng(seed)
    pos = rng.integers(-1, len(bits), 17).astype(np.int32)
    got = np.asarray(K.bitset_rank(b.words, pos, block_q=8, interpret=True))
    cum = np.concatenate([[0], np.cumsum(np.asarray(bits, int))])
    want = np.where(pos >= 0, cum[np.clip(pos, -1, len(bits) - 1) + 1], 0)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# batched_walk: fused K-hop record probe (ROADMAP item 4)
# ---------------------------------------------------------------------------
def _random_chain(rng, n0, hops, density=0.1):
    """Packed planes for a K-hop chain with NON-multiple-of-32 random dims."""
    dims = [n0] + [int(rng.integers(5, 90)) for _ in range(hops)]
    planes = [R.pack_bits(jnp.asarray(
        rng.random((dims[j], dims[j + 1])) < density)) for j in range(hops)]
    return dims, planes


@pytest.mark.parametrize("hops", [1, 2, 3, 4, 5, 6])
@pytest.mark.parametrize("density", [0.02, 0.25])
def test_batched_walk_pallas_parity(hops, density):
    rng = np.random.default_rng(hops * 100 + int(density * 100))
    n0 = int(rng.integers(5, 90))  # deliberately not a multiple of 32
    dims, planes = _random_chain(rng, n0, hops, density)
    B = 7
    mask = R.pack_bits(jnp.asarray(rng.random((B, n0)) < 0.3))
    got_out, got_cnt = K.batched_walk(mask, planes, use_pallas=True,
                                      interpret=True, block_b=4, block_k=64)
    want_out, want_cnt = R.batched_walk_ref(mask, planes)
    np.testing.assert_array_equal(np.asarray(got_out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(got_cnt), np.asarray(want_cnt))
    # counts really are the per-hop frontier sizes
    assert np.asarray(got_cnt).shape == (hops, B)


def test_batched_walk_empty_mask():
    rng = np.random.default_rng(3)
    _, planes = _random_chain(rng, 40, 3)
    mask = jnp.zeros((5, 2), dtype=jnp.uint32)  # 40 cols -> 2 words, all zero
    out, cnt = K.batched_walk(mask, planes, use_pallas=True, interpret=True,
                              block_b=4, block_k=64)
    assert not np.asarray(out).any()
    assert not np.asarray(cnt).any()


def test_batched_walk_oracle_guard_matches_pallas():
    """use_pallas=None resolves to the oracle off-TPU and must answer
    byte-identically to the interpret-mode Pallas kernel."""
    rng = np.random.default_rng(11)
    _, planes = _random_chain(rng, 33, 4)
    mask = R.pack_bits(jnp.asarray(rng.random((6, 33)) < 0.3))
    o_out, o_cnt = K.batched_walk(mask, planes, use_pallas=None)
    p_out, p_cnt = K.batched_walk(mask, planes, use_pallas=True,
                                  interpret=True, block_b=2, block_k=32)
    np.testing.assert_array_equal(np.asarray(o_out), np.asarray(p_out))
    np.testing.assert_array_equal(np.asarray(o_cnt), np.asarray(p_cnt))


def test_batched_walk_chain_mismatch_raises():
    rng = np.random.default_rng(0)
    a = R.pack_bits(jnp.asarray(rng.random((4, 40)) < 0.2))
    bad = R.pack_bits(jnp.asarray(rng.random((90, 10)) < 0.2))  # 90 != 40
    with pytest.raises(ValueError):
        K.batched_walk(a, [bad])
    with pytest.raises(ValueError):
        K.batched_walk(a, [])


def test_batched_walk_launch_reduction():
    """The tentpole contract: a K-hop batched probe is ONE dispatch fused
    vs exactly 3 per hop unfused, with byte-identical results."""
    rng = np.random.default_rng(21)
    hops = 5
    _, planes = _random_chain(rng, 50, hops)
    mask = R.pack_bits(jnp.asarray(rng.random((8, 50)) < 0.2))
    K.reset_launch_counts()
    f_out, f_cnt = K.batched_walk(mask, planes, use_pallas=None)
    assert K.launch_counts() == {"batched_walk": 1}
    K.reset_launch_counts()
    u_out, u_cnt = K.batched_walk_unfused(mask, planes, use_pallas=None)
    lc = K.launch_counts()
    assert sum(lc.values()) == 3 * hops, lc
    assert lc == {"bitmatmul": hops, "bitset_rank": hops,
                  "lineage_gather": hops}
    np.testing.assert_array_equal(np.asarray(f_out), np.asarray(u_out))
    np.testing.assert_array_equal(np.asarray(f_cnt), np.asarray(u_cnt))
    K.reset_launch_counts()


@given(st.integers(1, 4), st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_batched_walk_property(hops, seed):
    rng = np.random.default_rng(seed)
    n0 = int(rng.integers(1, 70))
    dims, planes = _random_chain(rng, n0, hops)
    B = int(rng.integers(1, 9))
    mask = R.pack_bits(jnp.asarray(rng.random((B, n0)) < 0.3))
    got_out, got_cnt = K.batched_walk(mask, planes, use_pallas=True,
                                      interpret=True, block_b=2, block_k=32)
    want_out, want_cnt = R.batched_walk_ref(mask, planes)
    np.testing.assert_array_equal(np.asarray(got_out), np.asarray(want_out))
    np.testing.assert_array_equal(np.asarray(got_cnt), np.asarray(want_cnt))


# ---------------------------------------------------------------------------
# fused walk over real pipelines: query-layer + session routing parity
# ---------------------------------------------------------------------------
SEEDS = range(12)
CAPTURES = ("structured", "coo")
DIRECTIONS = ("fwd", "bwd")


@functools.lru_cache(maxsize=None)
def _pipegen_index(seed, capture):
    """A random pipeline (``pipegen``), captured structured or forced COO.

    Scaled to 480-1,568 source rows, so that every structured chain's
    planes outweigh a sparse plan's gather answer table (64 KiB) and the
    gather route takes it.  Every op's other input is a fresh source, so
    the src -> sink flow is one linear chain.  The walks below only read
    the index, so one build serves every case of a (seed, capture)."""
    import pipegen
    from repro.core.capture import force_coo_capture

    scope = force_coo_capture() if capture == "coo" else contextlib.nullcontext()
    with scope:
        idx, sink, _ = pipegen.random_pipeline(seed, scale=32)
    return idx, sink


def _pipegen_probe(seed, capture, direction):
    """``(idx, src, dst, rows)``: a sparse 4-probe stack at the probed end
    (probe 0 empty)."""
    idx, sink = _pipegen_index(seed, capture)
    src, dst = ("src", sink) if direction == "fwd" else (sink, "src")
    rng = np.random.default_rng([seed, DIRECTIONS.index(direction)])
    rows = rng.random((4, idx.datasets[src].n_rows)) < 0.01
    rows[0] = False
    return idx, src, dst, rows


def _dense_launches():
    return K.launch_counts().get("batched_walk", 0)


def _assert_route(capture, gather_walks, dense_launches):
    """A structured chain takes the gather route; a forced-COO chain (under
    the plane cap) the one ``batched_walk`` launch."""
    if capture == "structured":
        assert (gather_walks, dense_launches) == (1, 0)
    else:
        assert (gather_walks, dense_launches) == (0, 1)


@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("capture", CAPTURES)
@pytest.mark.parametrize("seed", SEEDS)
def test_fused_walk_record_masks_parity_pipegen(seed, capture, direction):
    """Fused walker vs the full per-op walkers on randomized pipelines
    (outer joins / appends with -1 sentinels included): the same mask
    stack, through the route the chain's capture decides."""
    from repro.core import query as Q

    idx, src, dst, rows = _pipegen_probe(seed, capture, direction)
    walker = (Q.forward_record_masks_batch if direction == "fwd"
              else Q.backward_record_masks_batch)
    want = walker(idx, src, rows).get(
        dst, np.zeros((len(rows), idx.datasets[dst].n_rows), bool))
    counters = defaultdict(int)
    before = _dense_launches()
    got = Q.fused_walk_record_masks_batch(idx, src, dst, rows, direction,
                                          counters=counters)
    assert got is not None          # a linear chain passes the audit
    np.testing.assert_array_equal(got, want)
    _assert_route(capture, counters["gather_walk"],
                  _dense_launches() - before)


def test_fused_walk_accepts_real_chains():
    """The linearity audit must accept real chains: the unscaled random
    pipelines fuse (None, the fallback, is legal per chain, not for all),
    and a fused answer agrees with the per-op walker."""
    import pipegen
    from repro.core import query as Q

    fused_hits = 0
    for seed in SEEDS:
        idx, sink, rng = pipegen.random_pipeline(seed)
        rows_b = rng.random((4, idx.datasets["src"].n_rows)) < 0.3
        got = Q.fused_walk_record_masks_batch(idx, "src", sink, rows_b, "fwd")
        if got is not None:
            fused_hits += 1
            want = Q.forward_record_masks_batch(idx, "src", rows_b)[sink]
            np.testing.assert_array_equal(got, want)
    assert fused_hits > 0


def test_fused_walk_rejects_diamond():
    """path_tensors picks ONE path through a diamond; the linearity audit
    must refuse to fuse it (the full walker sums both branches)."""
    import pipegen
    from repro.core import query as Q

    idx, sink = pipegen.diamond_pipeline(0)
    n = idx.datasets["src"].n_rows
    rows = np.zeros((2, n), dtype=bool)
    rows[:, 0] = True
    assert Q.fused_walk_record_masks_batch(idx, "src", sink, rows, "fwd") is None


def test_fused_walk_identity_pair():
    import pipegen
    from repro.core import query as Q

    idx, sink, rng = pipegen.random_pipeline(1)
    n = idx.datasets["src"].n_rows
    rows = rng.random((3, n)) < 0.4
    got = Q.fused_walk_record_masks_batch(idx, "src", "src", rows, "fwd")
    np.testing.assert_array_equal(got, rows)


def _session_parity(idx, src, dst, rows, direction):
    """Run one record plan through a fused-walk session and a host-walk
    session: both answer alike; returns the fused session's counters and
    the ``batched_walk`` launches it made."""
    from repro.provenance import prov
    from repro.provenance.session import QuerySession

    s_on = QuerySession(idx, fused_walk=True, use_hopcache=False)
    s_off = QuerySession(idx, fused_walk=False, use_hopcache=False)
    q = prov(idx).source(src).rows_batch(rows)
    plan = (q.forward() if direction == "fwd" else q.backward()).to(dst).plan()
    before = _dense_launches()
    got = s_on.run(plan)
    launches = _dense_launches() - before
    want = s_off.run(plan)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert s_off.counters["fused_walk"] == 0
    return s_on.counters, launches


@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("capture", CAPTURES)
@pytest.mark.parametrize("seed", SEEDS)
def test_session_fused_walk_routing_parity(seed, capture, direction):
    """QuerySession(fused_walk=True) answers byte-identically to the plain
    walk and counts the fused walk on the route the chain's capture
    decides."""
    idx, src, dst, rows = _pipegen_probe(seed, capture, direction)
    counters, launches = _session_parity(idx, src, dst, rows, direction)
    assert counters["fused_walk"] == 1
    _assert_route(capture, counters["gather_walk"], launches)


@pytest.mark.parametrize("direction", DIRECTIONS)
@pytest.mark.parametrize("capture", CAPTURES)
def test_session_fused_walk_diamond_takes_the_host_walk(capture, direction):
    """A diamond fails the linearity audit: the fused session walks it on
    the host, with no device walk, and answers like the plain walk."""
    import pipegen
    from repro.core.capture import force_coo_capture

    scope = force_coo_capture() if capture == "coo" else contextlib.nullcontext()
    with scope:
        idx, sink = pipegen.diamond_pipeline(0)
    src, dst = ("src", sink) if direction == "fwd" else (sink, "src")
    rows = np.random.default_rng(3).random((3, idx.datasets[src].n_rows)) < 0.3
    counters, launches = _session_parity(idx, src, dst, rows, direction)
    assert counters["fused_walk"] == 0 and counters["gather_walk"] == 0
    assert launches == 0
    assert counters["host_walk_ns"] > 0


# ---------------------------------------------------------------------------
# calibration: measure -> fit -> persist -> load round-trip
# ---------------------------------------------------------------------------
def test_calibration_round_trip(tmp_path):
    from repro.core import calibrate, costmodel

    path = str(tmp_path / "calibration.json")
    try:
        fitted = calibrate.calibrate(path=path, quick=True, install=False)
        assert fitted.source == "calibrated"
        assert fitted.c_word_op > 0 and fitted.c_spmm_flop > 0
        assert 1e-4 <= fitted.density_threshold <= 0.5
        loaded = calibrate.load_constants(path)
        assert loaded is not None
        assert loaded.device == fitted.device
        assert loaded.c_word_op == pytest.approx(fitted.c_word_op)
        assert loaded.density_threshold == pytest.approx(fitted.density_threshold)
        prov = loaded.provenance()
        assert prov["source"] == "calibrated"
        assert prov["path"] == str(tmp_path / "calibration.json")

        # installing calibrated constants moves the router's crossover
        costmodel.set_constants(loaded)
        assert costmodel.active_constants().density_threshold == \
            pytest.approx(fitted.density_threshold)
        assert costmodel.pick_backend(loaded.density_threshold * 2) == "bitplane"
        assert costmodel.pick_backend(loaded.density_threshold / 2) == "csr"
    finally:
        costmodel.reset_constants()


def test_calibration_absent_file_keeps_defaults(tmp_path, monkeypatch):
    """No calibration file -> bit-for-bit default constants and routing."""
    from repro.core import calibrate, costmodel

    monkeypatch.setenv("REPRO_CALIBRATION", str(tmp_path / "nope.json"))
    try:
        costmodel.reset_constants()
        costmodel.maybe_load_calibration()
        c = costmodel.active_constants()
        assert c.source == "default"
        assert c.density_threshold == costmodel.DENSITY_THRESHOLD
        assert c.c_word_op == costmodel.C_WORD_OP
        assert costmodel.constants_provenance()["source"] == "default"
        assert calibrate.load_constants(str(tmp_path / "nope.json")) is None
    finally:
        costmodel.reset_constants()


def test_calibration_autoload_via_costmodel(tmp_path, monkeypatch):
    """CostModel.__init__ autoloads $REPRO_CALIBRATION once per process."""
    import pipegen
    from repro.core import calibrate, costmodel
    from repro.core.costmodel import CostModel

    path = str(tmp_path / "calibration.json")
    monkeypatch.setenv("REPRO_CALIBRATION", path)
    try:
        fitted = calibrate.calibrate(path=path, quick=True, install=False)
        costmodel.reset_constants()  # re-arm the once-per-process autoload
        idx, sink, rng = pipegen.random_pipeline(2)
        CostModel(idx)
        act = costmodel.active_constants()
        assert act.source == "calibrated"
        assert act.c_word_op == pytest.approx(fitted.c_word_op)
    finally:
        costmodel.reset_constants()
