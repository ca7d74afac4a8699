"""chip_smoke.py's phases at tiny sizes on the host, with the jnp oracles
standing in for the Pallas kernels: the parity logic and the failure paths
of the chip bring-up script."""
import importlib.util
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def german(smoke):
    return smoke.phase_usecases(names=("german",), count=8)


def test_usecases_phase_matches_reference(german):
    line, indexes = german
    g = line["german"]
    assert (g["rows"], g["cols"], g["sink_rows"]) == (1000, 21, 1000)
    assert g["session"]["plans"] == 4
    # German's source-to-sink plane fits the budget: the bitplane hop-cache
    # composed it and answered both directions
    assert g["bitplane_hopcache"]["entries_bitplane"] > 0
    assert set(indexes) == {"german"}


def test_tpcdi_phase_matches_reference(smoke, monkeypatch):
    from repro.dataprep import usecases

    monkeypatch.setitem(usecases.TPCDI_SCALES, 0, (300, 420))
    line = smoke.phase_tpcdi(scale=0, count=8)
    assert (line["left_rows"], line["right_rows"]) == (300, 420)
    assert line["join_rows"] == 300
    assert line["fused_plane_bytes"] == 420 * 420 // 8
    assert line["session"]["plans"] == 3
    # both sides of an inner key join are <=1-to-1: structured gathers
    assert line["hopcache"]["entries"] == 2
    assert line["hopcache"]["entries_structured"] == 2


@pytest.fixture(scope="module")
def compas(smoke):
    index, src, sink = smoke.capture_usecase("compas")
    plans = smoke.record_plans(index, src, sink, np.random.default_rng(0), 8)
    return index, plans


def test_compas_record_plans_pass_the_gather_route_check(smoke, compas):
    index, plans = compas
    routed = smoke.check_gather_route(index, plans, smoke.reference(index),
                                      fused_walk=True)
    assert routed == {"fused_walk": 2, "gather_walk": 2,
                      "gather_walk_declined": 0, "fused_walk_oversize": 0}


def test_gather_route_check_fails_on_the_dense_route(smoke, compas):
    index, plans = compas
    op = index.ops[-1]
    structured = op.tensor
    op.tensor = structured.as_coo()     # one hop with no structure
    try:
        with pytest.raises(RuntimeError, match="gather walk did not answer"):
            smoke.check_gather_route(index, plans, smoke.reference(index),
                                     fused_walk=True)
    finally:
        op.tensor = structured


def test_fused_walk_phase_routes_to_fused_kernel(smoke):
    line = smoke.phase_fused_walk(n=512, n_ops=8, count=8, fused_walk=True)
    assert line["hops"] == 8
    assert line["session"]["fused_walk"] == 2
    assert "gather_walk" not in line["session"]  # the hops have no structure
    assert line["plane_bytes"] == 8 * 512 * 512 // 8


def test_fused_walk_phase_fails_when_routed_to_host(smoke):
    with pytest.raises(RuntimeError, match="fused walk did not answer"):
        smoke.phase_fused_walk(n=256, n_ops=4, count=4, fused_walk=False)


def test_serving_phase_matches_session(smoke, german):
    index, src, sink = german[1]["german"]
    line = smoke.phase_serving(index, src, sink, n_requests=32, n_tenants=4,
                               max_batch=8)
    assert line["tier"]["completed"] == 32
    assert line["tier"]["max_batch_seen"] <= 8


def test_sharded_phase_matches_reference(smoke, german):
    line = smoke.phase_sharded({"german": german[1]["german"]}, n_shards=1,
                               count=8)
    assert line["german"]["engine"] == "collective"


def test_check_rejects_a_differing_answer(smoke):
    want = [np.arange(3, dtype=np.int64), np.arange(2, dtype=np.int64)]
    smoke.check("same", [w.copy() for w in want], want)
    with pytest.raises(smoke.Mismatch):
        smoke.check("value", [np.arange(3), np.arange(1, 3)], want)
    with pytest.raises(smoke.Mismatch):
        smoke.check("dtype", [w.astype(np.int32) for w in want], want)
    with pytest.raises(smoke.Mismatch):
        smoke.check("length", want[:1], want)


def test_main_exits_nonzero_without_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err
