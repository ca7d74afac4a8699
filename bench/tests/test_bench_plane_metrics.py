"""``walk.plane_hit_pct``: the share of fused walks whose plane operand the
session's plane store already held on the device.

The reader returns the counters' ratio, None where no walk looked its
planes up and where the program keeps no such counters (a checkout from
before the plane store), and a tiny fused run of each cell on the CPU
oracle reports it: both cells warm every shape in both directions before
the window, so every walk in it hits.
"""
import pytest

from bench import spec
from bench.tests.test_bench_harness import _measure, _tiny

NAME = "walk.plane_hit_pct"
CELLS = ["compas.batch_walk", "compas.serve_open"]


def _record(session):
    return {"counters": {"session": session}, "trace": None}


def test_reader_returns_the_hit_share():
    read = spec.reader(NAME)
    session = {"fused_walk": 8, "walk_plane_hits": 6, "walk_plane_misses": 2}
    assert read(_record(session)) == pytest.approx(75.0, rel=1e-12)


@pytest.mark.parametrize("session", [
    {"fused_walk": 0, "walk_plane_hits": 0, "walk_plane_misses": 0},
    {"fused_walk": 8, "walk_put_bytes": 341_200_000},
    {},
], ids=["no-lookups", "no-plane-store", "no-session-counters"])
def test_reader_is_empty_without_lookups_or_counters(session):
    assert spec.reader(NAME)(_record(session)) is None


def test_metric_is_a_walkers_program_counter_in_both_cells():
    (m,) = [m for m in spec.benchmark()["per_layer"] if m["name"] == NAME]
    assert m["source"] == "program_counter" and m["layer"] == "walkers"
    assert m["moves"] == "request_p50_ms"
    assert sorted(m["workloads"]) == CELLS


@pytest.mark.parametrize("name", CELLS)
def test_tiny_fused_run_hits_on_every_walk(name):
    cell = _tiny(name, fused_walk=True)
    assert NAME in [m["name"] for m in cell.per_layer]
    record = _measure(cell)
    c = record["counters"]["session"]
    assert c["fused_walk"] > 0 and c["walk_plane_misses"] == 0
    assert spec.reader(NAME)(record) == 100.0
