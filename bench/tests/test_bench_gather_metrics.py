"""The per-layer metrics of the fused walk's gather route, and the
``join_dim`` step of the ``tpcdi_sf3`` configuration.

Each reader gets a synthetic record with known counters (and, for the
kernel's metrics, a reduced trace) and returns the expected value; it
returns None where its denominator is 0 and where the program keeps no
such counter or the trace no such kernel (a checkout from before the
route).  The step's row map, computed by the step alone, is the
dimension side of the program's own join pairs.
"""
import numpy as np
import pytest

from bench import deploy, spec, work

CELL = "tpcdi_sf3.batch_default"
SESSION = {"plans": 10, "walk": 10, "fused_walk": 10, "gather_walk": 8,
           "gather_walk_ns": 80_000_000, "walk_put_ns": 4_000_000,
           "walk_put_bytes": 8_192, "walk_wait_ns": 12_000_000,
           # 8 plans of 256 probe rows, 250 answer rows, an SF3 map's links
           "gather_walk_rows": 8 * 256, "gather_walk_hits": 8 * 250,
           "gather_walk_links": 8 * 362342}
KERNEL_OP = ("%gather_walk.1 = s32[1,256,128]{2,1,0:T(8,128)} custom-call("
             "s32[2,1,128]{2,1,0} %bitcast.4), custom_call_target="
             "\"tpu_custom_call\"")
OPERAND_OP = "%fusion.2 = s32[256]{0} fusion(s32[256]{0} %gather_walk.1)"
WORK = {d: work.plan_work(64, work.lineage_hops(390978, [np.arange(362342)],
                                                 d))
        for d in ("fwd", "bwd")}


def _record(session=None, ops=None):
    trace = None if ops is None else {"op_seconds": dict(ops)}
    return {"counters": {"session": dict(SESSION if session is None
                                         else session)},
            "trace": trace, "work": WORK,
            "peaks": spec.peaks("TPU v5 lite")}


OPS = {KERNEL_OP: 0.0004, OPERAND_OP: 1.0}   # 0.4 ms over 8 plans
# a plan's least work: its row ids in and out and the map's links, int32s
LEAST = 4 * (256 + 250 + 362342) / spec.peaks("TPU v5 lite")[
    "hbm_bytes_per_s"]

# metric: (expected value, whether it reads the trace)
CASES = {
    "gather_walk.kernel_ms": (0.05, True),
    "gather_walk_roofline": (100.0 * 8 * LEAST / 0.0004, True),
    "session.gather_plan_pct": (80.0, False),
    "walk.gather_self_ms": (8.0, False),
    "gather_walk.put_mb": (0.001024, False),
}
METRICS = {m["name"]: m for m in spec.benchmark()["per_layer"]
           if m["name"] in CASES}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_returns_its_value(name):
    value = spec.reader(name)(_record(ops=OPS))
    assert value == pytest.approx(CASES[name][0], rel=1e-12)


def test_roofline_share_is_a_few_percent_at_the_cells_shapes():
    # 1.45 MB a plan at 819 GB/s: 1.77 us against 50 us of kernel a plan
    assert LEAST == pytest.approx(1.772e-6, rel=0.01)
    assert spec.reader("gather_walk_roofline")(_record(ops=OPS)) == \
        pytest.approx(3.54, rel=0.01)


def test_roofline_prices_the_route_not_the_packed_masks():
    """The route never moves the packed masks ``bench/work.py`` prices:
    its least work is well under a third of that plan's."""
    packed = sum(w.bytes for w in WORK.values()) / 2
    assert 4 * (256 + 250 + 362342) < packed / 3


@pytest.mark.parametrize("name", [n for n in sorted(CASES)
                                  if n != "session.gather_plan_pct"])
def test_reader_is_empty_without_route_plans(name):
    session = dict(SESSION, gather_walk=0)
    assert spec.reader(name)(_record(session, ops=OPS)) is None


def test_plan_share_reads_zero_without_route_plans_and_none_without_plans():
    read = spec.reader("session.gather_plan_pct")
    assert read(_record(dict(SESSION, gather_walk=0))) == 0.0
    assert read(_record(dict(SESSION, plans=0, gather_walk=0))) is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_empty_on_a_program_without_the_route(name):
    """The parent's counters: no ``gather_walk`` nor ``gather_walk_ns``,
    and no kernel of that name in the trace."""
    session = {k: v for k, v in SESSION.items()
               if not k.startswith("gather_walk")}
    assert spec.reader(name)(_record(session, ops={OPERAND_OP: 1.0})) is None


@pytest.mark.parametrize("name", [n for n in sorted(CASES) if CASES[n][1]])
def test_kernel_reader_is_empty_without_its_trace_events(name):
    read = spec.reader(name)
    assert read(_record(ops={OPERAND_OP: 1.0})) is None   # an operand only
    assert read(_record(ops=None)) is None                # untraced run


@pytest.mark.parametrize("name", sorted(CASES))
def test_metric_lists_the_new_cell_and_moves_its_p50(name):
    m = METRICS[name]
    assert m["workloads"] == [CELL] and m["moves"] == "request_p50_ms"
    assert m["source"] == ("device_trace" if CASES[name][1]
                           else "program_counter")


def test_new_cell_reports_p50_p95_and_setup():
    cell = spec.resolve(CELL)
    assert sorted(m["name"] for m in cell.end_to_end) == \
        ["request_p50_ms", "request_p95_ms", "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(CASES)
    assert cell.traffic_name == "closed_b64x4" and cell.chips == 1


# -- the join_dim step --------------------------------------------------------
def _captured(rows=300, facts=240, seed=2**31 + 99):
    cfg = {"table": {"rows": rows, "cols": 4, "categorical": 0,
                     "null_frac": 0},
           "pipeline": [{"kind": "join_dim",
                         "args": {"key": "a0", "rows": facts, "attrs": 3}}]}
    return deploy.capture(cfg, np.random.SeedSequence(seed), "join_test")


def test_row_map_is_the_programs_join_pairs():
    dep = _captured()
    (row_map,) = dep.row_steps
    op = dep.index.ops[dep.index.producer[dep.sink]]
    assert op.input_ids == [f"{dep.source}_fact", dep.source]
    # slot 1 of the join maps each joined row to its dimension row
    np.testing.assert_array_equal(op.tensor.slot_gather(1), row_map)
    # and the fact side is the identity: the join emits in fact-row order
    np.testing.assert_array_equal(op.tensor.slot_gather(0),
                                  np.arange(dep.n_sink))
    assert dep.n_sink == 240 and len(np.unique(row_map)) == 240


def test_fact_keys_are_unique_dimension_keys_drawn_from_the_seed():
    a, b, c = _captured(), _captured(), _captured(seed=2**31 + 100)
    key = a.index.datasets[a.source].table.col("a0")
    (row_map,) = a.row_steps
    values, counts = np.unique(key, return_counts=True)
    unique = set(values[counts == 1].tolist())
    assert set(np.asarray(key)[row_map].tolist()) <= unique
    np.testing.assert_array_equal(row_map, b.row_steps[0])
    assert not np.array_equal(row_map, c.row_steps[0])


def test_fact_table_is_cut_to_the_unique_keys_of_a_small_dimension():
    dep = _captured(rows=50, facts=362342)
    assert dep.n_sink <= 50
    (row_map,) = dep.row_steps
    assert len(np.unique(row_map)) == dep.n_sink


def test_a_program_without_the_device_walk_stops_at_set_up(monkeypatch):
    from repro.kernels import ops

    monkeypatch.delattr(ops, "gather_walk")
    with pytest.raises(RuntimeError, match="no device walk of a row map"):
        _captured()
