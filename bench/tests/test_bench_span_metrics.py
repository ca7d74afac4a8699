"""The per-layer metrics that read the program's span counters.

Each reader gets a synthetic record with known counter deltas and returns
the expected value; it returns None where its denominator is 0 and where
the program keeps no such counter (a checkout from before the spans).  A
tiny run of each cell on the CPU oracle reports every such metric that
lists the cell.
"""
import pytest

from bench import spec
from bench.tests.test_bench_harness import _measure, _tiny

SESSION = {"plans": 10, "walk": 8, "hopcache": 2, "fused_walk": 8,
           "execute_ns": 100_000_000, "fused_walk_ns": 80_000_000,
           "walk_put_ns": 24_000_000, "walk_put_bytes": 341_200_000,
           "walk_wait_ns": 40_000_000, "host_walk_ns": 0,
           "hopcache_probe_ns": 5_000_000}
TIER = {"batches": 6, "batched_plans": 12, "passes": 4, "pass_plans": 12,
        "pass_ns": 60_000_000, "queue_wait_ns": 36_000_000}

# metric: (counter group, expected value, the key whose 0 empties it)
CASES = {
    "walk.put_ms": ("session", 3.0, "fused_walk"),
    "walk.put_mb": ("session", 42.65, "fused_walk"),
    "walk.wait_ms": ("session", 5.0, "fused_walk"),
    "walk.self_ms": ("session", 2.0, "fused_walk"),
    "session.self_ms": ("session", 1.5, ("walk", "hopcache")),
    "tier.queue_wait_ms": ("tier", 3.0, "pass_plans"),
    "tier.pass_ms": ("tier", 15.0, "passes"),
}
SPAN_METRICS = [m for m in spec.benchmark()["per_layer"] if m["name"] in CASES]


def _record(session=None, tier=None):
    counters = {"session": dict(SESSION if session is None else session)}
    if tier is not False:
        counters["tier"] = dict(TIER if tier is None else tier)
    return {"counters": counters, "trace": None}


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_returns_the_counters_ratio(name):
    value = spec.reader(name)(_record())
    assert value == pytest.approx(CASES[name][1], rel=1e-12)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_empty_on_a_zero_denominator(name):
    group, _, keys = CASES[name]
    keys = keys if isinstance(keys, tuple) else (keys,)
    counters = {"session": dict(SESSION), "tier": dict(TIER)}
    for k in keys:
        counters[group][k] = 0
    assert spec.reader(name)(_record(counters["session"],
                                     counters["tier"])) is None


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_is_empty_without_the_programs_counters(name):
    """A program without the spans keeps only the older counters: the
    reader returns None and does not raise."""
    session = {k: SESSION[k] for k in ("plans", "walk", "hopcache",
                                       "fused_walk")}
    tier = {k: TIER[k] for k in ("batches", "batched_plans")}
    assert spec.reader(name)(_record(session, tier)) is None
    assert spec.reader(name)(_record(session, tier=False)) is None


def test_span_metrics_are_program_counters_with_their_cells():
    assert sorted(m["name"] for m in SPAN_METRICS) == sorted(CASES)
    for m in SPAN_METRICS:
        assert m["source"] == "program_counter"
        cells = set(m["workloads"])
        if m["name"].startswith("tier."):
            assert cells == {"compas.serve_open"}
        else:
            assert cells == {"compas.batch_walk", "compas.serve_open"}


@pytest.mark.parametrize("name", ["compas.batch_walk", "compas.serve_open"])
def test_tiny_fused_run_reports_every_span_metric_of_its_cell(name):
    cell = _tiny(name, fused_walk=True)
    record = _measure(cell)
    mine = [m["name"] for m in cell.per_layer if m["name"] in CASES]
    assert mine
    values = {m: spec.reader(m)(record) for m in mine}
    assert all(v is not None and v > 0 for v in values.values()), values
    c = record["counters"]["session"]
    assert values["walk.put_mb"] * 1e6 * c["fused_walk"] == \
        pytest.approx(c["walk_put_bytes"], rel=1e-12)
