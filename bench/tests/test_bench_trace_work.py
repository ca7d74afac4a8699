"""The reduction from a profiler trace to metrics, and the roofline's work
function."""
import numpy as np
import pytest

from bench import deploy, spec, tracing, work

record = spec.module("plans", "record")


def test_reduce_unions_overlapping_ops_and_names_idle_gaps():
    ops = [("batched_walk", 100, 300), ("copy.1", 200, 400),   # overlap
           ("batched_walk", 700, 800), ("fusion", 50, 120)]    # clipped
    spans = [("bench.window", 100, 1100), ("bench.request", 100, 600),
             ("bench.request", 600, 1100), ("bench.clients", 0, 2000)]
    out = tracing.reduce([ops], spans)
    assert out["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 400) + [700, 800) = 400 ns
    assert out["busy_s"] == pytest.approx(400e-9)
    assert tracing.kernel_seconds(out, "batched_walk") == (pytest.approx(300e-9), 1)
    # gap [400, 700) straddles two requests: the client loop covers most of
    # it; gap [800, 1100) lies in one request, the shorter of two covers
    assert [(n, round(s * 1e9)) for n, s in out["idle_gaps"]] == \
        [("bench.clients", 300), ("bench.request", 300)]
    assert out["device_ops"][0] == ["batched_walk", pytest.approx(300e-9)]


def _recorded_profile():
    """The recorded trace kept in ``bench/tests/data``, in the shape of
    ``jax.profiler.ProfileData``: planes, their lines, and events."""
    import json
    import os
    from types import SimpleNamespace as NS

    path = os.path.join(os.path.dirname(__file__), "data",
                        "compas_batch_walk_trace.json")
    with open(path) as f:
        planes = json.load(f)["planes"]
    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=ln["name"], events=[NS(name=n, start_ns=s, end_ns=e)
                                    for n, s, e in ln["events"]])
        for ln in p["lines"]]) for p in planes])


def test_reduce_reads_a_trace_recorded_on_the_chip():
    chips, spans = tracing.extract(_recorded_profile())
    assert len(chips) == 1 and len(chips[0]) == 478     # the XLA Ops line only
    assert {n for n, _, _ in spans} == {"bench.window", "bench.clients",
                                        "bench.request"}
    out = tracing.reduce(chips, spans)
    assert out["window_s"] == pytest.approx(0.06)
    assert out["busy_s"] == pytest.approx(0.009589654)
    # the kernel alone: not the slices whose text names its output
    seconds, names = tracing.kernel_seconds(out, "batched_walk")
    assert names == 1 and seconds == pytest.approx(0.00859538)
    assert seconds <= out["busy_s"] <= out["window_s"]
    assert out["device_ops"][0][0].startswith("%batched_walk.1 = ")
    assert {n for n, _ in out["idle_gaps"]} <= {"bench.clients", "bench.request"}


def test_reduce_averages_busy_over_chips_and_needs_one_window():
    spans = [("bench.window", 0, 100)]
    out = tracing.reduce([[("a", 0, 100)], [("a", 0, 50)]], spans)
    assert out["busy_s"] == pytest.approx(75e-9)
    with pytest.raises(RuntimeError):
        tracing.reduce([[("a", 0, 1)]], spans + spans)
    with pytest.raises(RuntimeError):
        tracing.reduce([], spans)


def _relation(seed: int, n_in: int, n_out: int, density: float):
    rng = np.random.default_rng(seed)
    return rng.random((n_in, n_out)) < density


@pytest.mark.parametrize("density", [0.0005, 0.01, 0.3])
def test_work_is_at_most_the_dense_and_the_index_encodings(density):
    dense = _relation(2**31 + 5, 700, 500, density)
    nnz = int(dense.sum())
    for batch in (1, 64):
        w = work.plan_work(batch, [(700, 500, nnz)])
        masks = work.packed_bytes(batch, 700) + work.packed_bytes(batch, 500)
        dense_bytes = np.packbits(dense, axis=1).nbytes
        index_bytes = 4 * (500 + 1) + 4 * nnz    # int32 offsets and indices
        assert w.bytes - masks <= min(dense_bytes, index_bytes)
        assert w.ops == batch * nnz


def test_least_time_names_the_bound_that_binds():
    peaks = spec.peaks("TPU v5 lite")
    t, bound = work.Work(bytes=819_000, ops=1).least_seconds(peaks)
    assert bound == "memory" and t == pytest.approx(1e-6)
    t, bound = work.Work(bytes=1, ops=393_000_000).least_seconds(peaks)
    assert bound == "compute" and t == pytest.approx(1e-6)


def test_lineage_hops_follow_the_pipeline_both_ways():
    kept = np.arange(0, 10, 2)
    steps = [None, kept, None]
    assert work.lineage_hops(10, steps, "fwd") == [(10, 10, 10), (10, 5, 5), (5, 5, 5)]
    assert work.lineage_hops(10, steps, "bwd") == [(5, 5, 5), (5, 10, 5), (10, 10, 10)]


def test_reference_composes_the_kept_rows_and_the_control_differs():
    steps = [None, np.array([1, 3, 4, 6]), np.array([0, 2, 3])]
    ref = record.Reference(8, steps)
    assert ref.src_of_sink.tolist() == [1, 4, 6]
    assert ref.answer("fwd", [0, 1, 6]).tolist() == [0, 2]
    assert ref.answer("bwd", [2, 0]).tolist() == [1, 6]
    ctl = record.Control(8, steps)
    assert ctl.answer("fwd", [0, 1, 6]).tolist() == [0]
    assert ctl.answer("fwd", [0]).tolist() == []


def test_reference_follows_a_row_map_that_repeats_rows():
    ref = record.Reference(4, [np.array([2, 0, 2, 3])])
    assert ref.answer("fwd", [2]).tolist() == [0, 2]
    assert ref.answer("bwd", [0, 1, 2]).tolist() == [0, 2]


class _Tracked:
    def filter_rows(self, mask, op_name):
        self.mask = mask
        return self


def test_filter_keeps_the_largest_values_in_row_order():
    values = deploy.SourceValues({"a": np.array([3.0, 9.0, 1.0, 7.0])})
    step = {"kind": "filter_top", "args": {"col": "a", "keep": 2}}
    d, kept = spec.module("steps", "filter_top").apply(_Tracked(), step, values)
    assert kept.tolist() == [1, 3] and d.mask.tolist() == [False, True, False, True]
    values.advance({"args": {"col": "a"}}, kept)
    with pytest.raises(ValueError):
        values.values("a")
