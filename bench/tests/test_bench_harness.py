"""The benchmark harness at a tiny size on the CPU.

Every cell of ``BENCHMARK.json`` resolves its files by name; the
measurement path refuses to report without a TPU; a run on the CPU with
the harness's look for a chip skipped comes out correct, and comes out not
correct with the control, or with the timed path broken underneath.
"""
import copy
import json
import os
import re

import numpy as np
import pytest

from bench import control, deploy, harness, spec
from bench import run as bench_run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
PEAKS = spec.peaks("TPU v5 lite")


def test_every_cell_resolves_its_files_by_name():
    bench = spec.benchmark()
    assert bench["paths"] == ["bench"]
    for cell_entry in bench["workloads"]:
        cell = spec.resolve(cell_entry["name"])
        assert cell.config["pipeline"]
        for step in cell.config["pipeline"]:
            assert callable(cell.module("steps", step["kind"]).apply)
        assert callable(cell.loop.warm) and callable(cell.loop.drive)
        assert callable(cell.plans.plan) and callable(cell.plans.control)
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"]))
    with pytest.raises(KeyError):
        spec.resolve("no.such_cell")


def test_benchmark_names_keys_and_bounds_keep_to_the_contract():
    bench = spec.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in bench[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for w in m["workloads"]:     # each cell it lists reports what it moves
            assert m["moves"] in {x["name"] for x in spec.resolve(w).end_to_end}
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in bench["configs"]:
        assert 1 <= len(c["source"]) <= 200 and c["file"].startswith("bench/")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) <= 64 << 10


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


def test_measurement_path_refuses_without_a_tpu(capsys, monkeypatch):
    monkeypatch.setenv("REPRO_CALIBRATION", "unset")
    rc = bench_run.main(["--workload", "compas.batch_walk", "--seed",
                         str(2**31 + 7), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def _tiny(name: str, fused_walk=None) -> spec.Cell:
    cell = copy.deepcopy(spec.resolve(name))
    cell.config["table"]["rows"] = 300
    for step in cell.config["pipeline"]:
        if step["kind"] == "filter_top":
            step["args"]["keep"] = 280
    if fused_walk is not None:
        cell.config["session"]["fused_walk"] = fused_walk
    cell.traffic["warmup_requests"] = 1
    if cell.traffic["loop"] == "closed":
        cell.traffic["probes_per_request"] = 8
    else:
        cell.traffic["rate_per_s"] = 400
        cell.config["tier"]["max_queue"] = 8
    return cell


def _measure(cell: spec.Cell, seed: int = 2**31 + 12345) -> dict:
    import jax

    return harness.measure(cell, seed, 0.3, False, 0.0, jax.devices(), PEAKS)


def _correct(record: dict) -> bool:
    return all(v <= lim for v, lim in record["checks"].values())


CELLS = [w["name"] for w in spec.benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_tiny_run_is_correct_and_reports_its_metrics(name):
    cell = _tiny(name)
    record = _measure(cell)
    assert _correct(record), record["checks"]
    assert record["requests"] > 0 and record["failed"] == 0
    assert record["compiles_in_window"] == 0
    metrics = harness.metrics(cell, record, trace=False)
    assert set(metrics) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in metrics.values())
    line = bench_run.result_line(cell, record, metrics, _FakeDevices, False)
    assert list(line)[-1] == "checks"
    json.dumps(line)


class _FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"


_FakeDevices = [_FakeDevice()]


def test_fused_walk_route_is_correct_on_the_cpu_oracle():
    record = _measure(_tiny("compas.batch_walk", fused_walk=True))
    assert _correct(record)
    assert record["counters"]["session"]["fused_walk"] == \
        record["counters"]["session"]["plans"] > 0


@pytest.mark.parametrize("name", CELLS)
def test_control_comes_out_not_correct(name, monkeypatch):
    cell = _tiny(name)
    monkeypatch.setattr(deploy, "session", deploy.session)
    control.use_control(cell)
    record = _measure(cell)
    assert record["checks"]["wrong_answers"][0] > 0
    assert not _correct(record)


class _AlteredAnswer:
    """The real session, with one answer altered where it is produced."""

    def __init__(self, real):
        self._real = real

    def __getattr__(self, name):
        return getattr(self._real, name)

    @staticmethod
    def _alter(answer):
        return np.append(answer, answer[-1] + 1 if len(answer) else 0)

    def run(self, plan):
        out = self._real.run(plan)
        if plan.batched:
            return [self._alter(out[0])] + list(out[1:])
        return self._alter(out)

    def run_many(self, plans):
        out = self._real.run_many(plans)
        return [self._alter(out[0])] + list(out[1:])


class _SwappedFanOut:
    """The real session, handing a fused batch's answers back rotated by
    one: each request gets its neighbour's answer."""

    def __init__(self, real):
        self._real = real

    def __getattr__(self, name):
        return getattr(self._real, name)

    def run_many(self, plans):
        out = self._real.run_many(plans)
        return out[1:] + out[:1]


@pytest.mark.parametrize("name", CELLS)
def test_altered_answer_comes_out_not_correct(name, monkeypatch):
    real = deploy.session
    monkeypatch.setattr(deploy, "session",
                        lambda dep, opts: _AlteredAnswer(real(dep, opts)))
    record = _measure(_tiny(name))
    assert record["checks"]["wrong_answers"][0] > 0
    assert not _correct(record)


def test_swapped_fan_out_comes_out_not_correct(monkeypatch):
    name = next(n for n in CELLS if "tier" in spec.resolve(n).config
                and spec.resolve(n).traffic["loop"] != "closed")
    real = deploy.session
    monkeypatch.setattr(deploy, "session",
                        lambda dep, opts: _SwappedFanOut(real(dep, opts)))
    record = _measure(_tiny(name))
    assert record["counters"]["tier"]["max_batch_seen"] > 1
    assert record["checks"]["wrong_answers"][0] > 0
    assert not _correct(record)


NEW_LOOP = """
import time


def warm(run):
    run.warm_sizes([run.traffic["probes_per_request"]])


def drive(run, seconds, span):
    import concurrent.futures
    from bench import loadgen
    out = loadgen.Served()
    out.t_start = time.perf_counter()
    for i in range(run.traffic["requests"]):
        d, rows, plan = run.request(i)
        k = out.add(time.perf_counter(), d, rows)
        out.sent[k] = out.due[k]
        fut = concurrent.futures.Future()
        fut.set_result(run.session.run(plan))
        out.settle(k, fut)
    out.t_end = time.perf_counter()
    return out
"""


def _checkout_with_new_files(tmp_path):
    """A copy of the benchmark with a new loop kind, traffic mix and
    configuration (a pipeline with no filter) added as files, and one
    cell added to ``BENCHMARK.json``."""
    import shutil

    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (tmp_path / "bench" / "loops" / "fixed_count.py").write_text(NEW_LOOP)
    (tmp_path / "bench" / "traffic" / "fixed_12.json").write_text(json.dumps({
        "loop": "fixed_count", "plan": "record", "requests": 12,
        "probes_per_request": 4, "rows_per_probe": 2,
        "directions": ["fwd", "bwd"]}))
    (tmp_path / "bench" / "configs" / "onehot_only.json").write_text(json.dumps({
        "table": {"rows": 200, "cols": 6, "categorical": 3, "null_frac": 0.0},
        "pipeline": [{"kind": "same_rows", "op": "onehot", "args": {"col": "a0"}},
                     {"kind": "same_rows", "op": "impute",
                      "args": {"cols": ["a4"], "strategy": "mean"}}],
        "session": {"use_hopcache": False}}))
    bench = spec.benchmark()
    bench["configs"].append({"name": "onehot_only", "source": "a test",
                             "file": "bench/configs/onehot_only.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "onehot_only.fixed", "config": "onehot_only",
                               "traffic": "fixed_12", "chips": 1, "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(tmp_path)


def test_a_new_loop_kind_and_configuration_need_only_new_files(tmp_path, monkeypatch):
    root = _checkout_with_new_files(tmp_path)
    cell = spec.resolve("onehot_only.fixed", root)
    assert cell.loop.__file__.startswith(root)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "request_p95_ms"}
    record = _measure(cell)
    assert _correct(record) and record["requests"] == 12
    metrics = harness.metrics(cell, record, trace=False)
    assert set(metrics) == {m["name"] for m in cell.end_to_end}
    # the control is defined on a pipeline with no filter, and is caught
    monkeypatch.setattr(deploy, "session", deploy.session)
    control.use_control(cell)
    assert not _correct(_measure(cell))


def test_probes_per_s_counts_answered_requests_only():
    from bench import loadgen
    import concurrent.futures

    served = loadgen.Served()
    for i in range(4):
        k = served.add(0.0, "fwd", np.arange(2))
        fut = concurrent.futures.Future()
        if i == 3:
            fut.set_exception(RuntimeError("refused"))
        else:
            fut.set_result([np.arange(1)])
        served.settle(k, fut)
    served.t_start, served.t_end = 0.0, 1.0
    assert served.failed() == 1 and int(served.answered().sum()) == 3
    rec = {"window_s": 1.0, "probes_answered": int(served.answered().sum()) * 64}
    assert spec.reader("probes_per_s")(rec) == 3 * 64


def test_open_loop_offers_the_same_gaps_in_each_seed_and_times_from_due():
    loop = spec.module("loops", "open_poisson")
    a = loop.arrivals(500.0, 1, 4.0, np.random.default_rng(1))
    b = loop.arrivals(500.0, 1, 4.0, np.random.default_rng(2))
    assert len(a) == len(b) == 2000 and not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, append=4.0)), np.sort(np.diff(b, append=4.0)))
    assert len(loop.arrivals(500.0, 8, 4.0, np.random.default_rng(1))) == 250
    from bench import loadgen

    served = loadgen.Served()
    k = served.add(10.0, "fwd", np.arange(1))
    served.sent[k], served.done[k] = 10.25, 10.5     # sent late, answered
    assert served.latencies_ms()[0] == 500.0 and served.late_ms()[0] == 250.0
