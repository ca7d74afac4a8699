"""Find a cell's files by the names ``BENCHMARK.json`` and its files give.

Under ``bench/``, each piece is a file of its own:

- ``configs[].file``: the configuration (source table, pipeline as a list
  of steps, session and tier options);
- ``steps/<kind>.py``: how one kind of pipeline step is captured and which rows it
  keeps (``apply``);
- ``traffic/<traffic>.json``: the traffic mix, a data file; its ``loop``
  and ``plan`` keys name the two modules below;
- ``loops/<loop>.py``: one load generator per loop kind (``warm`` and
  ``drive``);
- ``plans/<plan>.py``: one plan kind: how a request's plan is built, and
  its plain reference and control;
- ``metrics/<metric>.py``: one reader per metric, a ``read(record)`` that
  returns the metric's value, or None where it finds nothing to read;
- ``peaks.json``: the chip's peaks, keyed by JAX's ``device_kind``.

Adding a cell, a traffic mix, a loop kind, a plan kind, a step or a metric
adds files and entries; no existing file needs an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def module(group: str, name: str, root: str = ROOT):
    """``bench/<group>/<name>.py`` of the checkout at ``root``, loaded by
    its path (once per process)."""
    path = os.path.join(root, "bench", group, f"{name}.py")
    key = "bench:" + path
    if key not in sys.modules:
        if not os.path.isfile(path):
            raise KeyError(f"no {group} module {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: str = ROOT

    def module(self, group: str, name: str):
        return module(group, name, self.root)

    @property
    def loop(self):
        return self.module("loops", self.traffic["loop"])

    @property
    def plans(self):
        return self.module("plans", self.traffic["plan"])


def benchmark(root: str = ROOT) -> Dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def resolve(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with its files read.  A metric with a
    ``workloads`` list applies to the cells it lists, one without to every
    cell."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      f"{w['traffic']}.json"))

    def mine(m: Dict) -> bool:
        return workload in m.get("workloads", [workload])

    return Cell(workload, w["config"], config, w["traffic"], traffic,
                int(w["chips"]), [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)], root)


def reader(metric: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return module("metrics", metric, root).read


def peaks(device_kind: str, root: str = ROOT) -> Dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = _load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["kinds"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (kinds: {sorted(table['kinds'])})")
    return table["kinds"][device_kind]
