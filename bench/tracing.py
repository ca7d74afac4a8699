"""From a profiler trace to the numbers the per-layer metrics read.

``jax.profiler.trace`` writes an XSpace (``*.xplane.pb``).  Each chip is a
plane named ``/device:TPU:<n>``; the operations that ran on it are the
events of its ``XLA Ops`` line.  The benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) are events on the
host plane, on the same clock.  The window is the ``bench.window`` span.

- busy: the union of a chip's operation intervals inside the window,
  averaged over the chips;
- kernel time: the summed durations of the operations named after the
  kernel (operations whose text only names it as an operand do not count);
- idle gaps: the stretches of the window in which a chip ran nothing,
  each named by the benchmark span that covers most of it.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[str, int, int]       # (name, start_ns, end_ns)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10


def load(trace_dir: str):
    """The ``ProfileData`` of the one trace written under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found {paths}")
    return ProfileData.from_file(paths[0])


def extract(profile) -> Tuple[List[List[Interval]], List[Interval]]:
    """Per chip, its operations; and the benchmark's host spans."""
    chips: List[List[Interval]] = []
    spans: List[Interval] = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops: List[Interval] = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events]
            chips.append(ops)
        else:
            for line in plane.lines:
                spans += [(e.name, int(e.start_ns), int(e.end_ns))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return chips, spans


def _clip(ops: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops if e > lo and s < hi]


def _merge(ops: Sequence[Interval]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for _, s, e in sorted(ops, key=lambda x: x[1]):
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def _label(spans: Sequence[Interval], s: int, e: int) -> str:
    """The benchmark span that covers most of [s, e), the shortest on a
    tie."""
    best, best_cover, best_len = "none", 0, None
    for name, ss, se in spans:
        if name == WINDOW_SPAN:
            continue
        cover = min(e, se) - max(s, ss)
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and se - ss < best_len):
            best, best_cover, best_len = name, cover, se - ss
    return best


def reduce(chips: Sequence[Sequence[Interval]],
           spans: Sequence[Interval]) -> Dict:
    """The trace's numbers over the ``bench.window`` span."""
    wins = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found {len(wins)}")
    lo, hi = wins[0]
    if not chips:
        raise RuntimeError("the trace holds no TPU plane")
    busy_ns, by_name, gaps = [], {}, []
    for ops in chips:
        ops = _clip(ops, lo, hi)
        merged = _merge(ops)
        busy_ns.append(sum(e - s for s, e in merged))
        for name, s, e in ops:
            by_name[name] = by_name.get(name, 0) + (e - s)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "op_seconds": {k: v / 1e9 for k, v in by_name.items()},
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_label(spans, s, e), (e - s) / 1e9]
                      for s, e in gaps[:TOP]],
    }


def _own_name(op: str) -> str:
    """An operation's own name: a TPU trace names each operation by its HLO
    text (``%batched_walk.1 = (...) custom-call(...)``), in which the names
    of its operands appear too."""
    return op.split(" = ", 1)[0].lstrip("%")


def kernel_seconds(summary: Dict, kernel: str) -> Tuple[float, int]:
    """Summed device seconds of the operations named after ``kernel``
    (``kernel`` or ``kernel.<n>``), and how many distinct operation names
    matched."""
    hits = {k: v for k, v in summary["op_seconds"].items()
            if _own_name(k) == kernel or _own_name(k).startswith(kernel + ".")}
    return sum(hits.values()), len(hits)
