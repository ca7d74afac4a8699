"""Named spans at the program's layer boundaries.

``span(name, counters, key, **meta)`` opens a ``jax.profiler.TraceAnnotation``
(a no-op unless a profiler trace is recording; when one is, the span lands
on the host plane of that trace, on the same clock as the device
operations) and, given a counter dict, adds the span's
``time.perf_counter_ns()`` duration to ``counters[key]``.  The timing
counters are always on: two clock reads and one integer addition.  A span
adds no synchronisation: it times what the enclosed code already waits for.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import jax

__all__ = ["span"]


class span:
    """Context manager: a profiler span named ``name`` carrying ``meta``,
    and, when ``counters`` is given, its nanoseconds added to
    ``counters[key]``."""

    __slots__ = ("_annotation", "_counters", "_key", "_t0")

    def __init__(self, name: str, counters: Optional[Dict[str, int]] = None,
                 key: Optional[str] = None, **meta) -> None:
        self._annotation = jax.profiler.TraceAnnotation(name, **meta)
        self._counters = counters
        self._key = key

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        if self._counters is not None:
            self._counters[self._key] += time.perf_counter_ns() - self._t0
        self._annotation.__exit__(*exc)
