"""What every loop kind shares: probe rows drawn from the seed, and the
record of what one window served.

A request is timed from when it was due to when its answer was set.  In a
closed loop a request is due when its client is ready to send it; in an
open loop, at its arrival time, however late the generator sent it.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np


def probe_rows(rng, n: int, count: int, size: int) -> np.ndarray:
    """``count`` probe sets of ``size`` distinct rows of ``range(n)``, each
    sorted: ``(count, size)`` int64."""
    rows = rng.integers(0, n, size=(count, size))
    while True:
        s = np.sort(rows, axis=1)
        dup = (s[:, 1:] == s[:, :-1]).any(axis=1) if size > 1 else \
            np.zeros(count, dtype=bool)
        if not dup.any():
            return s
        rows[dup] = rng.integers(0, n, size=(int(dup.sum()), size))


@dataclasses.dataclass
class Served:
    """What one window served: per request its due time, its send time,
    its answer time (NaN if none came), its direction, probe rows and
    answer, and whether it failed."""
    due: List[float] = dataclasses.field(default_factory=list)
    sent: List[float] = dataclasses.field(default_factory=list)
    done: List[float] = dataclasses.field(default_factory=list)
    direction: List[str] = dataclasses.field(default_factory=list)
    rows: List[np.ndarray] = dataclasses.field(default_factory=list)
    answers: List[object] = dataclasses.field(default_factory=list)
    errors: List[bool] = dataclasses.field(default_factory=list)
    t_start: float = 0.0
    t_end: float = 0.0

    def add(self, due: float, direction: str, rows: np.ndarray) -> int:
        """A new request, due at ``due``; returns its number."""
        self.due.append(due)
        self.sent.append(float("nan"))
        self.done.append(float("nan"))
        self.direction.append(direction)
        self.rows.append(rows)
        self.answers.append(None)
        self.errors.append(False)
        return len(self.due) - 1

    def settle(self, i: int, fut) -> None:
        """The future of request ``i`` has its answer or its error."""
        t = time.perf_counter()
        if fut.exception() is None:
            self.answers[i] = fut.result()
            self.done[i] = t
        else:
            self.errors[i] = True

    def answered(self) -> np.ndarray:
        return ~np.isnan(np.asarray(self.done, dtype=float))

    def latencies_ms(self) -> np.ndarray:
        """Per request, due to answer.  A request that failed or was not
        answered counts as answered when the window closed."""
        done = np.asarray(self.done, dtype=float)
        done = np.where(np.isnan(done), self.t_end, done)
        return (done - np.asarray(self.due, dtype=float)) * 1e3

    def late_ms(self) -> np.ndarray:
        """Per request sent, how long after its due time it was sent."""
        late = np.asarray(self.sent, dtype=float) - np.asarray(self.due, dtype=float)
        return late[~np.isnan(late)] * 1e3

    def failed(self) -> int:
        """Requests that failed or were never answered."""
        return int((~self.answered()).sum())

    def compared(self):
        """``(direction, rows, answer)`` per answered request, one entry
        per probe set."""
        for d, rows, ans, t in zip(self.direction, self.rows, self.answers,
                                   self.done):
            if np.isnan(t):
                continue
            if rows.ndim == 2:
                yield from ((d, r, a) for r, a in zip(rows, ans))
            else:
                yield d, rows, ans
