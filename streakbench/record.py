"""What a run hands to the metric readers.

Each metric under ``metrics/`` is a file with one function,
``read(rec: Record) -> float | None``; None means the run had nothing for
it to read, and the metric is left out of the result line.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class Request:
    spec: dict                  # the query spec, varied values merged in
    k: int
    due: float
    admitted: float | None
    finished: float | None
    state: str                  # "ok", "error", "partial" or "pending"
    counters: dict              # the program's per-request counters


@dataclasses.dataclass
class Record:
    loop: str                   # "open" or "closed"
    t0: float                   # window start, host clock (s)
    t1: float                   # window end
    requests: list              # [Request] submitted in the window
    steps: list                 # [(start, end)] of engine steps
    setup: dict                 # set-up components, seconds, and setup_s
    store_bytes: int            # array bytes reachable from the store
    n_quads: int
    trace: object = None        # trace_reduce.Reduced, traced runs only

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def latencies(self) -> list:
        """Due to answer, seconds, of every request due in the window; a
        request not answered correctly in time counts as infinite."""
        return [r.finished - r.due if r.state == "ok" else math.inf
                for r in self.requests]

    def answered(self) -> list:
        return [r for r in self.requests if r.state == "ok"]

    def window_steps(self) -> list:
        return [(s, e) for s, e in self.steps if self.t0 <= s < self.t1]


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile: the ceil(q/100 * n)-th smallest value."""
    v = sorted(values)
    if not v:
        return math.nan
    return v[max(math.ceil(q / 100.0 * len(v)), 1) - 1]
