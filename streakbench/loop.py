"""The serving loops that drive `SpatialServeEngine` through the window.

One thread submits requests and calls `step()`; the benchmark's host clock
stamps each request's due time, the step whose slots first hold it, and
the step after which it is done. `span` wraps each call into the program
(a profiler annotation in a traced run), so the trace's idle gaps can be
named by what the host was doing.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
import time

clock = time.perf_counter


@dataclasses.dataclass
class Tracked:
    rid: int
    spec: dict
    k: int
    req: object
    due: float
    submitted: float = 0.0
    admitted: float | None = None     # start of the first step holding it
    finished: float | None = None     # end of the step that finished it


@dataclasses.dataclass
class Window:
    t0: float
    t1: float                          # t0 + the measured seconds
    end: float = 0.0                   # when the loop stopped (drain included)
    tracked: list = dataclasses.field(default_factory=list)
    steps: list = dataclasses.field(default_factory=list)   # (start, end)


def _no_span(name: str):
    return contextlib.nullcontext()


class Loop:
    """Drives one engine. `make(rid, spec, k)` returns a fresh request."""

    def __init__(self, engine, make, span=None):
        self.engine = engine
        self.make = make
        self.span = span or _no_span
        self.inflight: list[Tracked] = []
        self.on_close = None

    def _closed(self, w: Window) -> None:
        """Runs `on_close` once, at the first step that ends after t1."""
        if self.on_close is not None and clock() >= w.t1:
            self.on_close()
            self.on_close = None

    def _submit(self, w: Window, rid: int, spec: dict, k: int, due: float):
        tr = Tracked(rid, spec, k, self.make(rid, spec, k), due)
        with self.span("bench.submit"):
            self.engine.submit(tr.req)
        tr.submitted = clock()
        w.tracked.append(tr)
        self.inflight.append(tr)

    def _step(self, w: Window) -> list:
        """One engine step; returns the requests it finished."""
        ts = clock()
        with self.span("bench.step"):
            self.engine.step()
        te = clock()
        w.steps.append((ts, te))
        done, still = [], []
        for tr in self.inflight:
            if tr.admitted is None and tr.req.steps > 0:
                tr.admitted = ts
            if tr.req.done:
                tr.finished = te
                done.append(tr)
            else:
                still.append(tr)
        self.inflight = still
        return done

    def warm(self, draws: list, clients: int) -> list:
        """Serve every (spec, k) of `draws` with up to `clients` in flight,
        to the last answer; returns them tracked. Set-up, not the window."""
        w = Window(clock(), math.inf)
        todo = iter(enumerate(draws, start=-len(draws)))   # rids below 0
        for rid, (spec, k) in itertools.islice(todo, clients):
            self._submit(w, rid, spec, k, clock())
        while self.inflight:
            for _ in self._step(w):
                for rid, (spec, k) in itertools.islice(todo, 1):
                    self._submit(w, rid, spec, k, clock())
        return w.tracked

    def open(self, draws: list, due: list, seconds: float,
             drain_s: float, on_close=None) -> Window:
        """Submit request i at t0 + due[i]; step while anything is in
        flight; stop when all are answered or the drain time is spent.
        `on_close` runs once when the window has closed."""
        self.on_close = on_close
        t0 = clock()
        w = Window(t0, t0 + seconds)
        stop = w.t1 + drain_s
        i, n = 0, len(due)
        while True:
            now = clock()
            while i < n and t0 + due[i] <= now:
                spec, k = draws[i]
                self._submit(w, i, spec, k, t0 + due[i])
                i += 1
            if self.inflight:
                self._step(w)
                self._closed(w)
                if clock() > stop:
                    break
            elif i < n:
                with self.span("bench.wait_arrival"):
                    time.sleep(max(0.0, t0 + due[i] - clock()))
            else:
                break
        w.end = clock()
        self.on_close = None
        return w

    def closed(self, stream, clients: int, seconds: float,
               drain_s: float, on_close=None) -> Window:
        """`clients` callers, each sending its next draw from `stream` as
        soon as its last request is answered, until t0 + seconds; then the
        requests in flight are awaited up to `drain_s`. `on_close` runs
        once when the window has closed."""
        self.on_close = on_close
        t0 = clock()
        w = Window(t0, t0 + seconds)
        stop = w.t1 + drain_s
        rid = 0
        for _ in range(clients):
            spec, k = next(stream)
            self._submit(w, rid, spec, k, clock())
            rid += 1
        while self.inflight:
            done = self._step(w)
            self._closed(w)
            now = clock()
            if now < w.t1:
                for _ in done:
                    spec, k = next(stream)
                    self._submit(w, rid, spec, k, now)
                    rid += 1
            elif now > stop:
                break
        w.end = clock()
        self.on_close = None
        return w
