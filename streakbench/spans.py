"""Reduce the program's spans in a profiler trace to per-layer self time,
and name the device's idle time by the span the host was in.

The program marks each layer of its served top-k path with a host span
named ``streak.*`` (``src/repro/core/spans.py``). This reads them from a
traced run's ``.xplane.pb`` (`trace_reduce.load`), on the host line that
holds the ``bench.window`` span, clipped to that window:

- count, total and *self* time per name; the self time of a span is its
  duration less the part its ``streak.*`` children cover, so the self
  times of ``streak.step`` and everything under it add up to the steps'
  total;
- the device-idle time inside the window (no XLA operation running, as
  `trace_reduce` defines it), each stretch given to the innermost
  ``streak.*`` or ``bench.*`` span that overlaps it (``none`` outside all
  of them), by overlap and not by the stretch's midpoint.

Each ``streak.step`` also carries, as its metadata, what that step added
to the program's counters (share-cache lookups and hits, bytes uploaded
and fetched by the kernel dispatches); they are summed over the window's
steps.

`reduce` returns None when the window holds no ``streak.step``: a run of a
program without the spans, or no trace at all. A metric reader calls
`of_run`, which finds the profile that `harness.run` wrote for a traced
run and reduces it once.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
from pathlib import Path

from .trace_reduce import OPS_LINES, WINDOW_SPAN, _union, find_xplane, load

PREFIX = "streak."
STEP = "streak.step"
BENCH = "bench."
# where `harness.run` writes a traced run's profile, under the checkout
TRACE_DIR = Path(".bench_out") / "trace"


@dataclasses.dataclass
class Spans:
    window_s: float
    steps: int                  # streak.step spans in the window
    count: dict                 # name -> spans in the window
    total_s: dict               # name -> their time in the window
    self_s: dict                # name -> that time less their children's
    idle_s: dict                # innermost span -> device-idle seconds in it
    counters: dict              # counter -> its sum over the window's steps

    def per_step_ms(self, name: str) -> float | None:
        """Self time of `name` per engine step, ms."""
        if not self.steps:
            return None
        return 1000.0 * self.self_s.get(name, 0.0) / self.steps

    def counter_per_step(self, name: str) -> float | None:
        """What the window's steps added to counter `name`, per step."""
        if not self.steps or name not in self.counters:
            return None
        return self.counters[name] / self.steps

    def idle_in_program(self) -> float | None:
        """Share of the device-idle time inside ``bench.step`` that falls
        in a ``streak.*`` span."""
        prog = sum(v for n, v in self.idle_s.items() if n.startswith(PREFIX))
        whole = prog + self.idle_s.get("bench.step", 0.0)
        return prog / whole if whole else None

    def describe(self) -> str:
        idle = sum(self.idle_s.values())
        mean = 1000.0 * self.total_s[STEP] / max(self.steps, 1)
        lines = [f"spans: {self.steps} steps in {self.window_s:.3f} s, "
                 f"{STEP} {mean:.3f} ms on average; per step: " + ", ".join(
                     f"{n} {self.per_step_ms(n):.3f} ms self "
                     f"({self.count[n] / max(self.steps, 1):.2f}x)"
                     for n in sorted(self.self_s, key=self.self_s.get,
                                     reverse=True))]
        if idle:
            lines.append("device idle by span: " + ", ".join(
                f"{n} {100.0 * v / idle:.2f}%"
                for n, v in sorted(self.idle_s.items(), key=lambda t: -t[1])))
            share = self.idle_in_program()
            if share is not None:
                lines.append(f"device idle inside bench.step in streak.* "
                             f"spans: {100.0 * share:.2f}%")
        if self.counters:
            lines.append("counters per step: " + ", ".join(
                f"{n} {self.counter_per_step(n):.1f}"
                for n in sorted(self.counters)))
        return "\n".join(lines)


def _innermost(events: list, w0: int, w1: int) -> list:
    """[(start, end, name)] stretches of [w0, w1] in which `name` is the
    innermost of the nested `events` [(name, start, end)] ("none" where no
    event is open)."""
    out: list = []
    stack: list = []            # (name, end) of the open events
    cur = w0

    def emit(end, name):
        nonlocal cur
        a, b = max(cur, w0), min(end, w1)
        if b > a:
            out.append((a, b, name))
        cur = max(cur, end)

    for name, s, e in sorted(events, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:      # up to its end: the closing
            name_c, end = stack.pop()
            emit(end, name_c)
        emit(s, stack[-1][0] if stack else "none")
        if stack:
            e = min(e, stack[-1][1])      # a child ends with its parent
        stack.append((name, e))
    while stack:
        name, end = stack.pop()
        emit(end, name)
    emit(w1, "none")
    return out


def overlap(stretches: list, gaps: list) -> collections.Counter:
    """name -> the length of `gaps` [(start, end)] that the `stretches`
    [(start, end, name)] of that name cover; both sorted and disjoint."""
    out: collections.Counter = collections.Counter()
    i = 0
    for a, b, n in stretches:
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            out[n] += min(b, gaps[j][1]) - max(a, gaps[j][0])
            j += 1
    return out


def reduce(path: Path | None) -> Spans | None:
    if path is None:
        return None
    pd = load(path)
    window, line_events, step_stats, device_lines = None, None, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            device_lines.append([(e.start_ns, e.start_ns + e.duration_ns)
                                 for ln in plane.lines
                                 if ln.name in OPS_LINES for e in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in ln.events
                       if e.name.startswith((PREFIX, BENCH))]
                for name, s, e in evs:
                    if name == WINDOW_SPAN and window is None:
                        window, line_events = (s, e), evs
                        step_stats = [
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             list(ev.stats))
                            for ev in ln.events if ev.name == STEP]
    if window is None:
        return None
    w0, w1 = window
    events = [(n, s, e) for n, s, e in line_events
              if n != WINDOW_SPAN and e > w0 and s < w1]
    if not any(n == STEP for n, _, _ in events):
        return None
    count: collections.Counter = collections.Counter()
    total: collections.Counter = collections.Counter()
    for n, s, e in events:
        if n.startswith(PREFIX):
            count[n] += 1
            total[n] += (min(e, w1) - max(s, w0)) / 1e9
    stretches = _innermost(events, w0, w1)
    self_s: collections.Counter = collections.Counter()
    for a, b, n in stretches:
        if n.startswith(PREFIX):
            self_s[n] += (b - a) / 1e9
    counters: collections.Counter = collections.Counter()
    for s, e, stats in step_stats:
        if e > w0 and s < w1:
            for k, v in stats:
                if isinstance(v, (int, float)):
                    counters[k] += v
    idle: collections.Counter = collections.Counter()
    for ops in device_lines:
        busy = _union([(max(s, w0), min(e, w1)) for s, e in ops
                       if e > w0 and s < w1])
        edges = [w0] + [x for se in busy for x in se] + [w1]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        for n, ns in overlap(stretches, gaps).items():
            idle[n] += ns / 1e9 / len(device_lines)
    return Spans(window_s=(w1 - w0) / 1e9, steps=count[STEP],
                 count=dict(count), total_s=dict(total), self_s=dict(self_s),
                 idle_s=dict(idle), counters=dict(counters))


_reduced: dict = {}


def of_run(rec, root: Path) -> Spans | None:
    """The reduced spans of run `rec` of the checkout at `root`: None
    unless the run was traced. The profile is reduced once, and its summary
    printed on standard error then."""
    if rec.trace is None:
        return None
    path = find_xplane(Path(root) / TRACE_DIR)
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _reduced:
        _reduced.clear()
        _reduced[key] = reduce(path)
        if _reduced[key] is not None:
            print(_reduced[key].describe(), file=sys.stderr, flush=True)
    return _reduced[key]
