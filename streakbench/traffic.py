"""One generator for every traffic mix; a mix is a JSON file of parameters.

Keys of a mix file:

- ``loop``: ``"open"`` (arrivals on a schedule, at ``rate_qps``) or
  ``"closed"`` (``clients`` callers, each sending its next request as soon
  as its last one is answered, with no think time);
- ``queries``: the query specs, each a dict the configuration's generator
  turns into a query (a template name and its parameters);
- ``ks``: the per-tenant k values;
- ``vary`` (optional): parameters each request draws afresh, each merged
  into the query spec, ``{"name": {"geomspace": [lo, hi], "n": n}}``: the
  window's values are the n-point geometric grid from lo to hi;
- ``warmup_requests``: how many requests are served once before the
  window, as set-up, with as many in flight as the window keeps; they come
  from a stream of their own, whose varied values lie between the
  window's grid points, so that the warm-up makes the window's shapes but
  answers none of the window's varied queries;
- ``check_requests`` (optional): how many answered requests the check
  compares, drawn from the seed, the slowest among them; all when absent;
- ``drain_s``: how long answers due in the window are awaited after it.

The specs, the ks and each varied parameter are drawn in cycles of their
own: each cycle holds every value once, in an order drawn from the seed, so
every seed sends the same mix of work in another order. An open loop's
arrivals are a Poisson process given its count whose gaps are the same for
every seed, in another order.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

LOOPS = ("open", "closed")


@dataclasses.dataclass
class Mix:
    name: str
    loop: str
    queries: list
    ks: list
    warmup_requests: int
    drain_s: float
    rate_qps: float = 0.0
    clients: int = 0
    vary: dict = dataclasses.field(default_factory=dict)
    check_requests: int = 0           # 0: every answered request

    @classmethod
    def load(cls, path: Path) -> "Mix":
        d = json.loads(Path(path).read_text())
        mix = cls(name=Path(path).stem, loop=d["loop"], queries=d["queries"],
                  ks=[int(k) for k in d["ks"]],
                  warmup_requests=int(d["warmup_requests"]),
                  drain_s=float(d["drain_s"]),
                  rate_qps=float(d.get("rate_qps", 0.0)),
                  clients=int(d.get("clients", 0)),
                  vary=d.get("vary", {}),
                  check_requests=int(d.get("check_requests", 0)))
        if mix.loop not in LOOPS:
            raise ValueError(f"{path}: loop {mix.loop!r} not in {LOOPS}")
        if mix.loop == "open" and mix.rate_qps <= 0:
            raise ValueError(f"{path}: an open loop needs rate_qps > 0")
        if mix.loop == "closed" and mix.clients <= 0:
            raise ValueError(f"{path}: a closed loop needs clients > 0")
        if not mix.queries or not mix.ks:
            raise ValueError(f"{path}: no queries or no ks")
        for name, v in mix.vary.items():
            if set(v) != {"geomspace", "n"} or int(v["n"]) < 2:
                raise ValueError(f"{path}: vary {name!r} needs geomspace "
                                 "[lo, hi] and n >= 2")
        return mix

    def grid(self, name: str, purpose: int) -> list:
        """The values of varied parameter `name`: the window's grid
        (purpose 0), or the geometric midpoints between its points."""
        v = self.vary[name]
        g = np.geomspace(*map(float, v["geomspace"]), int(v["n"]))
        if purpose != 0:
            g = np.sqrt(g[1:] * g[:-1])
        return [float(x) for x in g]

    def stream(self, seed: int, purpose: int):
        """Endless (spec, k) draws; `purpose` 0 is the window's stream,
        other values give streams of their own (the warm-up's is 1)."""
        base = [int(seed) % (1 << 63), purpose]
        values = [list(range(len(self.queries))), self.ks]
        values += [self.grid(n, purpose) for n in sorted(self.vary)]
        cycles = [_cycle(np.random.default_rng(base + [i]), v)
                  for i, v in enumerate(values)]
        while True:
            i, k, *drawn = (next(c) for c in cycles)
            spec = dict(self.queries[i])
            spec.update(zip(sorted(self.vary), drawn))
            yield spec, int(k)

    def take(self, seed: int, purpose: int, n: int) -> list:
        it = self.stream(seed, purpose)
        return [next(it) for _ in range(n)]

    def arrivals(self, seed: int, seconds: float) -> np.ndarray:
        """Due times (s from the window's start) of an open loop: the gaps
        of one Poisson draw of rate × seconds arrivals, fixed by the rate
        and the window, in an order drawn from `seed`."""
        n = int(round(self.rate_qps * seconds))
        fixed = np.sort(np.random.default_rng(n).uniform(0.0, seconds, n))
        gaps = np.diff(fixed, prepend=0.0)
        rng = np.random.default_rng([int(seed) % (1 << 63), 2])
        return np.cumsum(gaps[rng.permutation(n)])


def _cycle(rng, values: list):
    """Every value once per cycle, each cycle in an order drawn from rng."""
    while True:
        for j in rng.permutation(len(values)):
            yield values[j]
