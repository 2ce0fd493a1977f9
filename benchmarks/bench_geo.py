"""Geographica-shaped query diversity: range / within-distance / kNN /
spatial-join selections (no top-k ranking), each at two dataset scales and
across 1/2/4 shards.

Geographica's micro benchmark stresses exactly these shapes; here they run
through the same SIP + fused-kernel pipeline as the paper's top-k queries
(core/shapes.py), so this suite tracks how much of the top-k machinery's
pruning transfers to plain spatial selections. ``derived`` carries the
result cardinality — a free cross-check that a perf change did not silently
change semantics.
"""
from __future__ import annotations

from repro import StreakEngine
from repro.core.query import Query, SpatialFilter, TriplePattern, Var
from repro.core.shard import shard_store
from repro.data import synth_rdf

from . import common

_GEO_CACHE: dict = {}

# (scale label, n_per_class) — "small" is Geographica-micro-sized, "large"
# is the regime where block scanning dominates per-query overheads
SCALES = (("small", 800), ("large", 6000))
SHARDS = (1, 2, 4)


def dataset(n_per_class: int):
    """The suite's lgd dataset at one scale (cached per process)."""
    if n_per_class not in _GEO_CACHE:
        _GEO_CACHE[n_per_class] = synth_rdf.make_lgd(
            n_per_class=n_per_class, seed=3, block=1024)
    return _GEO_CACHE[n_per_class]


def _patterns(ns, cls, suffix=""):
    p, g = Var(f"place{suffix}"), Var(f"g{suffix}")
    return p, g, (
        TriplePattern(p, Var(f"tp{suffix}"), ns[cls], g=Var(f"r{suffix}")),
        TriplePattern(Var(f"r{suffix}"), ns["hasConfidence"],
                      Var(f"conf{suffix}")),
        TriplePattern(p, ns["hasGeometry"], g),
    )


def queries(ns) -> list:
    """The four Geographica selection shapes over an lgd dataset."""
    pa, ga, pats_a = _patterns(ns, "class:hotel")
    pb, gb, pats_b = _patterns(ns, "class:park", "2")
    return [
        ("range", Query(select=(pa,), patterns=pats_a, ranking=None,
                        spatial=SpatialFilter(ga, None,
                                              window=(20.0, 15.0,
                                                      55.0, 45.0)))),
        ("within", Query(select=(pa,), patterns=pats_a, ranking=None,
                         spatial=SpatialFilter(ga, None, dist=12.0,
                                               center=(50.0, 50.0)))),
        ("knn", Query(select=(pa, pb), patterns=pats_a + pats_b,
                      ranking=None,
                      spatial=SpatialFilter(ga, gb, knn=3))),
        ("join", Query(select=(pa, pb), patterns=pats_a + pats_b,
                       ranking=None,
                       spatial=SpatialFilter(ga, gb, dist=2.0))),
    ]


def run() -> list:
    rows = []
    for scale, n_per_class in SCALES:
        ds = dataset(n_per_class)
        for n_shards in SHARDS:
            store = (ds.store if n_shards == 1
                     else shard_store(ds.store, n_shards))
            eng = StreakEngine(store)
            for shape, q in queries(ds.ns):
                scores, _, _ = eng.execute(q)  # warm scan cache + card check
                t = common.timeit(lambda: eng.execute(q))
                rows.append(common.row(
                    f"geographica/{scale}/{shape}/shards{n_shards}", t,
                    f"rows={len(scores)}"))
    return rows


if __name__ == "__main__":  # pragma: no cover - manual convenience
    for r in run():
        print(r)
