"""YAGO3-shaped deployment: the STREAK paper's YAGO queries Q1-Q8.

A copy of the repository's YAGO generator (`make_yago`), kept here so that
a change to the program cannot move the yardstick. POINT places in 25
Gaussian clusters on a 360-unit extent, with population density, number of
people, economic growth and inflation literals, neighbour and connection
edges, and reified event/person facts carrying a confidence. Q1-Q4 and Q7
are star-shaped (SS joins, ranked by ASC(popul + popul1)); Q5, Q6 and Q8
join a reified fact's place to an attribute place (OS/RS joins, ranked by
ASC(conf + popul1)). The distance is 2% of the extent unless the spec
gives its own share, ``dist_frac``.
"""
from __future__ import annotations

import numpy as np

from .common import Interner, RawData

EXTENT = 360.0
TERMS = ("hasPopulationDensity", "hasNumberOfPeople", "hasEconomicGrowth",
         "hasInflation", "isLocatedIn", "hasNeighbor", "isConnectedTo",
         "hasGeometry", "hasConfidence", "happenedIn", "wasBornIn", "diedIn",
         "rdf:type", "class:city", "class:village", "class:event",
         "class:person")


def generate(cfg: dict, seed: int) -> RawData:
    """Quads, terms, literals and geometries of one deployment, from `seed`."""
    n_places = int(cfg["n_places"])
    rng = np.random.default_rng(seed)
    d = Interner()
    quads: list[tuple[int, int, int, int]] = []
    n_fact = 0

    def fact(s: int, p: int, o: int) -> int:
        nonlocal n_fact
        g = d.term(f"_:fact{n_fact}")
        n_fact += 1
        quads.append((g, s, p, o))
        return g

    def plain(s: int, p: int, o: int) -> None:
        quads.append((0, s, p, o))

    ns = {t: d.term(t) for t in TERMS}
    n_loc = max(8, n_places // 50)
    locations = [d.term(f"loc{i}") for i in range(n_loc)]

    centers = rng.uniform(0.0, EXTENT, size=(25, 2))
    which = rng.integers(0, 25, size=n_places)
    pts = centers[which] + rng.normal(0, EXTENT * 0.02, size=(n_places, 2))
    pts = np.clip(pts, 0.0, EXTENT)
    popul = rng.lognormal(5.0, 1.5, size=n_places)
    people = rng.lognormal(8.0, 2.0, size=n_places)
    growth = rng.normal(2.0, 3.0, size=n_places)
    infl = rng.normal(4.0, 2.0, size=n_places)
    places = []
    for i in range(n_places):
        e = d.term(f"place{i}")
        places.append(e)
        plain(e, ns["hasGeometry"], d.term(f"geom:place{i}"))
        plain(e, ns["isLocatedIn"], locations[i % n_loc])
        kind = i % 3
        if kind == 0:  # "city": density + growth (+ inflation sometimes)
            plain(e, ns["hasPopulationDensity"], d.num(popul[i]))
            plain(e, ns["hasEconomicGrowth"], d.num(growth[i]))
            if i % 5 == 0:
                plain(e, ns["hasInflation"], d.num(infl[i]))
        elif kind == 1:  # "town": population count
            plain(e, ns["hasNumberOfPeople"], d.num(people[i]))
        else:  # both flavors
            plain(e, ns["hasPopulationDensity"], d.num(popul[i]))
            plain(e, ns["hasNumberOfPeople"], d.num(people[i]))
        if i % 4 == 0:
            plain(e, ns["hasNeighbor"], places[max(0, i - 1)])
        if i % 6 == 0:
            plain(d.term(f"conn{i}"), ns["isConnectedTo"], e)

    # reified event/person facts for the complex queries
    n_ev = n_places // 3
    conf = np.clip(rng.exponential(0.3, size=n_ev), 0.0, 1.0)
    for i in range(n_ev):
        ev = d.term(f"event{i}")
        target = places[int(rng.integers(0, n_places))]
        r = fact(ev, ns["happenedIn"], target)
        plain(r, ns["hasConfidence"], d.num(conf[i]))
        person = d.term(f"person{i}")
        r2 = fact(person, ns["wasBornIn"],
                  places[int(rng.integers(0, n_places))])
        plain(r2, ns["hasConfidence"], d.num(1.0 - conf[i]))

    ent = np.asarray(places, dtype=np.int64)
    return RawData(
        quads=np.array(quads, dtype=np.int64), terms=d.terms,
        numeric=d.numeric, next_id=d.next_id,
        geometry_predicate=ns["hasGeometry"], geom_entities=ent,
        geom_boxes=np.concatenate([pts, pts], axis=1),
        geom_offsets=np.arange(n_places + 1), geom_points=pts, exact=True)


def _star(t: dict, extra_a: tuple, extra_b: tuple) -> tuple:
    pats = [
        [None, "?place", t["hasPopulationDensity"], "?popul"],
        [None, "?place", t["hasGeometry"], "?g1"],
        [None, "?place", t["isLocatedIn"], "?loc1"],
        [None, "?nplace", t["hasNumberOfPeople"], "?popul1"],
        [None, "?nplace", t["hasGeometry"], "?g2"],
        [None, "?nplace", t["isLocatedIn"], "?loc2"],
    ]
    pats += [[None, "?place", t[p], f"?a_{p}"] for p in extra_a]
    pats += [[None, "?nplace", t[p], f"?b_{p}"] for p in extra_b]
    return pats, [["?popul", 1.0], ["?popul1", 1.0]]


def _reified(t: dict, pred: str) -> tuple:
    pats = [
        ["?r", "?a", t[pred], "?b"],
        [None, "?r", t["hasConfidence"], "?conf"],
        [None, "?b", t["hasGeometry"], "?g1"],
        [None, "?nplace", t["hasNumberOfPeople"], "?popul1"],
        [None, "?nplace", t["hasGeometry"], "?g2"],
        [None, "?nplace", t["isLocatedIn"], "?loc2"],
    ]
    return pats, [["?conf", 1.0], ["?popul1", 1.0]]


TEMPLATES = {
    "Q1": lambda t: _star(t, (), ()),
    "Q2": lambda t: _star(t, ("hasEconomicGrowth",), ()),
    "Q3": lambda t: _star(t, ("hasEconomicGrowth",), ("isLocatedIn",)),
    "Q4": lambda t: _star(t, ("hasEconomicGrowth", "hasNeighbor"), ()),
    "Q5": lambda t: _reified(t, "happenedIn"),
    "Q6": lambda t: _reified(t, "wasBornIn"),
    "Q7": lambda t: _star(t, ("hasNeighbor",), ()),
    "Q8": lambda t: _reified(t, "happenedIn"),
}


def query(data: RawData, spec: dict, k: int) -> dict:
    """Query `spec["template"]` (Q1-Q8) with the tenant's k, at distance
    `spec["dist_frac"]` (default 0.02) of the extent."""
    patterns, rank = TEMPLATES[spec["template"]](data.terms)
    return {"patterns": patterns,
            "spatial": ["?g1", "?g2",
                        EXTENT * float(spec.get("dist_frac", 0.02))],
            "rank": rank, "descending": False, "k": int(k)}
