"""What a deployment's generator hands over: plain arrays, no program types.

The program builds its store from these; the plain reference reads them
directly. Ids are the generator's own ("plain"); the program later gives
spatial entities ids of its own.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class RawData:
    quads: np.ndarray            # (n, 4) int64 as (g, s, p, o), plain ids
    terms: dict                  # term string -> plain id
    numeric: dict                # plain id -> float, numeric literals
    next_id: int                 # first id the dictionary has not handed out
    geometry_predicate: int
    geom_entities: np.ndarray    # (m,) plain ids of the spatial entities
    geom_boxes: np.ndarray       # (m, 4) world MBRs (xmin, ymin, xmax, ymax)
    geom_offsets: np.ndarray     # (m + 1,) CSR offsets into geom_points
    geom_points: np.ndarray      # (P, 2) float64 points of each geometry
    exact: bool                  # True: the points are ingested geometry

    def geometry(self, i: int) -> np.ndarray:
        return self.geom_points[self.geom_offsets[i]:self.geom_offsets[i + 1]]


def relabel(raw: RawData, seed: int) -> RawData:
    """The same deployment under other ids: every term id in [1, next_id)
    mapped by a permutation drawn from `seed`, and the quads reordered.
    Geometries, literal values and the query templates are untouched, so
    every seed does the same work and has the same answers up to ids."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    new = np.arange(raw.next_id, dtype=np.int64)
    new[1:] = 1 + rng.permutation(raw.next_id - 1)      # 0: default graph
    quads = new[raw.quads][rng.permutation(len(raw.quads))]
    return dataclasses.replace(
        raw, quads=quads,
        terms={t: int(new[i]) for t, i in raw.terms.items()},
        numeric={int(new[i]): v for i, v in raw.numeric.items()},
        geometry_predicate=int(new[raw.geometry_predicate]),
        geom_entities=new[raw.geom_entities])


class Interner:
    """Sequential term ids from 1; a term that parses as a float is a
    numeric literal with that value."""

    def __init__(self):
        self.terms: dict[str, int] = {}
        self.numeric: dict[int, float] = {}
        self.next_id = 1

    def term(self, t: str) -> int:
        i = self.terms.get(t)
        if i is None:
            i = self.next_id
            self.next_id += 1
            self.terms[t] = i
            try:
                self.numeric[i] = float(t)
            except ValueError:
                pass
        return i

    def num(self, v: float) -> int:
        return self.term(repr(float(v)))
