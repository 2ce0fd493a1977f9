"""LinkedGeoData with its OSM ways: the STREAK paper's LGD top-k distance
join over points, building rings, highways and waterways.

The layout is `lgd_scale`'s: entities with a reified type fact carrying an
exponential confidence quantized to `n_conf_bins` literals, attribute
quads, about 4.5 quads per entity, anchored in 64 Gaussian clusters; the
query template is `lgd_scale.query`. The geometries are exact vertex
lists, as LinkedGeoData publishes OSM nodes (POINT) and ways (LINESTRING,
POLYGON):

- ``class:poi``: one vertex;
- ``class:building``: a closed ring of 5-12 vertices around its anchor,
  the first vertex repeated last, of lognormal radius;
- ``class:highway`` and ``class:waterway``: heading-persistent random
  walks from their anchor, of lognormal vertex counts and step lengths.

An entity's box is its vertices' MBR. Distance is the least distance
between two geometries' vertices. Every number is the configuration's
(`assumed` in ``configs/lgd_ways_1m.json``).
"""
from __future__ import annotations

import numpy as np

from .common import RawData
from .lgd_scale import EXTENT, query  # noqa: F401  (the query template)

TERMS = ("rdf:type", "hasGeometry", "hasConfidence", "attr1", "attr2",
         "class:poi", "class:building", "class:highway", "class:waterway")
# class -> its share of the entities, out of 20
SHARES = (("class:poi", 8), ("class:building", 6), ("class:highway", 5),
          ("class:waterway", 1))
# where each class's cluster centers lie on the extent: points and
# buildings as lgd_scale's pois, ways as its sites
RANGES = {"class:poi": (0.0, 62.0), "class:building": (0.0, 62.0),
          "class:highway": (48.0, 100.0), "class:waterway": (48.0, 100.0)}
N_CLUSTERS = 64
CLUSTER_SIGMA = 0.02 * EXTENT
RING_VERTICES = (5, 12)
RING_RADIUS = (0.02, 0.5)             # lognormal median, sigma
WAY_VERTICES = {"class:highway": (12.0, 0.9, 2, 2000),
                "class:waterway": (80.0, 1.0, 2, 8000)}   # median, sigma, clip
STEP = (0.05, 0.5)                    # lognormal median, sigma
TURN_SIGMA = 0.3                      # heading change per step, radians


def _local(counts: np.ndarray) -> np.ndarray:
    """0..c-1 for each count c, concatenated."""
    return np.arange(int(counts.sum())) - np.repeat(np.cumsum(counts) - counts,
                                                    counts)


def _rings(rng, centers: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(vertex counts, concatenated vertices) of one closed ring per
    center, its first vertex repeated last."""
    n = len(centers)
    nv = rng.integers(RING_VERTICES[0], RING_VERTICES[1] + 1, n)
    r = rng.lognormal(np.log(RING_RADIUS[0]), RING_RADIUS[1], n)
    owner = np.repeat(np.arange(n), nv)
    ang = rng.uniform(0.0, 2.0 * np.pi, len(owner))
    ang = ang[np.lexsort((ang, owner))]
    ring = centers[owner] + r[owner, None] * np.stack(
        [np.cos(ang), np.sin(ang)], axis=1)
    # each ring's vertices, then its first again
    local = _local(nv + 1)
    src = np.repeat(np.cumsum(nv) - nv, nv + 1) + local % np.repeat(nv,
                                                                    nv + 1)
    return nv + 1, ring[src]


def _walks(rng, anchors: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenated vertices of one heading-persistent walk per anchor."""
    total = int(counts.sum())
    start = np.cumsum(counts) - counts
    owner = np.repeat(np.arange(len(counts)), counts)
    first = np.zeros(total, dtype=bool)
    first[start] = True
    turn = rng.normal(0.0, TURN_SIGMA, total)
    turn[first] = rng.uniform(0.0, 2.0 * np.pi, len(counts))
    c = np.cumsum(turn)
    heading = c - np.repeat(c[start] - turn[start], counts)
    step = rng.lognormal(np.log(STEP[0]), STEP[1], total)
    step[first] = 0.0
    d = step[:, None] * np.stack([np.cos(heading), np.sin(heading)], axis=1)
    c = np.cumsum(d, axis=0)
    walk = c - np.repeat(c[start] - d[start], counts, axis=0)
    return anchors[owner] + walk


def generate(cfg: dict, seed: int) -> RawData:
    """Quads, terms, literals and exact geometries of one deployment, from
    `seed`."""
    n_quads = int(cfg["n_quads"])
    n_conf_bins = int(cfg["n_conf_bins"])
    rng = np.random.default_rng(seed)
    terms: dict[str, int] = {}
    numeric: dict[int, float] = {}
    next_id = 1
    for t in TERMS:
        terms[t] = next_id
        next_id += 1
    grid = np.round(np.linspace(0.0, 1.0, n_conf_bins), 6)
    conf_ids = np.empty(n_conf_bins, dtype=np.int64)
    for i, v in enumerate(grid):
        t = repr(float(v))
        if t not in terms:
            terms[t] = next_id
            numeric[next_id] = float(t)
            next_id += 1
        conf_ids[i] = terms[t]

    n_ent = max(int(n_quads / 4.5), 20)
    e0 = 1 << 20                       # entities
    f0 = e0 + n_ent                    # reified type-fact ids
    g0 = f0 + n_ent                    # geometry objects
    a0 = g0 + n_ent                    # attribute object pool
    n_pool = 1 << 16
    ent = e0 + np.arange(n_ent, dtype=np.int64)
    fact = f0 + np.arange(n_ent, dtype=np.int64)
    geo = g0 + np.arange(n_ent, dtype=np.int64)

    # classes interleaved by their shares of every 20 entities
    of20 = np.concatenate([np.full(s, i) for i, (_, s) in enumerate(SHARES)])
    kind = of20[np.arange(n_ent) % len(of20)]
    names = [c for c, _ in SHARES]
    cls = np.array([terms[c] for c in names])[kind]
    lo = np.array([RANGES[c][0] for c in names])[kind]
    hi = np.array([RANGES[c][1] for c in names])[kind]
    centers = rng.uniform(0.0, 1.0, size=(N_CLUSTERS, 2))
    which = rng.integers(0, N_CLUSTERS, size=n_ent)
    anchor = centers[which] * (hi - lo)[:, None] + lo[:, None] \
        + rng.normal(0, CLUSTER_SIGMA, size=(n_ent, 2))
    anchor = np.clip(anchor, 0.0, EXTENT)

    counts = np.ones(n_ent, dtype=np.int64)          # pois: one vertex
    parts = {0: anchor[kind == 0]}
    b = np.flatnonzero(kind == 1)
    counts[b], parts[1] = _rings(rng, anchor[b])
    for k, name in enumerate(names[2:], start=2):
        w = np.flatnonzero(kind == k)
        med, sig, cmin, cmax = WAY_VERTICES[name]
        counts[w] = np.clip(np.rint(rng.lognormal(np.log(med), sig, len(w))),
                            cmin, cmax).astype(np.int64)
        parts[k] = _walks(rng, anchor[w], counts[w])
    offsets = np.concatenate([[0], np.cumsum(counts)])
    points = np.empty((int(offsets[-1]), 2))
    for k, pts in parts.items():
        e = np.flatnonzero(kind == k)
        points[np.repeat(offsets[e], counts[e]) + _local(counts[e])] = pts
    points = np.clip(points, 0.0, EXTENT)
    boxes = np.stack([np.minimum.reduceat(points[:, 0], offsets[:-1]),
                      np.minimum.reduceat(points[:, 1], offsets[:-1]),
                      np.maximum.reduceat(points[:, 0], offsets[:-1]),
                      np.maximum.reduceat(points[:, 1], offsets[:-1])], 1)

    conf_bin = np.minimum((rng.exponential(0.3, size=n_ent) *
                           (n_conf_bins - 1)).astype(np.int64),
                          n_conf_bins - 1)
    conf_obj = conf_ids[conf_bin]
    attr1_obj = a0 + rng.integers(0, n_pool, size=n_ent)
    has_a2 = np.arange(n_ent) % 2 == 0
    attr2_obj = a0 + rng.integers(0, n_pool, size=int(has_a2.sum()))

    zeros = np.zeros(n_ent, dtype=np.int64)
    quads = np.concatenate([
        np.stack([zeros, ent, np.full(n_ent, terms["hasGeometry"]), geo], 1),
        np.stack([fact, ent, np.full(n_ent, terms["rdf:type"]), cls], 1),
        np.stack([zeros, fact, np.full(n_ent, terms["hasConfidence"]),
                  conf_obj], 1),
        np.stack([zeros, ent, np.full(n_ent, terms["attr1"]), attr1_obj], 1),
        np.stack([zeros[has_a2], ent[has_a2],
                  np.full(int(has_a2.sum()), terms["attr2"]),
                  attr2_obj], 1),
    ]).astype(np.int64)
    return RawData(
        quads=quads, terms=terms, numeric=numeric, next_id=a0 + n_pool,
        geometry_predicate=terms["hasGeometry"], geom_entities=ent,
        geom_boxes=boxes, geom_offsets=offsets, geom_points=points,
        exact=True)
