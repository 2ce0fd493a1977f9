"""LinkedGeoData-shaped deployment: the STREAK paper's LGD top-k distance join.

A copy of the repository's bulk LGD generator (`make_scale`), kept here so
that a change to the program cannot move the yardstick. Two localized
spatial classes (poi in [0, 62], site in [48, 100] on a 100-unit extent),
64 Gaussian clusters, lognormal box MBRs, reified type facts carrying an
exponential confidence quantized to `n_conf_bins` literals, and attribute
quads, about 4.5 quads per entity. The query template is the paper's

    SELECT ?place ?nplace WHERE {
      GRAPH ?r { ?place ?typePred1 <a> } . ?r hasConfidence ?conf .
      ?place hasGeometry ?g1 .
      GRAPH ?r1 { ?nplace ?typePred2 <b> } . ?r1 hasConfidence ?conf1 .
      ?nplace hasGeometry ?g2 .
      FILTER(distance(?g1, ?g2) <= d) }
    ORDER BY ASC(?conf + ?conf1) LIMIT k

Entities carry no exact geometry, so an entity's geometry is the two
corners of its box, as the store keeps it.
"""
from __future__ import annotations

import numpy as np

from .common import RawData

EXTENT = 100.0
TERMS = ("rdf:type", "hasGeometry", "hasConfidence", "attr1", "attr2",
         "class:poi", "class:site")


def generate(cfg: dict, seed: int) -> RawData:
    """Quads, terms, literals and geometries of one deployment, from `seed`."""
    n_quads = int(cfg["n_quads"])
    n_conf_bins = int(cfg["n_conf_bins"])
    rng = np.random.default_rng(seed)
    terms: dict[str, int] = {}
    numeric: dict[int, float] = {}
    next_id = 1
    for t in TERMS:
        terms[t] = next_id
        next_id += 1
    # quantized confidence literals, interned as repr(float) like any literal
    grid = np.round(np.linspace(0.0, 1.0, n_conf_bins), 6)
    conf_ids = np.empty(n_conf_bins, dtype=np.int64)
    for i, v in enumerate(grid):
        t = repr(float(v))
        if t not in terms:
            terms[t] = next_id
            numeric[next_id] = float(t)
            next_id += 1
        conf_ids[i] = terms[t]

    n_ent = max(int(n_quads / 4.5), 2)
    e0 = 1 << 20                       # entities
    f0 = e0 + n_ent                    # reified type-fact ids
    g0 = f0 + n_ent                    # geometry objects
    a0 = g0 + n_ent                    # attribute object pool
    n_pool = 1 << 16

    ent = e0 + np.arange(n_ent, dtype=np.int64)
    fact = f0 + np.arange(n_ent, dtype=np.int64)
    geo = g0 + np.arange(n_ent, dtype=np.int64)

    is_site = np.arange(n_ent) % 2 == 1
    cls = np.where(is_site, terms["class:site"], terms["class:poi"])
    n_cl = 64
    lo = np.where(is_site, 48.0, 0.0)
    hi = np.where(is_site, 100.0, 62.0)
    centers = rng.uniform(0.0, 1.0, size=(n_cl, 2))
    which = rng.integers(0, n_cl, size=n_ent)
    pts = centers[which] * (hi - lo)[:, None] + lo[:, None] \
        + rng.normal(0, EXTENT * 0.02, size=(n_ent, 2))
    pts = np.clip(pts, 0.0, EXTENT)
    half = rng.lognormal(np.log(EXTENT * 0.002), 0.6, size=(n_ent, 2))
    boxes = np.concatenate([np.clip(pts - half, 0, EXTENT),
                            np.clip(pts + half, 0, EXTENT)], axis=1)

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

    # an entity's geometry: its box's two corners
    corners = np.stack([boxes[:, :2], boxes[:, 2:]], axis=1)   # (n, 2, 2)
    return RawData(
        quads=quads, terms=terms, numeric=numeric, next_id=a0 + n_pool,
        geometry_predicate=terms["hasGeometry"], geom_entities=ent,
        geom_boxes=boxes, geom_offsets=np.arange(n_ent + 1) * 2,
        geom_points=corners.reshape(-1, 2), exact=False)


def query(data: RawData, spec: dict, k: int) -> dict:
    """The LGD pair query for `spec` = {"a": class, "b": class,
    "dist_frac": distance as a share of the extent}."""
    t = data.terms
    return {
        "patterns": [
            ["?r", "?place", "?typePred1", t[spec["a"]]],
            [None, "?r", t["hasConfidence"], "?conf"],
            [None, "?place", t["hasGeometry"], "?g1"],
            ["?r1", "?nplace", "?typePred2", t[spec["b"]]],
            [None, "?r1", t["hasConfidence"], "?conf1"],
            [None, "?nplace", t["hasGeometry"], "?g2"],
        ],
        "spatial": ["?g1", "?g2", EXTENT * float(spec["dist_frac"])],
        "rank": [["?conf", 1.0], ["?conf1", 1.0]],
        "descending": False,
        "k": int(k),
    }
