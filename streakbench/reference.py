"""Plain reference for top-k SPARQL queries with a distance filter.

It evaluates a query straight from a generator's raw data: every pattern
is a mask over the quad table, patterns join with pandas, the distance
filter is a grid join over the geometries' points, and the rows are sorted
by their score. It shares no code with the program and reads nothing the
program built.

Semantics, as the configuration states them:
- a geometry variable ``?g`` stands for the subject ``?x`` of the pattern
  ``?x <geometry predicate> ?g``; the distance of two entities is the least
  euclidean distance between their geometries' points;
- points are float32; the squared distance is summed in float32, one
  rounding per operation, and its square root, taken in float64, is kept
  when it is at most the query's distance;
- a row's score is the weighted sum of its ranking variables' numeric
  values, in float64; a row with a non-numeric ranking value has no score
  and drops out;
- the answer is the k best-scored rows (bag semantics) over every
  variable but the filter's two geometry variables; rows tied on the k-th
  score may be any of them.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pandas as pd

# candidate point pairs expanded at once by the grid join
PAIR_CHUNK = 1 << 22


@dataclasses.dataclass
class Ranked:
    """A query's answers in rank order: every row at least as good as the
    `depth`-th, ties at that score included."""
    columns: list            # variable names without "?", sorted
    scores: np.ndarray       # (n,) float64, best first
    rows: np.ndarray         # (n, len(columns)) int64, plain ids


class Reference:
    """The plain reference over one deployment's raw data.

    `coord_dtype` is the precision of the distance arithmetic: float32 as
    the configuration states, or a lower one for the control.
    """

    def __init__(self, raw, coord_dtype=np.float32):
        self.quads = raw.quads
        self.geometry_predicate = raw.geometry_predicate
        ids = np.fromiter(raw.numeric.keys(), np.int64, len(raw.numeric))
        vals = np.fromiter(raw.numeric.values(), np.float64, len(raw.numeric))
        order = np.argsort(ids)
        self.num_ids, self.num_vals = ids[order], vals[order]
        order = np.argsort(raw.geom_entities)
        self.geom_ent = raw.geom_entities[order]
        starts = raw.geom_offsets[:-1][order]
        counts = np.diff(raw.geom_offsets)[order]
        self.geom_start, self.geom_count = starts, counts
        self.points = raw.geom_points.astype(np.float32).astype(coord_dtype)

    # -- patterns ---------------------------------------------------------
    def _pattern(self, pat) -> pd.DataFrame:
        q = self.quads
        mask = np.ones(len(q), dtype=bool)
        cols: dict[str, int] = {}
        for c, term in enumerate(pat):
            if term is None:
                continue
            if isinstance(term, str):
                if term in cols:                     # repeated variable
                    mask &= q[:, c] == q[:, cols[term]]
                else:
                    cols[term] = c
            else:
                mask &= q[:, c] == int(term)
        sel = q[mask]
        return pd.DataFrame({v[1:]: sel[:, c] for v, c in cols.items()})

    def _component(self, tables: list) -> pd.DataFrame:
        """Join tables that are connected through shared variables."""
        out, rest = tables[0], list(tables[1:])
        while rest:
            for i, t in enumerate(rest):
                on = sorted(set(out.columns) & set(t.columns))
                if on:
                    out = out.merge(t, on=on, how="inner")
                    rest.pop(i)
                    break
            else:
                raise ValueError("pattern component is not connected")
        return out

    def _values(self, col: np.ndarray) -> np.ndarray:
        pos = np.clip(np.searchsorted(self.num_ids, col), 0,
                      len(self.num_ids) - 1)
        hit = self.num_ids[pos] == col
        return np.where(hit, self.num_vals[pos], np.nan)

    # -- distance ---------------------------------------------------------
    def _points_of(self, ents: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(points, owner index into `ents`) of the entities' geometries."""
        pos = np.searchsorted(self.geom_ent, ents)
        pos = np.clip(pos, 0, len(self.geom_ent) - 1)
        ok = self.geom_ent[pos] == ents
        ents_i = np.flatnonzero(ok)
        cnt = self.geom_count[pos[ents_i]]
        owner = np.repeat(ents_i, cnt)
        first = np.repeat(self.geom_start[pos[ents_i]], cnt)
        local = np.arange(len(owner)) - np.repeat(np.cumsum(cnt) - cnt, cnt)
        return self.points[first + local], owner

    def within(self, ea: np.ndarray, eb: np.ndarray, dist: float
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Unique entity pairs (ea[i], eb[j]) whose distance is <= dist,
        and that distance."""
        pa, oa = self._points_of(ea)
        pb, ob = self._points_of(eb)
        cell = max(float(dist), 1e-9) * 1.01
        ca = np.floor(pa.astype(np.float64) / cell).astype(np.int64)
        cb = np.floor(pb.astype(np.float64) / cell).astype(np.int64)
        width = int(max(ca[:, 1].max(initial=0), cb[:, 1].max(initial=0))) + 3
        kb = (cb[:, 0] + 1) * width + (cb[:, 1] + 1)
        order = np.argsort(kb, kind="stable")
        kb_s = kb[order]
        found_i, found_j, found_d = [], [], []
        for dx in (-1, 0, 1):
            for dy in (-1, 0, 1):
                ka = (ca[:, 0] + 1 + dx) * width + (ca[:, 1] + 1 + dy)
                lo = np.searchsorted(kb_s, ka, "left")
                hi = np.searchsorted(kb_s, ka, "right")
                cnt = hi - lo
                total = np.cumsum(cnt)
                start = 0
                while start < len(ka):
                    base = total[start - 1] if start else 0
                    stop = int(np.searchsorted(total, base + PAIR_CHUNK,
                                               "right"))
                    stop = max(stop, start + 1)
                    c = cnt[start:stop]
                    ia = np.repeat(np.arange(start, stop), c)
                    off = np.arange(len(ia)) - np.repeat(np.cumsum(c) - c, c)
                    ib = order[np.repeat(lo[start:stop], c) + off]
                    d = pa[ia] - pb[ib]
                    core = d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]
                    dd = np.sqrt(core.astype(np.float64))
                    keep = dd <= float(dist)
                    found_i.append(oa[ia[keep]])
                    found_j.append(ob[ib[keep]])
                    found_d.append(dd[keep])
                    start = stop
        if not found_i:
            return np.empty(0, np.int64), np.empty(0, np.int64), np.empty(0)
        i, j, d = (np.concatenate(f) for f in (found_i, found_j, found_d))
        # an entity pair's distance is the least over its points' pairs
        order = np.lexsort((d, j, i))
        i, j, d = i[order], j[order], d[order]
        first = np.ones(len(i), dtype=bool)
        first[1:] = (i[1:] != i[:-1]) | (j[1:] != j[:-1])
        return ea[i[first]], eb[j[first]], d[first]

    # -- a query ----------------------------------------------------------
    def rank(self, q: dict, depth: int) -> Ranked:
        """Every answer of `q` at least as good as its `depth`-th best."""
        return self.rank_many([q], depth)[0]

    def rank_many(self, qs: list, depth: int) -> list:
        """`rank` of each query; queries that differ in their distance
        alone share one join at the largest of their distances."""
        groups: dict = {}
        for i, q in enumerate(qs):
            key = repr({**q, "spatial": q["spatial"][:2]})
            groups.setdefault(key, []).append(i)
        out = [None] * len(qs)
        for members in groups.values():
            far = max(members, key=lambda i: float(qs[i]["spatial"][2]))
            rows = self._rows(qs[far])
            for i in members:
                dist = float(qs[i]["spatial"][2])
                out[i] = self._top(rows[rows["_d"].to_numpy() <= dist],
                                   qs[i], depth)
        return out

    def _rows(self, q: dict) -> pd.DataFrame:
        """Every row of `q` within its distance, the entities' distance in
        column ``_d``, before ranking."""
        tables = [self._pattern(p) for p in q["patterns"]]
        ga, gb, dist = q["spatial"]
        ent_a = ent_b = None
        for p in q["patterns"]:
            if p[2] == self.geometry_predicate and p[3] in (ga, gb):
                if p[3] == ga:
                    ent_a = p[1][1:]
                else:
                    ent_b = p[1][1:]
        # connected components of the patterns through shared variables
        comps: list[list] = []
        for t in tables:
            touch = [any(set(t.columns) & set(u.columns) for u in c)
                     for c in comps]
            merged = [t] + [u for c, h in zip(comps, touch) if h for u in c]
            comps = [c for c, h in zip(comps, touch) if not h] + [merged]
        joined = [self._component(c) for c in comps]
        ia = next(i for i, j in enumerate(joined) if ent_a in j.columns)
        ib = next(i for i, j in enumerate(joined) if ent_b in j.columns)
        if ia == ib:
            t = joined[ia]
            pa, pb, d = self.within(np.unique(t[ent_a].to_numpy()),
                                    np.unique(t[ent_b].to_numpy()), dist)
            ok = pd.DataFrame({ent_a: pa, ent_b: pb, "_d": d})
            rows = t.merge(ok, on=[ent_a, ent_b], how="inner")
        else:
            a, b = joined[ia], joined[ib]
            pa, pb, d = self.within(np.unique(a[ent_a].to_numpy()),
                                    np.unique(b[ent_b].to_numpy()), dist)
            pairs = pd.DataFrame({ent_a: pa, ent_b: pb, "_d": d})
            rows = a.merge(pairs, on=ent_a).merge(b, on=ent_b)
        for i, t in enumerate(joined):             # components off the filter
            if i not in (ia, ib):
                rows = rows.merge(t, how="cross")
        return rows

    def _top(self, rows: pd.DataFrame, q: dict, depth: int) -> Ranked:
        ga, gb, _ = q["spatial"]
        score = np.zeros(len(rows))
        for var, w in q["rank"]:
            score = score + float(w) * self._values(rows[var[1:]].to_numpy())
        ok = ~np.isnan(score)
        score = score[ok]
        # the filter's geometry variables are consumed by the filter
        columns = sorted(c for c in rows.columns
                         if c not in (ga[1:], gb[1:], "_d"))
        table = rows[columns].to_numpy(dtype=np.int64)[ok]
        key = -score if q["descending"] else score
        order = np.argsort(key, kind="stable")
        if len(order) > depth > 0:
            worst = key[order[depth - 1]]
            order = order[key[order] <= worst]
        return Ranked(columns, score[order], table[order])
