"""The one module of the benchmark that calls the program under test.

It builds the store from a generator's raw data, turns the benchmark's
plain query descriptions into the program's `Query`, constructs the
serving engine with the program's default `ExecConfig()`, reads the
program's counters, and maps the ids in served rows back to plain ids.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from repro.core import fault  # noqa: E402
from repro.core.dictionary import Dictionary  # noqa: E402
from repro.core.executor import ExecConfig  # noqa: E402
from repro.core.query import (Query, Ranking, SpatialFilter,  # noqa: E402
                              TriplePattern, Var)
from repro.core.store import build_store  # noqa: E402
from repro.serve.spatial import SpatialRequest, SpatialServeEngine  # noqa: E402


def build(raw, cfg: dict):
    """The program's store over `raw`, with the configuration's index
    settings."""
    d = Dictionary(dict(raw.terms), {i: t for t, i in raw.terms.items()},
                   dict(raw.numeric), raw.next_id)
    ents = raw.geom_entities.tolist()
    geometries = dict(zip(ents, raw.geom_boxes))
    exact = None
    if raw.exact:
        exact = {e: raw.geometry(i) for i, e in enumerate(ents)}
    return build_store(raw.quads.copy(), d,
                       geometry_predicate=raw.geometry_predicate,
                       geometries=geometries, exact_geoms=exact,
                       l_max=int(cfg["l_max"]),
                       leaf_capacity=int(cfg["leaf_capacity"]),
                       block=int(cfg["block"]))


def _term(t):
    if t is None:
        return None
    return Var(t[1:]) if isinstance(t, str) else int(t)


def to_query(q: dict) -> Query:
    pats = tuple(TriplePattern(_term(s), _term(p), _term(o), g=_term(g))
                 for g, s, p, o in q["patterns"])
    ga, gb, dist = q["spatial"]
    return Query(
        select=(), patterns=pats,
        spatial=SpatialFilter(Var(ga[1:]), Var(gb[1:]), float(dist)),
        ranking=Ranking(tuple((Var(v[1:]), float(w)) for v, w in q["rank"]),
                        descending=bool(q["descending"])),
        k=int(q["k"]))


def engine(store, cfg: dict) -> SpatialServeEngine:
    """The serving engine as users run it: default backends and slots."""
    return SpatialServeEngine(store, ExecConfig(),
                              max_slots=int(cfg["max_slots"]))


def request_state(req) -> str:
    """"ok", "error", "partial" or "pending"."""
    if not req.done:
        return "pending"
    if req.error is not None:
        return "error"
    if req.stats is not None and req.stats.partial:
        return "partial"
    return "ok"


def request_counters(req) -> dict:
    st = req.stats
    return {"driver_blocks": int(st.driver_blocks),
            "mbr_pairs": int(st.join.candidates)}


def reset_fault_counters() -> None:
    fault.STATE.reset()


def fault_counters(eng) -> dict:
    """Kernel failures, fallbacks and demotions, serve faults, and the
    launches per (op, route)."""
    fs, ss = fault.STATE.stats, eng.stats
    return {
        "kernel_failures": int(fs.failures),
        "kernel_fallbacks": int(fs.fallbacks),
        "policy_demotions": int(fs.policy_demotions),
        "serve_faults": int(ss.faults + ss.pooled_fallbacks
                            + ss.admission_failures),
        "launches": {f"{op}/{b}": int(n)
                     for (op, b), n in sorted(fs.calls.items())},
    }


@dataclasses.dataclass
class IdMap:
    """The program's spatial ids back to the generator's plain ids."""
    spatial: np.ndarray   # sorted
    plain: np.ndarray

    @classmethod
    def of(cls, store) -> "IdMap":
        m = store.tree.entity_to_id
        plain = np.fromiter(m.keys(), np.int64, len(m))
        spatial = np.fromiter(m.values(), np.int64, len(m))
        order = np.argsort(spatial)
        return cls(spatial[order], plain[order])

    def __call__(self, col: np.ndarray) -> np.ndarray:
        col = np.asarray(col, dtype=np.int64)
        pos = np.clip(np.searchsorted(self.spatial, col), 0,
                      len(self.spatial) - 1)
        hit = self.spatial[pos] == col
        return np.where(hit, self.plain[pos], col)


def answer(req, idmap: IdMap) -> tuple[np.ndarray, dict]:
    """(scores, {column: plain ids}) of a finished request."""
    rows = {c: idmap(v) for c, v in req.rows.items()}
    return np.asarray(req.scores, dtype=np.float64), rows
