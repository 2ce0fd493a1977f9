"""Vertex pairs the exact refinement's kernel computed per request
answered in the window, padding included (each launch's tile area,
pairs x m_pad x n_pad): the program's counter (`refine_vertex_pairs`), as
each ``streak.step`` span of the traced window carries it. A program
without the counter reads nothing."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    done = [r for r in rec.answered() if r.finished <= rec.t1]
    if sp is None or "refine_vertex_pairs" not in sp.counters or not done:
        return None
    return sp.counters["refine_vertex_pairs"] / len(done)
