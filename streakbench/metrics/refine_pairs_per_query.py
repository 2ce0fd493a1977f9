"""Geometry pairs the exact refinement computed per request answered in
the window: the program's counter (`refine_pairs`), as each
``streak.step`` span of the traced window carries it. Pairs whose verdict
came from the share cache are not computed and not counted. A program
without the counter reads nothing."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    done = [r for r in rec.answered() if r.finished <= rec.t1]
    if sp is None or "refine_pairs" not in sp.counters or not done:
        return None
    return sp.counters["refine_pairs"] / len(done)
