"""Self time of the program's ``streak.refine`` spans per engine step in
the traced window, ms: exact-geometry refinement."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    return sp.per_step_ms("streak.refine") if sp is not None else None
