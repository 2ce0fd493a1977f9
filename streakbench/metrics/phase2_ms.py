"""Self time of the program's ``streak.phase2`` spans per engine step in
the traced window, ms: Phase 2: the V* node selection."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    return sp.per_step_ms("streak.phase2") if sp is not None else None
