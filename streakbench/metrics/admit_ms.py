"""Self time of the program's ``streak.admit`` spans per engine step in the
traced window, ms: cursor construction: the plan, the Bloom preparation
and the root-path masks."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    return sp.per_step_ms("streak.admit") if sp is not None else None
