"""Self time of the program's ``streak.scan`` spans per engine step in the
traced window, ms: relational scans and merge joins, the APS choice
included."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    return sp.per_step_ms("streak.scan") if sp is not None else None
