"""Self time of the program's ``streak.topk`` spans per engine step in the
traced window, ms: result assembly and the top-k merge."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    return sp.per_step_ms("streak.topk") if sp is not None else None
