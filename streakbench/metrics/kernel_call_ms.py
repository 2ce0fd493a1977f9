"""Self time of the program's ``streak.kernel`` spans per engine step in
the traced window, ms: kernel dispatch, from entry to the host result
(padding, key split, upload, launch, fetch)."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    return sp.per_step_ms("streak.kernel") if sp is not None else None
