"""Share-cache hits over lookups in the traced window's engine steps, every
kind of entry together, %: the program's counters, as each
``streak.step`` span carries them."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    if sp is None or not sp.counters.get("share_lookups"):
        return None
    return 100.0 * sp.counters["share_hits"] / sp.counters["share_lookups"]
