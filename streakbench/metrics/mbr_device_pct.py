"""Share of the Phase 3 MBR join's block-product pairs that the device
tested in the traced window's engine steps, %: the program's counters
(`mbr_pairs`, `mbr_device_pairs`), as each ``streak.step`` span carries
them. A program without those counters reads nothing."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    if sp is None or not sp.counters.get("mbr_pairs"):
        return None
    return 100.0 * sp.counters.get("mbr_device_pairs", 0) / sp.counters[
        "mbr_pairs"]
