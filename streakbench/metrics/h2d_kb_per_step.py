"""Bytes of host arrays the kernel dispatchers handed to the device, kB
(1000 bytes) per engine step of the traced window: the program's
counter, as each ``streak.step`` span carries it."""
from pathlib import Path

from streakbench import spans

ROOT = Path(__file__).resolve().parents[2]


def read(rec):
    sp = spans.of_run(rec, ROOT)
    v = sp.counter_per_step("h2d_bytes") if sp is not None else None
    return v / 1000.0 if v is not None else None
