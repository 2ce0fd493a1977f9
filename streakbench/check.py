"""Whether a served answer is the reference's top-k answer.

Scores must equal the reference's k best, in order and bit for bit. Each
row must be a reference row with the same score; rows tied on the k-th
score may be any of the tied reference rows, every better row must be
there.
"""
from __future__ import annotations

import collections

import numpy as np


def same_answer(scores: np.ndarray, rows: dict, ref, k: int) -> str | None:
    """None when (scores, rows) is a correct top-k answer against `ref`
    (a `reference.Ranked` at depth >= k), else what differs."""
    n = min(int(k), len(ref.scores))
    if len(scores) != n:
        return f"{len(scores)} rows, want {n}"
    if not np.array_equal(scores, ref.scores[:n]):
        bad = int(np.flatnonzero(scores != ref.scores[:n])[0])
        return f"score {bad} is {scores[bad]!r}, want {ref.scores[bad]!r}"
    if n == 0:                  # an empty answer has no columns to compare
        return None
    if sorted(rows) != ref.columns:
        return f"columns {sorted(rows)}, want {ref.columns}"
    worst = ref.scores[n - 1]
    depth = len(ref.scores)
    # every reference row as good as the k-th (the ties included)
    last = np.flatnonzero(ref.scores == worst)[-1] + 1 if depth else 0
    want = collections.Counter(
        (float(s), tuple(r)) for s, r in zip(ref.scores[:last],
                                             ref.rows[:last].tolist()))
    got = np.stack([np.asarray(rows[c], dtype=np.int64)
                    for c in ref.columns], 1)
    for s, r in zip(scores.tolist(), got.tolist()):
        key = (float(s), tuple(r))
        if want[key] <= 0:
            return f"row {r} at score {s!r} is not a reference answer"
        want[key] -= 1
    return None
