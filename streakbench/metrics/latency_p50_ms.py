"""Median time from a request's due time to its answer on the host, over
every request due in the window (unanswered ones count as infinite)."""
from streakbench.record import percentile


def read(rec):
    return 1000.0 * percentile(rec.latencies(), 50) if rec.requests else None
