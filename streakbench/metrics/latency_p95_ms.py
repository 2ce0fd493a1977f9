"""95th percentile of the time from a request's due time to its answer on
the host, over every request due in the window; a request that errs,
returns partial results or is still unanswered when the drain ends counts
as infinite."""
from streakbench.record import percentile


def read(rec):
    return 1000.0 * percentile(rec.latencies(), 95) if rec.requests else None
