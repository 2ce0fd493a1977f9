"""Driver blocks the cursor read per answered request (`ExecStats`)."""


def read(rec):
    done = rec.answered()
    if not done:
        return None
    return sum(r.counters["driver_blocks"] for r in done) / len(done)
