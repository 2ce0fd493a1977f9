"""Phase 3 MBR candidate pairs per answered request
(`ExecStats.join.candidates`)."""


def read(rec):
    done = rec.answered()
    if not done:
        return None
    return sum(r.counters["mbr_pairs"] for r in done) / len(done)
