"""Requests answered inside the window, per second of the window."""


def read(rec):
    done = [r for r in rec.answered() if r.finished <= rec.t1]
    return len(done) / rec.seconds
