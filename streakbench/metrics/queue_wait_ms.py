"""Mean time from a request's due time to the start of the first step
whose slots hold it, over the requests due in the window and admitted."""


def read(rec):
    waits = [r.admitted - r.due for r in rec.requests if r.admitted is not None]
    return 1000.0 * sum(waits) / len(waits) if waits else None
