"""Device time of the Pallas kernels over the traced window."""


def read(rec):
    return rec.trace.kernel_pct if rec.trace is not None else None
