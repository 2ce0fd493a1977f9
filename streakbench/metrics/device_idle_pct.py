"""Share of the traced window in which no operation ran on the device."""


def read(rec):
    return rec.trace.idle_pct if rec.trace is not None else None
