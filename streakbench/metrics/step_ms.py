"""Mean host time of one `SpatialServeEngine.step()` started in the window."""


def read(rec):
    steps = rec.window_steps()
    if not steps:
        return None
    return 1000.0 * sum(e - s for s, e in steps) / len(steps)
