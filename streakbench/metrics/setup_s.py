"""Process start to the first request of the window: data, store build,
engine, compile-cache loads and warm-up."""


def read(rec):
    return rec.setup["setup_s"]
