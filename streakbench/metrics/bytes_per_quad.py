"""Bytes of every numpy and jax array reachable from the built store,
each buffer counted once, per quad."""


def read(rec):
    return rec.store_bytes / rec.n_quads
