"""Exact refinement over many-vertex ways, and the bounded program families
of the refinement and of the descent and rank passes.

`pool_min_dist` sends each (m_pad, n_pad) size class to the device in
tiles of a fixed number of pairs (`spatial_join.refine_tile`), so it makes
at most one program per class whatever the data; `merge_join_ranks` and
`tree_descend` slice their padded results on the host, so they make one
program per power-of-two class and not one per probe or block count. The
programs are counted as JAX reports them (`jax.monitoring`).
"""
import jax.monitoring
import numpy as np
import pytest

from repro.core import spatial_join
from repro.core.store import GeomPool
from repro.kernels import ops

CLASSES = [1, 2, 4, 8, 16, 32, 64, 128]
_COMPILES: list | None = None


def _on_compile(name, secs, **kw):
    if _COMPILES is not None and \
            name == "/jax/core/compile/backend_compile_duration":
        _COMPILES.append(kw.get("fun_name", ""))


jax.monitoring.register_event_duration_secs_listener(_on_compile)


class _Programs:
    """Names of the programs made inside the block."""

    def __enter__(self):
        global _COMPILES
        _COMPILES = self.made = []
        return self

    def __exit__(self, *exc):
        global _COMPILES
        _COMPILES = None


def _way(rng, n: int) -> np.ndarray:
    """A heading-persistent walk of n vertices."""
    heading = rng.uniform(0, 2 * np.pi) + np.cumsum(rng.normal(0, 0.3, n))
    step = rng.lognormal(np.log(0.5), 0.5, n)
    step[0] = 0.0
    d = step[:, None] * np.stack([np.cos(heading), np.sin(heading)], 1)
    return rng.uniform(20, 80, 2) + np.cumsum(d, axis=0)


def _ways_pool(seed: int) -> tuple[GeomPool, list]:
    """Ways of every size class (exact powers of two and one past each)
    and ways past 128 vertices, which refine in fragments."""
    rng = np.random.default_rng(seed)
    sizes = sorted({c for c in CLASSES} | {c + 1 for c in CLASSES[:-1]}
                   | {3, 100, 129, 300, 1000})
    return GeomPool.from_lists([_way(rng, n) for n in sizes]), sizes


def _slices(pool: GeomPool, rows: np.ndarray) -> list:
    off = pool.offsets
    return [np.asarray(pool.points[off[r]:off[r + 1]], dtype=np.float64)
            for r in rows]


def _size_class_rows(sizes: list, c: int) -> np.ndarray:
    """Pool rows whose whole geometry falls in size class c."""
    return np.array([i for i, n in enumerate(sizes)
                     if n <= spatial_join.REFINE_MAX_PTS
                     and 1 << (n - 1).bit_length() == c], dtype=np.int64)


@pytest.mark.parametrize("m_pad", CLASSES)
def test_every_size_class_agrees_with_the_looped_oracle(m_pad):
    """Pairs of class (m_pad, n_pad) for every n_pad, and against ways
    past 128 vertices, whose fragments fall in several classes: distances
    as the float64 loop's, keep masks identical."""
    pool, sizes = _ways_pool(seed=m_pad)
    ra = _size_class_rows(sizes, m_pad)
    rb = np.arange(len(sizes), dtype=np.int64)
    ra, rb = np.repeat(ra, len(rb)), np.tile(rb, len(ra))
    ra, rb = np.concatenate([ra, rb]), np.concatenate([rb, ra])
    got = spatial_join.pool_min_dist(pool, ra, rb)
    want = spatial_join.exact_pair_distance_looped(
        _slices(pool, ra), _slices(pool, rb))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    pairs = np.arange(len(ra))
    for dist in np.quantile(want, [0.1, 0.5, 0.9]):
        dist = float(dist) * (1 + 1e-3)
        if np.min(np.abs(want - dist)) < 1e-4 * (1 + dist):
            continue
        keep = spatial_join.refine(pairs, pairs, pool, ra, rb, dist)
        np.testing.assert_array_equal(keep, spatial_join.refine_looped(
            pairs, pairs, _slices(pool, ra), _slices(pool, rb), dist))


def test_a_class_past_its_tile_spans_launches():
    """More pairs of one class than its tile holds go in several launches,
    the last filled by replaying a pair."""
    rng = np.random.default_rng(9)
    pool = GeomPool.from_lists([rng.uniform(0, 100, (n, 2))
                                for n in [5, 7, 6, 8]])
    t = spatial_join.refine_tile(8, 8)
    ra = rng.integers(0, 4, 2 * t + 3).astype(np.int64)
    rb = rng.integers(0, 4, 2 * t + 3).astype(np.int64)
    before = spatial_join.REFINE.launches
    got = spatial_join.pool_min_dist(pool, ra, rb)
    assert spatial_join.REFINE.launches - before == 3
    want = spatial_join.exact_pair_distance_looped(
        _slices(pool, ra), _slices(pool, rb))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_refine_tiles_are_fixed_powers_of_two():
    for m in CLASSES:
        for n in CLASSES:
            t = spatial_join.refine_tile(m, n)
            assert t & (t - 1) == 0 and 256 <= t <= 4096
            assert t == spatial_join.refine_tile(n, m)


def test_counters_count_pairs_fragments_vertex_pairs_and_launches():
    rng = np.random.default_rng(2)
    pool = GeomPool.from_lists([rng.uniform(0, 100, (n, 2))
                                for n in (1, 1, 300, 7)])
    ra = np.array([0, 1, 0, 2], dtype=np.int64)
    rb = np.array([1, 0, 1, 3], dtype=np.int64)
    rf = spatial_join.REFINE
    before = (rf.pairs, rf.fragment_pairs, rf.vertex_pairs, rf.launches)
    spatial_join.pool_min_dist(pool, ra, rb)
    # three (1, 1) pairs; 300 vertices against 7: fragments of 128, 128
    # and 44 against 7, in classes (128, 8) and (64, 8)
    launches = [(1, 1), (128, 8), (64, 8)]
    vpairs = sum(spatial_join.refine_tile(m, n) * m * n for m, n in launches)
    assert (rf.pairs, rf.fragment_pairs, rf.vertex_pairs, rf.launches) == (
        before[0] + 4, before[1] + 6, before[2] + vpairs,
        before[3] + len(launches))


def test_refinement_makes_no_program_after_its_family_is_made():
    """`compile_refine` makes one program per size class the pool's
    fragments can fall in; refinements of any pair set then make none."""
    pool, sizes = _ways_pool(seed=1)
    assert spatial_join.refine_classes(pool) == CLASSES
    with _Programs() as made:
        assert spatial_join.compile_refine(pool) == len(CLASSES) ** 2
    assert len(made.made) <= len(CLASSES) ** 2
    rng = np.random.default_rng(3)
    with _Programs() as made:
        for n in rng.integers(1, 600, 12):
            ra = rng.integers(0, len(sizes), n).astype(np.int64)
            rb = rng.integers(0, len(sizes), n).astype(np.int64)
            spatial_join.pool_min_dist(pool, ra, rb)
    assert made.made == []


def test_rank_pass_makes_one_program_per_pow2_class():
    """64 distinct probe counts make no more rank-pass programs than the
    counts have power-of-two classes: the result is sliced on the host."""
    rng = np.random.default_rng(4)
    table = np.sort(rng.integers(0, 1 << 40, 1000))
    counts = rng.choice(np.arange(1, 700), 64, replace=False)
    classes = {max(int(c - 1).bit_length(), 3) for c in counts}
    with _Programs() as made:
        for c in counts:
            probes = rng.integers(0, 1 << 40, c)
            lo, hi = ops.merge_join_ranks(table, probes, backend="kernel")
            np.testing.assert_array_equal(
                lo, np.searchsorted(table, probes, "left"))
            np.testing.assert_array_equal(
                hi, np.searchsorted(table, probes, "right"))
    assert len(made.made) <= len(classes)


def test_descent_makes_one_program_per_pow2_class():
    """64 distinct block counts make no more descent programs than the
    counts have power-of-two classes."""
    rng = np.random.default_rng(5)
    n, m = 37, 5
    node_keys = rng.integers(-2**40, 2**40, (4, n))
    cs = rng.random(n) < 0.5
    counts = np.arange(1, 65)
    classes = {int(b - 1).bit_length() for b in counts}
    with _Programs() as made:
        for b in counts:
            box_keys = rng.integers(-2**40, 2**40, (b, m, 4))
            out = ops.tree_descend(node_keys, cs, box_keys, backend="kernel")
            assert out.shape == (b, n)
    assert len(made.made) <= len(classes)
