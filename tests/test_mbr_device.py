"""The `kernel` route of the Phase-3 MBR join: candidate pairs formed on
the device (kernels/ops.py `mbr_candidates`), rechecked on the host in
float64. It has to give the numpy route's (i, j) list element for element.

On the CPU the device programs run through XLA:CPU. Most tests shrink the
padded block classes (`small_classes`) so that row chunks, column chunks
and several capacities show at small sizes.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import fault, geometry, spatial_join
from repro.kernels import mbr_candidates as mc
from repro.kernels import ops, ref


@pytest.fixture
def on_device(monkeypatch):
    """Every block of the `kernel` route goes to the device."""
    monkeypatch.setattr(spatial_join, "DEVICE_MIN_PAIRS", 0)


@pytest.fixture
def small_classes(monkeypatch, on_device):
    monkeypatch.setattr(ops, "MBR_ROWS", 64)
    monkeypatch.setattr(ops, "MBR_MIN_COLS", 128)
    monkeypatch.setattr(ops, "MBR_MAX_COLS", 512)


def _boxes(rng, n, size=0.01):
    lo = rng.random((n, 2))
    return np.concatenate([lo, lo + rng.random((n, 2)) * size], axis=1)


def _same(a, b, dist):
    ri, rj = spatial_join.mbr_distance_join(a, b, dist, "numpy")
    ki, kj = spatial_join.mbr_distance_join(a, b, dist, "kernel")
    assert ki.dtype == ri.dtype == np.int64
    np.testing.assert_array_equal(ki, ri)
    np.testing.assert_array_equal(kj, rj)
    return ri, rj


@pytest.mark.parametrize("m,n,dist", [
    (1, 1, 0.5), (7, 300, 0.05), (64, 128, 0.02), (65, 129, 0.1),
    (200, 1500, 0.03), (130, 2000, 0.005)])
def test_device_route_equals_numpy(small_classes, m, n, dist):
    rng = np.random.default_rng(m * 1000 + n)
    _same(_boxes(rng, m), _boxes(rng, n), dist)


def test_full_size_classes_equal_numpy(on_device):
    """The chip's own classes: 1100 rows (two row chunks) against 17000
    columns (two column chunks, the second padded)."""
    rng = np.random.default_rng(5)
    i, j = _same(_boxes(rng, 1100, 0.0), _boxes(rng, 17000, 0.0), 0.004)
    assert len(i) and (j >= ops.MBR_MAX_COLS).any()


def test_pairs_at_and_within_a_float32_ulp_of_the_distance(small_classes):
    """Point boxes placed at the distance and a float32 ulp or less to
    either side of it, along both axes: the float32 test cannot tell them
    apart, the float64 recheck has to."""
    dist = 0.0123456789
    offs = dist * (1.0 + np.array([-2.0, -1.0, -2.0 ** -7, 0.0, 2.0 ** -7,
                                   1.0, 2.0]) * 2.0 ** -24)
    a = np.array([[0.25, 0.5, 0.25, 0.5], [0.75, 0.125, 0.75, 0.125]])
    pts = [(x + s * o, y) for x, y in a[:, :2] for o in offs for s in (-1, 1)]
    pts += [(x, y + s * o) for x, y in a[:, :2] for o in offs for s in (-1, 1)]
    pts = np.array(pts)
    b = np.concatenate([pts, pts], axis=1)
    d = geometry.box_min_dist(a[:, None, :], b[None, :, :])
    near = np.unique(d[np.abs(d - dist) <= 3.0 * 2.0 ** -24 * dist])
    # the placement straddles the distance on both sides
    assert (near < dist).any() and (near > dist).any() and len(near) > 8
    # at each probe some pair lies exactly at the distance
    for probe in [dist, *near[::max(1, len(near) // 6)]]:
        _same(a, b, float(probe))


def test_degenerate_boxes_and_zero_distance(small_classes):
    pt = np.array([[0.25, 0.5, 0.25, 0.5]])
    other = np.array([[0.25, 0.5, 0.25, 0.5], [0.7, 0.7, 0.7, 0.7],
                      [0.0, 0.0, 0.0, 0.0]])
    i, j = _same(pt, other, 0.0)
    assert i.tolist() == [0] and j.tolist() == [0]


def test_padding_never_matches(small_classes):
    """Real boxes at the origin, where the zero padding lies: no pair names
    a padding row or column."""
    a = np.zeros((70, 4))
    b = np.zeros((130, 4))
    i, j = _same(a, b, 0.0)
    assert len(i) == 70 * 130 and i.max() == 69 and j.max() == 129


def test_column_chunks_keep_row_major_order(small_classes):
    rng = np.random.default_rng(3)
    a, b = _boxes(rng, 150), _boxes(rng, 1337)   # 3 column chunks, 3 rows
    i, j = _same(a, b, 0.05)
    assert len(i) > 1000
    assert (np.diff(i) >= 0).all()
    assert ((np.diff(j) > 0) | (np.diff(i) > 0)).all()


def test_overflowing_chunk_falls_to_the_dense_test(small_classes,
                                                  monkeypatch):
    """More candidates than the chunk's mask has words: the chunk's dense
    mask is fetched instead, and the pairs stay exact."""
    calls = []
    dense = ops._mbr_mask_jit
    monkeypatch.setattr(ops, "_mbr_mask_jit",
                        lambda *a: calls.append(1) or dense(*a))
    rng = np.random.default_rng(4)
    a, b = _boxes(rng, 64), _boxes(rng, 512)
    i, _ = _same(a, b, 0.5)
    assert calls and len(i) > 64 * 512 // 32


def test_device_fetches_the_count_and_its_candidates(on_device):
    """What comes back is the count and 4 bytes a candidate in a power-of-
    two capacity, never the (M, N) mask."""
    rng = np.random.default_rng(6)
    a, b = _boxes(rng, 600), _boxes(rng, 3000)
    fault.STATE.reset()
    try:
        i, _ = _same(a, b, 0.01)
        cap = max(ops.MBR_MIN_CAP, 1 << int(len(i) - 1).bit_length())
        assert len(i) <= cap < 2 * len(i) + 1024
        assert fault.STATE.stats.d2h_bytes["mbr_candidates"] >= 4 * len(i)
        assert fault.STATE.stats.d2h_bytes["mbr_candidates"] <= 4 + 4 * cap
    finally:
        fault.STATE.reset()


def test_small_blocks_stay_on_the_host():
    """Below DEVICE_MIN_PAIRS the `kernel` route makes no dispatch; at it,
    one."""
    rng = np.random.default_rng(8)
    fault.STATE.reset()
    try:
        n = 600
        m = -(-spatial_join.DEVICE_MIN_PAIRS // n)     # m * n >= the cut
        before = spatial_join.PAIRS.on_device
        _same(_boxes(rng, m - 1), _boxes(rng, n), 0.01)
        assert not fault.STATE.stats.calls
        assert not fault.STATE.stats.h2d_bytes
        assert spatial_join.PAIRS.on_device == before
        _same(_boxes(rng, m), _boxes(rng, n), 0.01)
        assert sum(fault.STATE.stats.calls.values()) == 1
        assert spatial_join.PAIRS.on_device == before + m * n
    finally:
        fault.STATE.reset()


def test_programs_stay_bounded_over_random_shapes(small_classes):
    """50 random block shapes make at most one test program per column
    class and one compaction program per capacity class."""
    rng = np.random.default_rng(9)
    mc.count_words.clear_cache()
    mc.compact.clear_cache()
    for _ in range(50):
        m, n = int(rng.integers(1, 200)), int(rng.integers(1, 3000))
        _same(_boxes(rng, m), _boxes(rng, n), float(rng.uniform(0.005, 0.2)))
    col_classes = 3                      # 128, 256, 512
    cap_classes = 1                      # 1024 (64 x 512 / 32 words at most)
    assert mc.count_words._cache_size() <= col_classes
    assert mc.compact._cache_size() <= cap_classes


@pytest.mark.parametrize("scale", [1e-6, 1.0, 360.0, 1e6])
def test_threshold_admits_every_float64_pair(scale):
    """The widened float32 test keeps every pair the float64 test keeps,
    at coordinates of any magnitude, with pairs packed at the distance."""
    rng = np.random.default_rng(int(scale) + 10)
    dist = 0.02 * scale
    a = rng.random((200, 2)) * scale
    ang = rng.random(400) * 2 * np.pi
    r = dist * (1.0 + (rng.random(400) - 0.5) * 2.0 ** -20)
    src = a[rng.integers(0, 200, 400)]
    b = src + np.stack([np.cos(ang), np.sin(ang)], axis=1) * r[:, None]
    A, B = np.concatenate([a, a], 1), np.concatenate([b, b], 1)
    want = geometry.box_min_dist(A[:, None, :], B[None, :, :]) <= dist
    t = ops.mbr_threshold32(A, B, dist)
    got = np.asarray(ref.mbr_mask_ref(A.astype(np.float32),
                                      B.T.astype(np.float32), t))
    assert want.sum() > 50
    assert not (want & ~got).any()
