"""Pallas TPU kernel: bucketed exact-geometry min-distance (refinement §3.2.4).

Refinement validates MBR candidate pairs against exact point-set geometries
(points / polylines / polygon rings). The CSR geometry pool (core/store.py)
lets the caller gather a whole *bucket* of candidate pairs — all padded to
one (m_pad, n_pad) size class — into dense per-dimension coordinate planes:

    a_planes  dims x (B, m_pad)   driver points, one plane per coordinate so
    b_planes  dims x (B, n_pad)   the lane dimension is a point axis

Both metrics reduce to the same kernel: euclidean refinement uses the raw
(x, y) planes (dims=2), haversine uses per-point unit-sphere (X, Y, Z)
planes (dims=3, ``GeomPool.planes3d``) whose squared chord distance is
``4·h`` — so the inner loop is pure multiply/add either way, with the trig
hoisted to pool build time and the monotone final transform
(core/spatial_join.py::core_to_dist) applied once per pair in float64.

Padding replicates a real point of the same entity (every pool row holds at
least one point), so duplicated points can never change the minimum and the
kernel needs no validity masks. The kernel sees the planes transposed —
points on the sublane axis, pairs on the lane axis — so a fori_loop over the
m_pad driver points reads each as one (1, bb) sublane row, broadcasts it
against the (n_pad, bb) driven points on the VPU, and keeps an elementwise
running minimum; one sublane reduction at the end gives the lane-dense
(1, bb) result. The min of a set does not depend on the order it is taken
in, so the result equals the loop order of the host twin bit for bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

POS_INF = float("inf")


def _kernel(*refs, m_pad: int, dims: int):
    a_refs = refs[:dims]                            # dims x (m_pad, bb)
    b = [r[...] for r in refs[dims:2 * dims]]       # dims x (n_pad, bb)
    out_ref = refs[2 * dims]                        # (1, bb)

    def body(i, best):
        v = None
        for ar, bd in zip(a_refs, b):
            d = ar[pl.ds(i, 1), :] - bd             # (1, bb) - (n_pad, bb)
            v = d * d if v is None else v + d * d
        return jnp.minimum(best, v)

    init = jnp.full(b[0].shape, POS_INF, dtype=jnp.float32)
    best = jax.lax.fori_loop(0, m_pad, body, init)
    out_ref[...] = jnp.min(best, axis=0, keepdims=True)


@jax.jit
def bucketed_min_core_host(a_planes: tuple, b_planes: tuple) -> jnp.ndarray:
    """CPU twin of the kernel: same fori_loop over driver points, (B, n_pad)
    working set. ~2-4x faster on CPU than jitting the dense (B, m, n) oracle
    (XLA CPU materializes the cube), with the kernel's exact numerics."""
    m_pad = a_planes[0].shape[1]

    def body(i, best):
        v = None
        for ad, bd in zip(a_planes, b_planes):
            ai = jax.lax.dynamic_slice_in_dim(ad, i, 1, axis=1)
            d = ai - bd
            v = d * d if v is None else v + d * d
        return jnp.minimum(best, jnp.min(v, axis=1))

    init = jnp.full(a_planes[0].shape[0], POS_INF, dtype=jnp.float32)
    return jax.lax.fori_loop(0, m_pad, body, init)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def bucketed_min_core(a_planes: tuple, b_planes: tuple,
                      bb: int = 256, interpret: bool = False) -> jnp.ndarray:
    """Per-pair min squared distance over one padded size-class bucket.

    a_planes / b_planes: dims-tuples of (B, m_pad) / (B, n_pad) float32
    coordinate planes (padding must replicate real points). Returns (B,)
    float32 minima of ``sum_d (a_d - b_d)²`` over the m_pad x n_pad point
    pairs of each row; the caller applies the metric's monotone distance
    transform.
    """
    dims = len(a_planes)
    m, m_pad = a_planes[0].shape
    n_pad = b_planes[0].shape[1]
    bp = -(-m // bb) * bb
    tiles = [jnp.pad(t.astype(jnp.float32), ((0, bp - m), (0, 0))).T
             for t in (*a_planes, *b_planes)]
    raw = pl.pallas_call(
        functools.partial(_kernel, m_pad=m_pad, dims=dims),
        grid=(bp // bb,),
        in_specs=([pl.BlockSpec((m_pad, bb), lambda i: (0, i))] * dims
                  + [pl.BlockSpec((n_pad, bb), lambda i: (0, i))] * dims),
        out_specs=pl.BlockSpec((1, bb), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, bp), jnp.float32),
        interpret=interpret,
    )(*tiles)
    return raw[0, :m]
