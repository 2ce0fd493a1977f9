"""Pallas TPU kernel: tiled pairwise MBR distance (Phase-3 hot loop).

The spatial join's inner loop tests every (driver, driven) MBR pair of a
block against the query distance. On TPU this is a VPU-bound elementwise
broadcast over an (M, N) tile grid; each (bm, bn) output tile lives in VMEM
with the two 4-wide box operands staged alongside.

Tiling: the driven boxes arrive transposed, (4, N), so tiles are (bm, 1) x
(1, bn) broadcasts of box components — the output tile (bm, bn) f32 is the
only VMEM-sized buffer (default 256x256x4B = 256 KiB << 16 MiB VMEM), and
the lane dimension (bn) is a multiple of 128 to stay register-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, b_ref, out_ref):
    # a_ref: (bm, 4) driver boxes; b_ref: (4, bn) driven boxes
    a = a_ref[...]
    b = b_ref[...]
    ax0, ay0, ax1, ay1 = (a[:, 0:1], a[:, 1:2], a[:, 2:3], a[:, 3:4])
    bx0, by0, bx1, by1 = (b[0:1, :], b[1:2, :], b[2:3, :], b[3:4, :])
    dx = jnp.maximum(0.0, jnp.maximum(ax0 - bx1, bx0 - ax1))
    dy = jnp.maximum(0.0, jnp.maximum(ay0 - by1, by0 - ay1))
    out_ref[...] = jnp.sqrt(dx * dx + dy * dy)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def distance_join(driver: jnp.ndarray, driven: jnp.ndarray,
                  bm: int = 256, bn: int = 256,
                  interpret: bool = False) -> jnp.ndarray:
    """Pairwise box min-distance matrix (M, N) float32.

    Inputs are padded up to tile multiples; padding rows produce garbage
    distances that the caller masks (ops.distance_join_mask handles it).
    """
    m, n = driver.shape[0], driven.shape[0]
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    drv = jnp.pad(driver.astype(jnp.float32), ((0, mp - m), (0, 0)))
    dvn = jnp.pad(driven.astype(jnp.float32), ((0, np_ - n), (0, 0))).T
    grid = (mp // bm, np_ // bn)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((4, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(drv, dvn)
    return out[:m, :n]
