"""Pallas TPU kernel: fused streaming top-k distance join (paper §3.3).

The matrix kernel (distance_join.py) materializes the full (M, N) distance
matrix in HBM and lets the caller mask it — throwing away the paper's core
insight that a top-k join only ever needs the pairs that can still beat the
shared threshold θ. This kernel fuses the whole Phase-3 predicate into the
tile loop: per (bm, bn) tile it

  1. computes MBR min-distances in VMEM,
  2. applies the distance predicate AND the score-key threshold
     (``driver_key[i] + driven_key[j] > θ`` — a sound upper bound on any
     result row produced by the pair, see core/spatial_join.py),
  3. folds each driver row's survivors into a running fixed-width per-row
     top-k partial (scores + driven indices) carried across the inner grid
     dimension,

so the only HBM outputs are (M, k) partials plus a per-row survivor count —
peak memory is independent of N. The count lets the caller detect rows whose
survivors overflowed the k-wide partial and recover them exactly (the
streaming wrapper densifies just those rows, keeping the join lossless).

The running-merge uses an iterative extract-max selection loop (max / where /
iota only) rather than lax.top_k, so the kernel stays within Mosaic-supported
primitives. The partial is kept `kp` = k rounded up to 128 lanes wide, so the
concatenation of partial and tile stays lane-aligned; the lanes past k stay
-inf / -1 and the wrapper slices them off. The driven side arrives transposed
— (4, N) boxes and (1, N) keys / query ids — so each tile's driven columns
are lane rows that broadcast against the (bm, 1) driver columns directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = float("-inf")


def _select_topk(cat_s: jnp.ndarray, cat_i: jnp.ndarray, k: int, kp: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row top-k of (bm, W) scores with aligned indices, written into
    the first k of `kp` output lanes (the rest stay -inf / -1).

    K-step extract-max: each step takes the row max, locates its first
    column (ties resolve to the lowest column, matching lax.top_k), records
    (score, index) in output lane t by an iota mask, and masks the column
    out. Mosaic-safe ops only.
    """
    bm, w = cat_s.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (bm, w), 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (bm, kp), 1)

    def body(t, carry):
        cur_s, out_s, out_i = carry
        m = jnp.max(cur_s, axis=1, keepdims=True)                  # (bm, 1)
        at_max = cur_s == m
        pick = jnp.min(jnp.where(at_max, iota, w), axis=1,
                       keepdims=True)                              # (bm, 1)
        sel = iota == pick                                         # one-hot
        idx = jnp.sum(jnp.where(sel, cat_i, 0), axis=1, keepdims=True)
        put = out_lane == t
        out_s = jnp.where(put, m, out_s)
        out_i = jnp.where(put, idx, out_i)
        cur_s = jnp.where(sel, NEG_INF, cur_s)
        return cur_s, out_s, out_i

    out_s = jnp.full((bm, kp), NEG_INF, dtype=cat_s.dtype)
    out_i = jnp.full((bm, kp), -1, dtype=jnp.int32)
    _, out_s, out_i = jax.lax.fori_loop(0, k, body, (cat_s, out_s, out_i))
    # padding steps re-pick masked (-inf) columns: scrub their stale indices
    out_i = jnp.where(out_s == NEG_INF, -1, out_i)
    return out_s, out_i


def _kernel(dist_ref, theta_ref, a_ref, ak_ref, aq_ref, b_ref, bk_ref,
            bq_ref, s_ref, i_ref, c_ref, *, bn: int, k: int, kp: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_ref[...] = jnp.full_like(s_ref, NEG_INF)
        i_ref[...] = jnp.full_like(i_ref, -1)
        c_ref[...] = jnp.zeros_like(c_ref)

    a = a_ref[...]                                  # (bm, 4) driver boxes
    b = b_ref[...]                                  # (4, bn) driven boxes
    ax0, ay0, ax1, ay1 = (a[:, 0:1], a[:, 1:2], a[:, 2:3], a[:, 3:4])
    bx0, by0, bx1, by1 = (b[0:1, :], b[1:2, :], b[2:3, :], b[3:4, :])
    dx = jnp.maximum(0.0, jnp.maximum(ax0 - bx1, bx0 - ax1))
    dy = jnp.maximum(0.0, jnp.maximum(ay0 - by1, by0 - ay1))
    d = jnp.sqrt(dx * dx + dy * dy)                 # (bm, bn)

    bound = ak_ref[...] + bk_ref[...]               # (bm, 1) + (1, bn)
    # per-ROW distance/theta (multi-query launches carry one per driver row)
    # and query-id masking: a pair only survives when driver and driven rows
    # belong to the same query
    same_q = aq_ref[...] == bq_ref[...]             # (bm, bn)
    valid = (d <= dist_ref[...]) & (bound > theta_ref[...]) & same_q
    col = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
           + j * bn)                                # global driven index
    tile_s = jnp.where(valid, bound, NEG_INF)
    tile_i = jnp.where(valid, col, -1)

    cat_s = jnp.concatenate([s_ref[...], tile_s], axis=1)    # (bm, kp + bn)
    cat_i = jnp.concatenate([i_ref[...], tile_i], axis=1)
    top_s, top_i = _select_topk(cat_s, cat_i, k, kp)
    s_ref[...] = top_s
    i_ref[...] = top_i
    c_ref[...] = c_ref[...] + jnp.sum(valid.astype(jnp.int32), axis=1,
                                      keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("k", "bm", "bn", "interpret"))
def fused_topk_join(driver: jnp.ndarray, driven: jnp.ndarray,
                    driver_keys: jnp.ndarray, driven_keys: jnp.ndarray,
                    dist, theta, k: int = 64,
                    bm: int = 128, bn: int = 128,
                    row_qid: jnp.ndarray | None = None,
                    col_qid: jnp.ndarray | None = None,
                    interpret: bool = False
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Streaming per-row top-k distance join.

    driver (M, 4) / driven (N, 4) MBRs; driver_keys (M,) / driven_keys (N,)
    per-entity score-key upper bounds (use 0 for a pure distance join, -inf
    to exclude an entity). `dist` and `theta` may be traced scalars — θ
    changes between tile batches without recompiling — or per-driver-row
    ``(M,)`` arrays, which is how a multi-query launch carries each query's
    own distance threshold and top-k state (serve/spatial.py). `row_qid` /
    `col_qid` are optional int32 query ids: when given, pairs whose driver
    row and driven column belong to different queries are masked out, so
    several queries' blocks share one kernel grid.

    Returns (scores (M, k) f32, idx (M, k) int32, counts (M,) int32): per
    driver row the k best surviving pairs by key bound (padded with
    -inf / -1) and the TOTAL survivor count (counts[i] > k ⟹ the partial
    overflowed and the caller must recover row i densely).
    """
    m, n = driver.shape[0], driven.shape[0]
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    kp = -(-k // 128) * 128
    drv = jnp.pad(driver.astype(jnp.float32), ((0, mp - m), (0, 0)))
    dvn = jnp.pad(driven.astype(jnp.float32), ((0, np_ - n), (0, 0))).T
    # padded driven columns carry a -inf key: bound = -inf is never > θ
    # (θ ≥ -inf), so padding can never appear among the survivors
    dk = jnp.pad(driver_keys.astype(jnp.float32), (0, mp - m),
                 constant_values=NEG_INF).reshape(-1, 1)
    vk = jnp.pad(driven_keys.astype(jnp.float32), (0, np_ - n),
                 constant_values=NEG_INF).reshape(1, -1)
    # scalar dist/theta broadcast to per-row columns; padded rows keep their
    # -inf key, so their dist/theta values are irrelevant
    dist_arr = jnp.pad(jnp.broadcast_to(
        jnp.asarray(dist, dtype=jnp.float32), (m,)), (0, mp - m)
    ).reshape(-1, 1)
    theta_arr = jnp.pad(jnp.broadcast_to(
        jnp.asarray(theta, dtype=jnp.float32), (m,)), (0, mp - m)
    ).reshape(-1, 1)
    # absent qids = everything is query 0; pads get -1 / -2 so a padded row
    # can never match a padded column either
    rq = (jnp.zeros(m, jnp.int32) if row_qid is None
          else row_qid.astype(jnp.int32))
    cq = (jnp.zeros(n, jnp.int32) if col_qid is None
          else col_qid.astype(jnp.int32))
    rq = jnp.pad(rq, (0, mp - m), constant_values=-1).reshape(-1, 1)
    cq = jnp.pad(cq, (0, np_ - n), constant_values=-2).reshape(1, -1)
    grid = (mp // bm, np_ // bn)
    scores, idx, counts = pl.pallas_call(
        functools.partial(_kernel, bn=bn, k=k, kp=kp),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 4), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((4, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, kp), jnp.float32),
            jax.ShapeDtypeStruct((mp, kp), jnp.int32),
            jax.ShapeDtypeStruct((mp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(dist_arr, theta_arr, drv, dk, rq, dvn, vk, cq)
    return scores[:m, :k], idx[:m, :k], counts[:m, 0]
