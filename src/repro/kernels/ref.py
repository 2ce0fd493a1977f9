"""Pure-jnp oracles for every Pallas kernel.

Each `*_ref` is the semantic specification; kernel tests sweep shapes/dtypes
and assert_allclose kernels (interpret=True on CPU) against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ----------------------------------------------------------- distance join --
def distance_join_ref(driver: jnp.ndarray, driven: jnp.ndarray) -> jnp.ndarray:
    """Pairwise min distance between boxes. driver (M,4), driven (N,4) ->
    (M, N) float32 (0 where boxes intersect)."""
    a = driver[:, None, :]
    b = driven[None, :, :]
    dx = jnp.maximum(0.0, jnp.maximum(a[..., 0] - b[..., 2],
                                      b[..., 0] - a[..., 2]))
    dy = jnp.maximum(0.0, jnp.maximum(a[..., 1] - b[..., 3],
                                      b[..., 1] - a[..., 3]))
    return jnp.sqrt(dx * dx + dy * dy).astype(jnp.float32)


def mbr_mask_ref(driver: jnp.ndarray, driven_t: jnp.ndarray,
                 thresh) -> jnp.ndarray:
    """Squared-distance MBR test: driver (M, 4) and transposed driven
    (4, N) float32 boxes -> (M, N) bool, dx² + dy² <= thresh."""
    ax0, ay0, ax1, ay1 = (driver[:, c:c + 1] for c in range(4))
    bx0, by0, bx1, by1 = (driven_t[c:c + 1, :] for c in range(4))
    dx = jnp.maximum(0.0, jnp.maximum(ax0 - bx1, bx0 - ax1))
    dy = jnp.maximum(0.0, jnp.maximum(ay0 - by1, by0 - ay1))
    return dx * dx + dy * dy <= thresh


# ------------------------------------------------- fused top-k distance join --
def fused_topk_join_ref(driver: jnp.ndarray, driven: jnp.ndarray,
                        driver_keys: jnp.ndarray, driven_keys: jnp.ndarray,
                        dist, theta, k: int,
                        row_qid: jnp.ndarray | None = None,
                        col_qid: jnp.ndarray | None = None
                        ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dense oracle for kernels/fused_topk_join.py.

    Materializes the (M, N) distance matrix (it is the *specification*, not
    the streaming implementation) and reduces it to the same (M, k) per-row
    partials: pair survives iff box_dist <= dist AND key bound
    driver_keys[i] + driven_keys[j] > theta AND (when query ids are given)
    both rows belong to the same query. `dist` / `theta` may be scalars or
    per-driver-row (M,) arrays. Returns (scores (M, k), idx (M, k) int32,
    counts (M,) int32) padded with -inf / -1.
    """
    d = distance_join_ref(driver, driven)
    m = d.shape[0]
    bound = (driver_keys.astype(jnp.float32)[:, None]
             + driven_keys.astype(jnp.float32)[None, :])
    dist_row = jnp.broadcast_to(jnp.asarray(dist, jnp.float32), (m,))
    theta_row = jnp.broadcast_to(jnp.asarray(theta, jnp.float32), (m,))
    valid = (d <= dist_row[:, None]) & (bound > theta_row[:, None])
    if row_qid is not None and col_qid is not None:
        valid &= (row_qid.astype(jnp.int32)[:, None]
                  == col_qid.astype(jnp.int32)[None, :])
    m, n = d.shape
    col = jax.lax.broadcasted_iota(jnp.int32, (m, n), 1)
    s = jnp.where(valid, bound, -jnp.inf)
    i = jnp.where(valid, col, -1)
    kk = min(k, n)
    top_s, pos = jax.lax.top_k(s, kk)
    top_i = jnp.take_along_axis(i, pos, axis=1)
    top_i = jnp.where(jnp.isneginf(top_s), -1, top_i)
    if kk < k:  # fewer candidates than the partial width: pad
        top_s = jnp.pad(top_s, ((0, 0), (0, k - kk)),
                        constant_values=-jnp.inf)
        top_i = jnp.pad(top_i, ((0, 0), (0, k - kk)), constant_values=-1)
    counts = jnp.sum(valid.astype(jnp.int32), axis=1)
    return top_s, top_i, counts


# ------------------------------------------- bucketed geometry refinement --
def bucketed_min_core_ref(a_planes: tuple, b_planes: tuple) -> jnp.ndarray:
    """Oracle for kernels/geom_refine.py: per-row min squared distance.

    a_planes / b_planes: dims-tuples of (B, m_pad) / (B, n_pad) float32
    coordinate planes; padding must replicate real points of the same
    entity. Returns (B,) float32 minima of ``sum_d (a_d - b_d)²`` over each
    row's point pairs — the metric *core* (squared euclid for dims=2; the
    unit-sphere chord², i.e. 4·haversine-h, for dims=3). The core is
    monotone in the true distance, so the caller applies the final transform
    (sqrt; 2R·asin(√/2)) once per pair in float64 numpy — XLA's jitted
    ``asin`` is not exact at 0, which would turn self-distances into
    ~3e-4 km.
    """
    core = None
    for ad, bd in zip(a_planes, b_planes):
        d = ad[:, :, None] - bd[:, None, :]
        core = d * d if core is None else core + d * d
    return jnp.min(core, axis=(1, 2))


# --------------------------------------------------- merge-join rank pass --
def merge_join_ranks_ref(t_hi: jnp.ndarray, t_lo: jnp.ndarray,
                         p_hi: jnp.ndarray, p_lo: jnp.ndarray
                         ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle for kernels/merge_join.py: dense counting insertion ranks.

    t_* (N,) / p_* (M,) int32 planes of int64 keys split as
    (hi32, sign-bit-flipped lo32), table sorted by the underlying int64.
    Materializes the (M, N) comparison masks (it is the specification, not
    the streaming implementation) and returns (lo (M,), hi (M,)) int32 with
    lo[i] = #{table < probe_i}, hi[i] = #{table <= probe_i}.
    """
    hi_eq = t_hi[None, :] == p_hi[:, None]
    lt = (t_hi[None, :] < p_hi[:, None]) | (hi_eq
                                            & (t_lo[None, :] < p_lo[:, None]))
    le = lt | (hi_eq & (t_lo[None, :] == p_lo[:, None]))
    return (jnp.sum(lt.astype(jnp.int32), axis=1),
            jnp.sum(le.astype(jnp.int32), axis=1))


# ------------------------------------------------------------ tree descent --
def tree_descend_ref(nodes_hi: jnp.ndarray, nodes_lo: jnp.ndarray,
                     cs: jnp.ndarray, boxes_hi: jnp.ndarray,
                     boxes_lo: jnp.ndarray) -> jnp.ndarray:
    """Oracle for kernels/tree_descend.py: dense candidate-node masks.

    nodes_* (4, N) int32 key planes of node MBRs (rows x0, y0, x2, y3);
    cs (N,) int32 0/1 root-path Bloom mask; boxes_* (B, M, 4) planes of
    expanded driver boxes. Materializes the (B, M, N) interval tests (the
    specification, not the tiled implementation) and returns (B, N) int32:
    any box intersecting the node MBR, masked by cs.
    """
    def le(a_hi, a_lo, b_hi, b_lo):
        return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))

    def node(c):
        return nodes_hi[c][None, None, :], nodes_lo[c][None, None, :]

    def box(c):
        return boxes_hi[:, :, c][:, :, None], boxes_lo[:, :, c][:, :, None]

    hit = (le(*node(0), *box(2)) & le(*box(0), *node(2))
           & le(*node(1), *box(3)) & le(*box(1), *node(3)))
    any_hit = jnp.max(hit.astype(jnp.int32), axis=1)        # (B, N)
    return any_hit & cs.astype(jnp.int32)[None, :]


# -------------------------------------------------------------- bloom probe --
def _mix32_jnp(x, seed: int):
    x = (x + jnp.uint32(0x9E3779B9) * jnp.uint32(seed + 1)).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = (x * jnp.uint32(0x85EBCA6B)).astype(jnp.uint32)
    x = x ^ (x >> 13)
    x = (x * jnp.uint32(0xC2B2AE35)).astype(jnp.uint32)
    x = x ^ (x >> 16)
    return x


def hash32_jnp(lo: jnp.ndarray, hi: jnp.ndarray, seed: int) -> jnp.ndarray:
    """Matches repro.core.charsets.hash32 given the key's (lo32, hi32)."""
    return _mix32_jnp(lo.astype(jnp.uint32) ^ _mix32_jnp(hi.astype(jnp.uint32),
                                                         seed + 7), seed)


def bloom_probe_ref(bits: jnp.ndarray, key_lo: jnp.ndarray,
                    key_hi: jnp.ndarray, k: int) -> jnp.ndarray:
    """bits (B, W) uint32 (pre-gathered filter rows), keys split into 32-bit
    halves. Returns (B,) bool: all k probe bits set."""
    nbits = bits.shape[1] * 32
    h1 = hash32_jnp(key_lo, key_hi, 0)
    h2 = hash32_jnp(key_lo, key_hi, 1) | jnp.uint32(1)
    hit = jnp.ones(bits.shape[0], dtype=bool)
    for i in range(k):
        pos = (h1 + jnp.uint32(i) * h2) % jnp.uint32(nbits)
        w = (pos // 32).astype(jnp.int32)
        bshift = (pos % 32).astype(jnp.uint32)
        # one-hot word select (kernel does the same trick: no in-row gather)
        sel = jnp.sum(
            bits * (jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
                    == w[:, None]).astype(jnp.uint32), axis=1)
        hit &= ((sel >> bshift) & jnp.uint32(1)) == 1
    return hit


# ---------------------------------------------------------------- block scan --
def block_scan_ref(scores: jnp.ndarray, theta: float
                   ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Blocked top-k summary pass. scores (nb, B) float32.

    Returns (block_max (nb,), survivor_count (nb,), mask (nb, B) uint8) where
    survivors are entries with score > theta.
    """
    mask = scores > theta
    return (scores.max(axis=1),
            mask.sum(axis=1).astype(jnp.int32),
            mask.astype(jnp.uint8))


# ------------------------------------------------------------------- morton --
def morton_ref(cx: jnp.ndarray, cy: jnp.ndarray) -> jnp.ndarray:
    """Interleave 16-bit cell coords -> int32 Morton code. Any shape."""
    def spread(v):
        v = v.astype(jnp.uint32) & jnp.uint32(0xFFFF)
        v = (v | (v << 8)) & jnp.uint32(0x00FF00FF)
        v = (v | (v << 4)) & jnp.uint32(0x0F0F0F0F)
        v = (v | (v << 2)) & jnp.uint32(0x33333333)
        v = (v | (v << 1)) & jnp.uint32(0x55555555)
        return v
    return (spread(cx) | (spread(cy) << 1)).astype(jnp.int32)


# --------------------------------------------------------- flash attention --
def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True, scale: float | None = None
                        ) -> jnp.ndarray:
    """GQA attention oracle. q (B, Hq, S, D); k, v (B, Hkv, S, D)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        kk.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, vv.astype(jnp.float32)
                      ).astype(q.dtype)
