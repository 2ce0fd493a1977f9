"""Pallas TPU kernel: fused S-QuadTree candidate-node descent (Phase 1).

`squadtree.candidate_nodes` used to walk the tree one level at a time —
every level a host round-trip (np.unique over the frontier, Bloom probes,
MBR tests, child pushes). The MBR nesting invariant collapses the whole
traversal: a child's MBR is contained in its parent's *exactly* in f64
(each node's MBR is the min/max union of object boxes clipped to its cell,
over a subset of the parent's objects clipped to a nested cell), so an
expanded driver box that hits a node's MBR hits every ancestor's too, and
the level-synchronous frontier's verdict for node n under block b reduces
to

    in_v[b, n] = any_box_hit(b, n) & cs_path[n]

where cs_path ANDs the Bloom verdict down the root path — block- and
box-independent, precomputed once per query (`SQuadTree.cs_path_mask`).
What remains for the device is a dense (block, node) interval test over
all boxes: embarrassingly parallel, zero per-level host syncs.

The engine's box tests are f64 ``<=`` comparisons and the kernel runs
32-bit math, so coordinates are mapped on the host to order-isomorphic
int64 sort keys (`ops.f64_sort_keys`: IEEE-754 total-order flip, -0.0
canonicalized) and split into (hi32, sign-flipped lo32) planes; the
lexicographic plane compare below equals the f64 compare bit-for-bit —
the same plane trick the merge-join rank kernel uses for its int64 keys.

Grid: (blocks, node tiles, box tiles); each (1, nt) node-tile output row
is an accumulator revisited across the box-tile axis (zeroed on the first
tile via `pl.when`), OR-ing in each box tile's hit-any reduction, so one
(bm-box, nt-node) tile pair is VMEM resident at a time. The output is laid
out (B, 1, N) so that a one-row block spans the whole sublane axis, which
the TPU's (8, 128) block tiling requires. Node lanes padded
past N carry cs = 0; box rows padded past M carry the never-intersecting
sentinel box (mins at the key maximum, maxs at the key minimum — real
keys live strictly inside the int64 range, see `ops.f64_sort_keys`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _plane_le(a_hi, a_lo, b_hi, b_lo):
    """Broadcasted a <= b on (hi32, sign-flipped lo32) int64 key planes."""
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _kernel(nx0h_ref, nx0l_ref, ny0h_ref, ny0l_ref,
            nx2h_ref, nx2l_ref, ny3h_ref, ny3l_ref,
            bx0h_ref, bx0l_ref, by0h_ref, by0l_ref,
            bx2h_ref, bx2l_ref, by3h_ref, by3l_ref,
            cs_ref, out_ref):
    # the (1, nt) node-tile row is an accumulator revisited across the
    # box-tile axis (out index map ignores program_id(2))
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # interval test: node MBR (a) vs expanded driver box (b) intersect iff
    # a.x0 <= b.x2 & b.x0 <= a.x2 & a.y0 <= b.y3 & b.y0 <= a.y3
    hit = (_plane_le(nx0h_ref[...], nx0l_ref[...],      # (1, nt) node planes
                     bx2h_ref[...], bx2l_ref[...])      # (bm, 1) box planes
           & _plane_le(bx0h_ref[...], bx0l_ref[...],
                       nx2h_ref[...], nx2l_ref[...])
           & _plane_le(ny0h_ref[...], ny0l_ref[...],
                       by3h_ref[...], by3l_ref[...])
           & _plane_le(by0h_ref[...], by0l_ref[...],
                       ny3h_ref[...], ny3l_ref[...]))   # (bm, nt)
    any_hit = jnp.max(hit.astype(jnp.int32), axis=0, keepdims=True)
    out_ref[...] = out_ref[...] | (any_hit & cs_ref[...])


@functools.partial(jax.jit, static_argnames=("bm", "nt", "interpret"))
def tree_descend(nodes_hi: jnp.ndarray, nodes_lo: jnp.ndarray,
                 cs: jnp.ndarray, boxes_hi: jnp.ndarray,
                 boxes_lo: jnp.ndarray, bm: int = 512, nt: int = 512,
                 interpret: bool = False) -> jnp.ndarray:
    """Dense candidate-node masks over one driver-block batch.

    nodes_* (4, N) int32 key planes of the node MBRs (rows x0, y0, x2, y3);
    cs (N,) int32 0/1 root-path Bloom mask; boxes_* (B, M, 4) planes of the
    expanded driver boxes, padding rows pre-sentineled by the caller
    (`ops.DESCEND_PAD_BOX`). `bm` / `nt` bound the VMEM-resident box / node
    tiles (`nt` lane-rounded and clamped to the padded node count).
    Returns (B, N) int32 0/1 masks.
    """
    b, m = boxes_hi.shape[0], boxes_hi.shape[1]
    n = nodes_hi.shape[1]
    nt = max(-(-nt // 128) * 128, 128)
    n128 = max(-(-n // 128) * 128, 128)
    nt = min(nt, n128)
    n_pad = -(-n128 // nt) * nt
    bm = max(bm, 8)
    m_pad = max(-(-m // bm) * bm, bm)
    # node-lane padding: zero keys, killed by cs = 0
    nodes_hi = jnp.pad(nodes_hi, ((0, 0), (0, n_pad - n)))
    nodes_lo = jnp.pad(nodes_lo, ((0, 0), (0, n_pad - n)))
    cs = jnp.pad(cs, (0, n_pad - n)).reshape(1, -1)
    if m_pad > m:  # box-row padding: the never-intersecting sentinel box
        sent = jnp.array([[0x7FFFFFFF, 0x7FFFFFFF,
                           -0x80000000, -0x80000000]], jnp.int32)
        pad = jnp.broadcast_to(sent, (b, m_pad - m, 4))
        boxes_hi = jnp.concatenate([boxes_hi, pad], axis=1)
        boxes_lo = jnp.concatenate([boxes_lo, pad], axis=1)
    bh = boxes_hi.reshape(-1, 4)    # (B * m_pad, 4)
    bl = boxes_lo.reshape(-1, 4)
    mt = m_pad // bm
    node_spec = pl.BlockSpec((1, nt), lambda bb, t, j: (0, t))
    box_spec = pl.BlockSpec((bm, 1), lambda bb, t, j: (bb * mt + j, 0))
    node_in = [p[c:c + 1, :] for c in range(4) for p in (nodes_hi, nodes_lo)]
    box_in = [p[:, c:c + 1] for c in range(4) for p in (bh, bl)]
    out = pl.pallas_call(
        _kernel,
        grid=(b, n_pad // nt, mt),
        in_specs=[node_spec] * 8 + [box_spec] * 8 + [node_spec],
        out_specs=pl.BlockSpec((None, 1, nt), lambda bb, t, j: (bb, 0, t)),
        out_shape=jax.ShapeDtypeStruct((b, 1, n_pad), jnp.int32),
        interpret=interpret,
    )(*node_in, *box_in, cs)
    return out[:, 0, :n]
