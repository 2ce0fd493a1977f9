"""STREAK top-k retrieval as a serving primitive.

The paper's ORDER BY ... LIMIT machinery (block-wise scoring, per-block upper
bounds, threshold early termination) applied to candidate scoring:

- `blocked_topk`      : lax.scan over item blocks, carrying a running top-k —
                        the fixed "S-Plan-like" full scan (offline bulk path).
- `streak_topk`       : lax.while_loop with the threshold test — blocks are
                        pre-sorted by their score UPPER BOUND (block_max of
                        ||e_i|| — a Cauchy-Schwarz bound, the exact analogue
                        of the paper's numeric-index block_max), and the loop
                        stops at the first block whose bound cannot beat
                        theta. This is the paper's N-Plan early termination.

Both are exact (return the true top-k); `streak_topk` simply reads fewer
blocks. Used by the sasrec serve_p99 / serve_bulk / retrieval_cand cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _merge_topk(scores, ids, new_scores, new_ids, k):
    s = jnp.concatenate([scores, new_scores], axis=-1)
    i = jnp.concatenate([ids, new_ids], axis=-1)
    top_s, pos = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(i, pos, axis=-1)


@functools.partial(jax.jit, static_argnames=("k", "block"))
def blocked_topk(state: jnp.ndarray, items: jnp.ndarray, k: int = 100,
                 block: int = 65536):
    """state (B, D) x items (N, D) -> (scores (B, k), ids (B, k)).

    Full blocked scan: every item block is scored; memory stays at
    (B, block) instead of (B, N).
    """
    b, d = state.shape
    n = items.shape[0]
    nb = -(-n // block)
    npad = nb * block
    items_p = jnp.pad(items, ((0, npad - n), (0, 0)))
    items_b = items_p.reshape(nb, block, d)

    def body(carry, xs):
        scores, ids = carry
        blk_idx, blk = xs
        s = state @ blk.T                                   # (B, block)
        base = blk_idx * block
        cand_ids = base + jnp.arange(block, dtype=jnp.int32)
        s = jnp.where(cand_ids[None, :] < n, s, -jnp.inf)
        scores, ids = _merge_topk(scores, ids,
                                  s, jnp.broadcast_to(cand_ids, s.shape), k)
        return (scores, ids), None

    init = (jnp.full((b, k), -jnp.inf, state.dtype),
            jnp.zeros((b, k), jnp.int32))
    (scores, ids), _ = jax.lax.scan(
        body, init, (jnp.arange(nb, dtype=jnp.int32), items_b))
    return scores, ids


def block_bounds(items: jnp.ndarray, block: int) -> jnp.ndarray:
    """Per-block score upper-bound material: max ||item|| per block."""
    n, d = items.shape
    nb = -(-n // block)
    items_p = jnp.pad(items, ((0, nb * block - n), (0, 0)))
    norms = jnp.sqrt(jnp.sum(items_p * items_p, axis=-1))
    return norms.reshape(nb, block).max(axis=1)            # (nb,)


def sort_items_by_norm(items: jnp.ndarray, block: int):
    """Reorder the catalog by descending norm so block bounds decrease —
    the analogue of STREAK's value-sorted numeric index (build-time step)."""
    norms = jnp.sqrt(jnp.sum(items * items, axis=-1))
    order = jnp.argsort(-norms)
    return items[order], order


@functools.partial(jax.jit, static_argnames=("k", "block"))
def streak_topk(state: jnp.ndarray, items_sorted: jnp.ndarray,
                item_order: jnp.ndarray, bounds: jnp.ndarray,
                k: int = 100, block: int = 65536):
    """Early-terminating top-k over a norm-sorted catalog.

    state (B, D); items_sorted (N, D) descending-norm; bounds (nb,).
    Stops at the first block where ||state|| * bound <= theta (the k-th best
    score so far) — no later block can contribute (Cauchy-Schwarz), exactly
    the paper's threshold test against the numeric block_max.
    """
    b, d = state.shape
    n = items_sorted.shape[0]
    nb = bounds.shape[0]
    items_b = jnp.pad(items_sorted, ((0, nb * block - n), (0, 0))) \
        .reshape(nb, block, d)
    state_norm = jnp.sqrt(jnp.sum(state * state, axis=-1))   # (B,)

    def cond(carry):
        bi, scores, ids = carry
        theta = scores[:, -1]                                # (B,) k-th best
        can_improve = (state_norm * bounds[jnp.minimum(bi, nb - 1)]
                       > theta).any()
        return (bi < nb) & can_improve

    def body(carry):
        bi, scores, ids = carry
        blk = jax.lax.dynamic_index_in_dim(items_b, bi, 0, keepdims=False)
        s = state @ blk.T
        base = bi * block
        cand = base + jnp.arange(block, dtype=jnp.int32)
        s = jnp.where(cand[None, :] < n, s, -jnp.inf)
        real_ids = item_order[jnp.clip(cand, 0, n - 1)].astype(jnp.int32)
        scores, ids = _merge_topk(scores, ids, s,
                                  jnp.broadcast_to(real_ids, s.shape), k)
        return bi + 1, scores, ids

    # inits derive from `state` (zero-valued add) so that under shard_map the
    # carry inherits state's varying-axis type and matches the body output
    zero = jnp.zeros_like(state[:, :1])
    init = (jnp.int32(0),
            jnp.full((b, k), -jnp.inf, state.dtype) + zero,
            jnp.zeros((b, k), jnp.int32) + zero.astype(jnp.int32))
    bi, scores, ids = jax.lax.while_loop(cond, body, init)
    return scores, ids, bi   # bi = blocks actually read (early-out metric)


def streak_topk_sharded(state, items_sorted, item_order, bounds,
                        mesh, axis: str = "model", k: int = 100,
                        block: int = 65536):
    """Expert-parallel STREAK retrieval: each `axis` shard runs the
    early-terminating scan over its local (norm-interleaved) block set, then
    one k-wide all-gather merges shard-local top-k — no per-block
    all-gathers of the catalog (the baseline's dominant collective).

    Blocks should be dealt round-robin across shards (data prep) so every
    shard sees the same bound profile and early-out fires uniformly.
    """
    from jax.sharding import PartitionSpec as P

    def local(state_, items_, order_, bounds_):
        # mark the (replicated) query state shard-varying so the while-loop
        # carry typing matches the shard-local block scan
        state_ = jax.lax.pcast(state_, (axis,), to="varying")
        scores, ids, bi = streak_topk(state_, items_, order_, bounds_,
                                      k=k, block=block)
        all_s = jax.lax.all_gather(scores, axis, axis=1)   # (B, n, k)
        all_i = jax.lax.all_gather(ids, axis, axis=1)
        b = all_s.shape[0]
        top_s, pos = jax.lax.top_k(all_s.reshape(b, -1), k)
        top_i = jnp.take_along_axis(all_i.reshape(b, -1), pos, axis=-1)
        return top_s, top_i, jax.lax.pmax(bi, axis)

    # replication checks off: outputs ARE replicated (all_gather +
    # deterministic top_k) but the varying-axis inference cannot prove it
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis), P(axis)),
        out_specs=(P(), P(), P()), check_vma=False)(
            state, items_sorted, item_order, bounds)


def blocked_topk_sharded(state, items, mesh, axis: str = "model",
                         k: int = 100, block: int = 65536):
    """Catalog-sharded bulk scoring: each `axis` shard scans ITS item rows
    (no per-block catalog all-gather), then one k-wide merge. The offline
    serve_bulk path: kills the baseline's dominant collective term."""
    from jax.sharding import PartitionSpec as P
    n = items.shape[0]
    shards = mesh.shape[axis]
    base = jnp.arange(0, n, n // shards, dtype=jnp.int32)[:shards]

    def local(state_, items_, offset_):
        state_ = jax.lax.pcast(state_, (axis,), to="varying")
        scores, ids = blocked_topk(state_, items_, k=k,
                                   block=min(block, items_.shape[0]))
        ids = ids + offset_[0]
        all_s = jax.lax.all_gather(scores, axis, axis=1)
        all_i = jax.lax.all_gather(ids, axis, axis=1)
        b = all_s.shape[0]
        top_s, pos = jax.lax.top_k(all_s.reshape(b, -1), k)
        top_i = jnp.take_along_axis(all_i.reshape(b, -1), pos, axis=-1)
        return top_s, top_i

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis, None), P(axis)),
        out_specs=(P(), P()), check_vma=False)(state, items, base)
