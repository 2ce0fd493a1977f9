"""Pallas TPU kernel: the rank pass of the two-phase sort-merge join.

The relational path (paper §3.2.1-3.2.2) joins pattern scans over the
sorted permutation indexes. core/join.py reduces every equi-join to one
primitive over *scalar composite keys*: given a sorted int64 table and a
batch of int64 probes, find each probe's lower and upper insertion rank

    lo[i] = |{ j : table[j] <  probe[i] }|
    hi[i] = |{ j : table[j] <= probe[i] }|

(`hi - lo` is the match multiplicity; the gather pass then materializes the
matching pairs with CSR cumsum/repeat arithmetic).

The engine runs without jax x64, so the wrapper (kernels/ops.py) splits the
int64 keys into (hi32, biased lo32) int32 planes on the host — comparing
(signed hi, signed lo-with-flipped-sign-bit) lexicographically equals the
int64 comparison, the same trick bloom_probe uses for its key halves — and
everything below is pure 32-bit math.

TPU has no efficient per-lane gather, so instead of a binary search the
kernel uses the VPU-friendly *counting* form: each (bb,)-probe block
broadcasts against the whole table resident in VMEM and sums the two
comparison masks over the lane axis. The table is padded with int64-max
sentinel planes, which compare strictly greater than any real probe
(core/join.py packs keys into [0, 2^63-1)), so padding never counts. Work
is O(M·N) compares versus O(M·log N) for the binary search, but it is all
8x128 VPU compares with zero control flow.

The table axis is tiled INSIDE the kernel: the table planes stay in HBM
(`memory_space=ANY`) and stream through a two-slot VMEM scratch with
explicit async copies — tile j+1's DMA is issued before tile j's compare
pass runs, so for tables past VMEM the HBM stream overlaps the VPU
counting loop instead of serializing with it (double buffering). Each grid
step is one probe block; its rank pair accumulates in registers across the
tile loop. Tables that fit a single tile degenerate to one warm-up copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# planes of the int64-max padding sentinel: hi = 0x7FFFFFFF and
# lo = 0xFFFFFFFF ^ sign-bit-flip = 0x7FFFFFFF
_SENT = 0x7FFFFFFF


def _plane_lt_le(t_hi, t_lo, p_hi, p_lo):
    """Broadcasted (table < probe, table <= probe) on split int64 planes."""
    hi_eq = t_hi == p_hi
    lt = (t_hi < p_hi) | (hi_eq & (t_lo < p_lo))
    le = lt | (hi_eq & (t_lo == p_lo))
    return lt, le


def _kernel(n_tiles: int, tn: int,
            t_ref, p_hi_ref, p_lo_ref, lo_ref, hi_ref):
    """One probe block against the whole table.

    `t_ref` is the stacked (2, n_pad) hi/lo plane array left in HBM; tiles
    stream through a (2 slots, 2 planes, tn) VMEM scratch. The next tile's
    copy is started BEFORE waiting on the current one, so tile j+1's HBM
    read overlaps tile j's O(bb·tn) compare-and-sum.
    """
    p_hi = p_hi_ref[...]                                   # (bb, 1)
    p_lo = p_lo_ref[...]

    def scoped(scratch, sem):
        def copy_in(slot, j):
            return pltpu.make_async_copy(
                t_ref.at[:, pl.ds(j * tn, tn)], scratch.at[slot],
                sem.at[slot])

        copy_in(0, 0).start()                              # warm-up

        def body(j, carry):
            lo_acc, hi_acc = carry
            slot = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_tiles)
            def _prefetch():
                copy_in(jax.lax.rem(j + 1, 2), j + 1).start()

            copy_in(slot, j).wait()
            blk = scratch[slot]                            # (2, tn)
            lt, le = _plane_lt_le(blk[0:1, :], blk[1:2, :], p_hi, p_lo)
            return (lo_acc + jnp.sum(lt.astype(jnp.int32), axis=1,
                                     keepdims=True),
                    hi_acc + jnp.sum(le.astype(jnp.int32), axis=1,
                                     keepdims=True))

        z = jnp.zeros(lo_ref.shape, jnp.int32)
        lo, hi = jax.lax.fori_loop(0, n_tiles, body, (z, z))
        lo_ref[...] = lo
        hi_ref[...] = hi

    pl.run_scoped(scoped,
                  scratch=pltpu.VMEM((2, 2, tn), jnp.int32),
                  sem=pltpu.SemaphoreType.DMA((2,)))


@functools.partial(jax.jit, static_argnames=("bb", "tn", "interpret"))
def merge_join_ranks(t_hi: jnp.ndarray, t_lo: jnp.ndarray,
                     p_hi: jnp.ndarray, p_lo: jnp.ndarray,
                     bb: int = 512, tn: int = 2048,
                     interpret: bool = False
                     ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Counting rank pass over one probe batch.

    t_* (N,) / p_* (M,) int32 planes of sorted table keys / probe keys
    (see `ops.split_key_planes`; table sorted by the underlying int64).
    `tn` bounds the VMEM-resident table tile (lane-rounded, clamped to the
    padded table size so small tables stay single-tile). The defaults keep
    each (bb, tn) int32 compare mask at 4 MiB, inside the 16 MiB of scoped
    VMEM a v5e kernel gets by default.
    Returns (lo (M,), hi (M,)) int32 insertion ranks.
    """
    m = p_hi.shape[0]
    n = t_hi.shape[0]
    tn = max(-(-tn // 128) * 128, 128)
    n128 = max(-(-n // 128) * 128, 128)
    tn = min(tn, n128)
    n_pad = -(-n128 // tn) * tn
    mp = max(-(-m // bb) * bb, bb)
    t_hi = jnp.pad(t_hi, (0, n_pad - n), constant_values=_SENT)
    t_lo = jnp.pad(t_lo, (0, n_pad - n), constant_values=_SENT)
    p_hi = jnp.pad(p_hi, (0, mp - m))
    p_lo = jnp.pad(p_lo, (0, mp - m))
    t_planes = jnp.stack([t_hi, t_lo])                     # (2, n_pad)
    lo, hi = pl.pallas_call(
        functools.partial(_kernel, n_pad // tn, tn),
        grid=(mp // bb,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),             # table: HBM
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=[pl.BlockSpec((bb, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bb, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((mp, 1), jnp.int32),
                   jax.ShapeDtypeStruct((mp, 1), jnp.int32)],
        interpret=interpret,
    )(t_planes, p_hi.reshape(-1, 1), p_lo.reshape(-1, 1))
    return lo[:m, 0], hi[:m, 0]


@functools.partial(jax.jit, static_argnames=("side",))
def merge_join_ranks_host(t_hi: jnp.ndarray, t_lo: jnp.ndarray,
                          p_hi: jnp.ndarray, p_lo: jnp.ndarray,
                          side: str = "both"):
    """CPU twin: branchless binary search, vectorized over probes — the
    loop-structured O(M·log N) form of the kernel's counting semantics
    (integer-exact, so all routes are bit-identical). log2(N) unrolled
    steps, each two gathers + one plane compare over the probe vector.
    side="left"/"right" skips the unused bound's search entirely."""
    n = t_hi.shape[0]
    if n == 0:
        z = jnp.zeros(p_hi.shape, dtype=jnp.int32)
        return (z, z) if side == "both" else z

    def bound(strict: bool) -> jnp.ndarray:
        pos = jnp.zeros(p_hi.shape, dtype=jnp.int32)
        step = 1 << max(int(n).bit_length(), 1)
        while step:
            # can we extend the all-pred prefix to pos + step?
            idx = jnp.minimum(pos + (step - 1), n - 1)
            lt, le = _plane_lt_le(jnp.take(t_hi, idx), jnp.take(t_lo, idx),
                                  p_hi, p_lo)
            pred = lt if strict else le
            pos = jnp.where((pos + step <= n) & pred, pos + step, pos)
            step >>= 1
        return pos

    if side == "left":
        return bound(True)
    if side == "right":
        return bound(False)
    return bound(True), bound(False)
