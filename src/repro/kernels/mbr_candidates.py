"""Device-side Phase-3 MBR test and candidate compaction (XLA).

The MBR join tests every (driver, driven) box pair of a block against the
query distance and keeps about one pair in a hundred. On the chip the test
itself is a cheap fused broadcast; what costs is turning the (M, N) mask
into the list of its set positions without fetching the mask. Two
programs do it, so that the second can be sized to the count the first
finds:

- `count_words` tests the padded block, packs the mask row-major into
  uint32 words (bit b of word w is flat position 32·w + b), and lays the
  set bits out in order: word w owns output slots [excl[w], excl[w] +
  popcount(w)). One scatter marks the first slot of every non-empty word
  and a running max fills each slot with its word. It returns the count
  and those three word planes, padded to `MAX_WORDS` so that the second
  program is the same for every block shape.
- `compact` takes the first `cap` slots, gathers each slot's word and
  base, and selects the slot's bit of the word by a five-step popcount
  search. It returns the flat positions in row-major order.

The running sums and maxima are log-step scans over 128-wide rows (`_scan`):
XLA's own cumulative ops compile for tens of seconds on the TPU at these
lengths. A block whose count exceeds its word count has no room in the
slot plane; the caller (ops.mbr_candidates) fetches its dense mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref

# slot planes of the largest padded block: ops.MBR_ROWS x ops.MBR_MAX_COLS
MAX_WORDS = 1024 * 16384 // 32


def _scan_lanes(y: jnp.ndarray, op, ident) -> jnp.ndarray:
    """Inclusive scan of `op` along the last axis (Hillis-Steele steps)."""
    s = 1
    while s < y.shape[1]:
        pad = jnp.full((y.shape[0], s), ident, y.dtype)
        y = op(y, jnp.concatenate([pad, y[:, :-s]], axis=1))
        s *= 2
    return y


def _scan(x: jnp.ndarray, op, ident, lanes: int = 128) -> jnp.ndarray:
    """Inclusive scan of `op` over a 1-D array whose length is a power of
    two: within rows of `lanes`, then the rows' carries, recursively."""
    if x.shape[0] <= lanes:
        return _scan_lanes(x[None, :], op, ident)[0]
    y = _scan_lanes(x.reshape(-1, lanes), op, ident)
    carry = _scan(y[:, -1], op, ident, lanes)
    prev = jnp.concatenate([jnp.full((1,), ident, x.dtype), carry[:-1]])
    return op(y, prev[:, None]).ravel()


@jax.jit
def count_words(driver, driven_t, m, n, thresh):
    """driver (Mp, 4) and transposed driven (4, Np) float32 boxes, of which
    the first `m` rows and `n` columns are real; `thresh` the squared
    float32 threshold. Returns (count, words, excl, slot_word), the last
    three padded to `MAX_WORDS`."""
    mask = ref.mbr_mask_ref(driver, driven_t, thresh)
    rows = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 0) < m
    cols = jax.lax.broadcasted_iota(jnp.int32, mask.shape, 1) < n
    bits = (mask & rows & cols).reshape(-1, 32).astype(jnp.uint32)
    words = jnp.sum(bits << jnp.arange(32, dtype=jnp.uint32), axis=1,
                    dtype=jnp.uint32)
    pop = jax.lax.population_count(words).astype(jnp.int32)
    incl = _scan(pop, jnp.add, 0)
    excl = incl - pop
    nw = words.shape[0]
    ids = jnp.arange(nw, dtype=jnp.int32)
    first = jnp.zeros(nw, jnp.int32).at[jnp.where(pop > 0, excl, nw)].set(
        ids, mode="drop")
    slot_word = _scan(first, jnp.maximum, 0)
    pad = (0, MAX_WORDS - nw)
    return (incl[-1], jnp.pad(words, pad), jnp.pad(excl, pad),
            jnp.pad(slot_word, pad))


@functools.partial(jax.jit, static_argnames=("cap",))
def compact(words, excl, slot_word, cap: int):
    """Flat mask positions of output slots [0, cap), row-major. Those
    below the count `count_words` returned are the candidates, provided
    that count does not exceed the block's word count."""
    sw = slot_word[:cap]
    word = words[sw]
    r = (jnp.arange(cap, dtype=jnp.int32) - excl[sw]).astype(jnp.uint32)
    pos = jnp.zeros(cap, jnp.uint32)
    for half in (16, 8, 4, 2, 1):
        c = jax.lax.population_count(
            (word >> pos) & jnp.uint32((1 << half) - 1))
        up = r >= c
        r = jnp.where(up, r - c, r)
        pos = jnp.where(up, pos + half, pos)
    return sw * 32 + pos.astype(jnp.int32)
