"""Pallas TPU kernel: batched Bloom-filter membership probes.

Phase-1 candidate search probes |frontier| x |driven CS| keys against
per-node Bloom filters. The filter rows are gathered once by the wrapper
(XLA gather); the kernel is pure 32-bit integer math: double hashing
(h1 + i*h2) mod nbits, word selection by one-hot reduction over the W lane
dimension (no in-row gather on TPU), and a bit test per probe.

Block layout: (bb, W) filter rows + (bb, 1) key halves per tile; all
buffers are VMEM-resident and lane-aligned for W in {8, 16, 32}. The rows
enter as int32 bit patterns: the TPU lowering has no reduction over unsigned
integers, and the one-hot word select sums a single non-zero word, so the
int32 sum returns that word's bits unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mix32(x, seed: int):
    x = (x + jnp.uint32(0x9E3779B9) * jnp.uint32(seed + 1)).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = (x * jnp.uint32(0x85EBCA6B)).astype(jnp.uint32)
    x = x ^ (x >> 13)
    x = (x * jnp.uint32(0xC2B2AE35)).astype(jnp.uint32)
    x = x ^ (x >> 16)
    return x


def _hash32(lo, hi, seed: int):
    return _mix32(lo ^ _mix32(hi, seed + 7), seed)


def _kernel(bits_ref, lo_ref, hi_ref, out_ref, *, k: int):
    bits = bits_ref[...]                       # (bb, W) int32 bit patterns
    lo = lo_ref[...].astype(jnp.uint32)        # (bb, 1)
    hi = hi_ref[...].astype(jnp.uint32)
    w_iota = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    nbits = bits.shape[1] * 32
    h1 = _hash32(lo, hi, 0)
    h2 = _hash32(lo, hi, 1) | jnp.uint32(1)
    hit = jnp.ones(lo.shape, dtype=jnp.int32)
    for i in range(k):
        pos = (h1 + jnp.uint32(i) * h2) % jnp.uint32(nbits)
        w = (pos // 32).astype(jnp.int32)
        shift = (pos % 32).astype(jnp.int32)
        sel = jnp.sum(jnp.where(w_iota == w, bits, 0), axis=1, keepdims=True)
        hit = hit & (jax.lax.shift_right_logical(sel, shift) & 1)
    out_ref[...] = hit


@functools.partial(jax.jit, static_argnames=("k", "bb", "interpret"))
def bloom_probe(bits: jnp.ndarray, key_lo: jnp.ndarray, key_hi: jnp.ndarray,
                k: int = 3, bb: int = 1024,
                interpret: bool = False) -> jnp.ndarray:
    """bits (B, W) uint32 pre-gathered rows; keys split in 32-bit halves.

    Returns (B,) int32 (1 = all k bits set).
    """
    b, w = bits.shape
    bp = -(-b // bb) * bb
    bits_p = jnp.pad(jax.lax.bitcast_convert_type(bits, jnp.int32),
                     ((0, bp - b), (0, 0)))
    lo_p = jnp.pad(key_lo.astype(jnp.int32).reshape(-1, 1), ((0, bp - b), (0, 0)))
    hi_p = jnp.pad(key_hi.astype(jnp.int32).reshape(-1, 1), ((0, bp - b), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, w), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        interpret=interpret,
    )(bits_p, lo_p, hi_p)
    return out[:b, 0]
