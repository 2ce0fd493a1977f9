"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes ("data", "model").
Multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model") — the
"pod" axis composes with "data" for batch sharding and carries the cross-pod
gradient all-reduce (optionally int8-compressed, dist/grad_compression.py).

Defined as a function so importing this module never touches jax device
state (the dry-run forces 512 host devices BEFORE any jax import).
"""
from __future__ import annotations

import jax


def auto_axes(n_axes: int) -> tuple:
    """`axis_types` for a mesh whose axes are all Auto-sharded."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=auto_axes(len(axes)))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over the real local devices (tests, examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=auto_axes(2))


def make_shard_mesh(n_shards: int):
    """1-axis ``("shard",)`` mesh for the Morton-prefix store shards.

    Sized to the largest divisor of `n_shards` that fits the local device
    count, so a stacked ``(S, ...)`` per-shard batch partitions evenly —
    each device sweeps its resident shards with `lax.map` when S exceeds
    the device count (CI's shardlane forces 8 host devices via XLA_FLAGS).
    """
    n = len(jax.devices())
    d = max(k for k in range(1, min(n, n_shards) + 1) if n_shards % k == 0)
    return jax.make_mesh((d,), ("shard",), axis_types=auto_axes(1))
