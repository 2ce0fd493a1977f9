"""Compile the main-path Pallas kernels for a TPU v5e that is described,
not attached, at the engine's real sizes and tile defaults, and the
device join's XLA programs (kernels/mbr_candidates.py) at their classes.

Interpret mode (the rest of the suite) cannot see what the TPU compiler
refuses: unaligned blocks, ops with no Mosaic lowering, tiles past the
scoped VMEM. These compiles can, at no chip time. Each test asserts the
compiled program holds the kernel (`tpu_custom_call`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import spatial_join
from repro.kernels import (bloom_probe, distance_join, fused_topk_join,
                           geom_refine, mbr_candidates, merge_join, ops,
                           tree_descend)

F32, I32, U32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without one
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # noqa: BLE001 — no TPU compiler here
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _assert_kernel(lowered) -> None:
    assert "tpu_custom_call" in lowered.compile().as_text()


# (driver rows, driven columns, partial width): one tenant's 1024-row block
# at the cold-start kcap; eight tenants' rows at the tuner's ceiling; and
# the smallest pow2 launch with a k below the 128-lane partial
@pytest.mark.parametrize("m,n,k", [(1024, 4096, 64), (8192, 4096, 1024),
                                   (128, 128, 8)])
def test_fused_topk_join_compiles(shape, m, n, k):
    f = jax.jit(lambda a, b, ak, bk, d, t, rq, cq: fused_topk_join
                .fused_topk_join(a, b, ak, bk, d, t, k=k,
                                 row_qid=rq, col_qid=cq))
    _assert_kernel(f.lower(
        shape((m, 4), F32), shape((n, 4), F32), shape((m,), F32),
        shape((n,), F32), shape((m,), F32), shape((m,), F32),
        shape((m,), I32), shape((n,), I32)))


def test_distance_join_compiles(shape):
    # dense overflow recovery: a block's rows against one fused column batch
    _assert_kernel(distance_join.distance_join.lower(
        shape((1024, 4), F32), shape((4096, 4), F32)))


def test_tree_descend_compiles(shape):
    # 8 lookahead blocks of 4096 boxes (make_scale's block) over ~2k nodes
    _assert_kernel(tree_descend.tree_descend.lower(
        shape((4, 1893), I32), shape((4, 1893), I32), shape((1893,), I32),
        shape((8, 4096, 4), I32), shape((8, 4096, 4), I32)))


def test_bloom_probe_compiles(shape):
    # root-path masks: every tree node against a few driven CS keys
    _assert_kernel(bloom_probe.bloom_probe.lower(
        shape((1893 * 48, 8), U32), shape((1893 * 48,), I32),
        shape((1893 * 48,), I32), k=3))


@pytest.mark.parametrize("m_pad,n_pad,dims", [(4, 4, 2), (128, 128, 3),
                                              (1, 64, 2)])
def test_geom_refine_compiles(shape, m_pad, n_pad, dims):
    a = tuple(shape((1024, m_pad), F32) for _ in range(dims))
    b = tuple(shape((1024, n_pad), F32) for _ in range(dims))
    _assert_kernel(geom_refine.bucketed_min_core.lower(a, b))


@pytest.mark.parametrize("m_pad", [1, 2, 4, 8, 16, 32, 64, 128])
def test_refine_family_compiles(shape, m_pad):
    # the refinement's whole program family: every (m_pad, n_pad) size
    # class at its fixed tile of pairs (core/spatial_join.py::refine_tile)
    for n_pad in (1, 2, 4, 8, 16, 32, 64, 128):
        t = spatial_join.refine_tile(m_pad, n_pad)
        a = tuple(shape((t, m_pad), F32) for _ in range(2))
        b = tuple(shape((t, n_pad), F32) for _ in range(2))
        _assert_kernel(geom_refine.bucketed_min_core.lower(a, b))


def test_merge_join_ranks_compiles(shape):
    # a 1M-quad permutation index against a large probe batch
    t, p = 1 << 20, 1 << 17
    _assert_kernel(merge_join.merge_join_ranks.lower(
        shape((t,), I32), shape((t,), I32), shape((p,), I32),
        shape((p,), I32)))


@pytest.mark.parametrize("ncols", [ops.MBR_MIN_COLS, ops.MBR_MAX_COLS])
def test_mbr_count_words_compiles(shape, ncols):
    mbr_candidates.count_words.lower(
        shape((ops.MBR_ROWS, 4), F32), shape((4, ncols), F32),
        shape((), I32), shape((), I32), shape((), F32)).compile()


@pytest.mark.parametrize("cap", [ops.MBR_MIN_CAP, mbr_candidates.MAX_WORDS])
def test_mbr_compact_compiles(shape, cap):
    w = mbr_candidates.MAX_WORDS
    mbr_candidates.compact.lower(
        shape((w,), U32), shape((w,), I32), shape((w,), I32),
        cap=cap).compile()


def test_sharded_descent_compiles_over_four_chips(topo):
    """The `--chips 4` path: the shard_map'd descent over a 2x2 host."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("shard",))
    split = NamedSharding(mesh, PartitionSpec("shard"))
    whole = NamedSharding(mesh, PartitionSpec())
    f = ops.sharded_descend_fn(mesh, pallas=True)
    lowered = f.lower(
        jax.ShapeDtypeStruct((4, 4, 512), I32, sharding=split),
        jax.ShapeDtypeStruct((4, 4, 512), I32, sharding=split),
        jax.ShapeDtypeStruct((4, 512), I32, sharding=split),
        jax.ShapeDtypeStruct((8, 4096, 4), I32, sharding=whole),
        jax.ShapeDtypeStruct((8, 4096, 4), I32, sharding=whole))
    _assert_kernel(lowered)
