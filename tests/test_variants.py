"""Perf-variant equivalence: the optimized paths must match the baselines."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import transformer
from repro.serve import retrieval


def _cfg(**kw):
    base = dict(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                d_ff=64, vocab=64, dtype="float32", remat=False)
    base.update(kw)
    return transformer.TransformerConfig(**base)


def test_scatter_cache_update_matches_onehot():
    cfg = _cfg()
    cfg_opt = dataclasses.replace(cfg, scatter_cache_update=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0, 64)
    c1 = transformer.init_cache(cfg, 2, 8)
    c2 = transformer.init_cache(cfg_opt, 2, 8)
    for t in range(6):
        l1, c1 = transformer.decode_step(params, c1, tokens[:, t],
                                         jnp.array([t, t]), cfg)
        l2, c2 = transformer.decode_step(params, c2, tokens[:, t],
                                         jnp.array([t, t]), cfg_opt)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c1["k"]), np.asarray(c2["k"]),
                               rtol=1e-5, atol=1e-6)


def test_bf16_operand_attention_close_to_f32():
    cfg = _cfg(dtype="bfloat16")
    cfg_opt = dataclasses.replace(cfg, attn_bf16_operands=True)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    h1 = transformer.forward(params, tokens, cfg)
    h2 = transformer.forward(params, tokens, cfg_opt)
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_microbatch_accumulation_matches_full_batch_grads():
    cfg = _cfg(loss_chunks=1)
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 64)

    loss_fn = lambda p, b: transformer.lm_loss(p, b, cfg)
    _, g_full = jax.value_and_grad(loss_fn)(params, tokens)

    def micro(gsum, tk):
        l, g = jax.value_and_grad(loss_fn)(params, tk)
        return jax.tree.map(lambda a, b: a + b, gsum, g), l
    zeros = jax.tree.map(jnp.zeros_like, params)
    gsum, _ = jax.lax.scan(micro, zeros, tokens.reshape(4, 2, 16))
    g_acc = jax.tree.map(lambda g: g / 4, gsum)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_acc)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_streak_topk_sharded_matches_unsharded():
    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    rng = np.random.default_rng(0)
    state = jnp.asarray(rng.normal(size=(2, 8)).astype(np.float32))
    items = jnp.asarray((rng.normal(size=(512, 8))
                         * rng.exponential(1.0, (512, 1))).astype(np.float32))
    block = 64
    items_s, order = retrieval.sort_items_by_norm(items, block)
    bounds = retrieval.block_bounds(items_s, block)
    s1, i1, _ = retrieval.streak_topk(state, items_s, order.astype(jnp.int32),
                                      bounds, k=8, block=block)
    with mesh:
        s2, i2, _ = retrieval.streak_topk_sharded(
            state, items_s, order.astype(jnp.int32), bounds, mesh=mesh,
            axis="model", k=8, block=block)
    np.testing.assert_allclose(np.sort(np.asarray(s1), axis=-1),
                               np.sort(np.asarray(s2), axis=-1), rtol=1e-5)
