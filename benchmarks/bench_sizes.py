"""Tables 1/3 + the store-size scaling curve (1M -> 100M synthetic quads).

The `scale/` section builds `synth_rdf.make_scale` datasets at increasing
quad counts and reports, per size: build time, store/tree bytes, the
Morton-prefix sharded store's per-shard bytes with the compressed E-list
tier (`PackedEList`) against the uncompressed tier, and per-query engine
latency unsharded vs 4-way sharded — with the sharded results asserted
identical to the unsharded engine before anything is timed.

Default sizes stop at 10M so a run stays within CI-class time; set ``REPRO_BENCH_SIZES`` (comma-separated
quad counts, e.g. ``1000000,100000000``) to sweep the full curve.
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro import ExecConfig, StreakEngine
from repro.core.shard import shard_store
from repro.data import synth_rdf

from . import common

DEFAULT_SIZES = (1_000_000, 3_000_000, 10_000_000)
N_SHARDS = 4


def _sizes() -> tuple:
    env = os.environ.get("REPRO_BENCH_SIZES")
    if not env:
        return DEFAULT_SIZES
    return tuple(int(s) for s in env.split(",") if s.strip())


def scaling_curve() -> list:
    rows = []
    for n_quads in _sizes():
        t0 = time.time()
        ds = synth_rdf.make_scale(n_quads, seed=0)
        build_s = time.time() - t0
        store, tree = ds.store, ds.store.tree
        t0 = time.time()
        sharded = shard_store(store, N_SHARDS, compressed=True)
        shard_s = time.time() - t0

        # compressed E-list tier vs the plain int64 tier, same trees: the
        # packed encoding records the id counts, so the uncompressed bytes
        # are known without a second build
        packed_b = sum(sh.tree.packed.nbytes()
                       for sh in sharded.tree_shards)
        plain_b = sum(int(sh.tree.packed.counts.sum(dtype=np.int64)) * 8
                      for sh in sharded.tree_shards)
        tree_b = sharded.shard_tree_nbytes()
        tree_plain_b = tree_b - packed_b + plain_b
        tag = f"scale/n{n_quads}"
        rows.append(common.row(
            f"{tag}/build", build_s * 1e6,
            f"quads={store.n_quads};spatial={tree.n_objects};"
            f"nodes={tree.n_nodes};shard_build_s={shard_s:.1f}"))
        rows.append(common.row(
            f"{tag}/bytes", 0.0,
            f"store_mb={store.nbytes() / 2**20:.1f};"
            f"tree_mb={tree.nbytes() / 2**20:.2f};"
            f"shard_tree_mb={tree_b / 2**20:.2f};"
            f"shard_tree_plain_mb={tree_plain_b / 2**20:.2f};"
            f"elist_packed_mb={packed_b / 2**20:.2f};"
            f"elist_plain_mb={plain_b / 2**20:.2f};"
            f"elist_ratio={plain_b / max(packed_b, 1):.2f}x;"
            f"tree_ratio={tree_plain_b / max(tree_b, 1):.2f}x"))

        eng = StreakEngine(store, ExecConfig())
        eng_sh = StreakEngine(sharded, ExecConfig())
        for qi, q in enumerate(ds.queries):
            s0, r0, _ = eng.execute(q)
            s1, r1, _ = eng_sh.execute(q)
            np.testing.assert_array_equal(np.sort(s1), np.sort(s0))
            assert r1.n == r0.n
            t = common.timeit(lambda: eng.execute(q), warmup=1, repeat=1)
            t_sh = common.timeit(lambda: eng_sh.execute(q), warmup=1,
                                 repeat=1)
            rows.append(common.row(f"{tag}/Q{qi + 1}_unsharded", t,
                                   f"rows={r0.n}"))
            rows.append(common.row(
                f"{tag}/Q{qi + 1}_sharded{N_SHARDS}", t_sh,
                f"rows={r1.n};speedup={t / max(t_sh, 1e-9):.2f}x"))
    return rows


def run() -> list:
    rows = []
    for ds_name in ("yago3", "lgd"):
        ds = common.dataset(ds_name)
        store = ds.store
        tree = store.tree
        rows.append(common.row(
            f"table1_data/{ds_name}", 0.0,
            f"quads={store.n_quads};spatial={tree.n_objects};"
            f"nodes={tree.n_nodes}"))
        rows.append(common.row(
            f"table3_sizes/{ds_name}", 0.0,
            f"raw_mb={ds.raw_nbytes/2**20:.1f};"
            f"store_mb={store.nbytes()/2**20:.1f};"
            f"squadtree_mb={tree.nbytes()/2**20:.2f};"
            f"tree_frac={tree.nbytes()/max(ds.raw_nbytes,1)*100:.2f}%"))
    rows += scaling_curve()
    return rows
