"""The benchmark's parts on their own: the generators' copies, the traffic
sampler, the reference's distance join, the array walker and the trace
reduction."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from conftest import ROOT
from streakbench import check, reference, trace_reduce, traffic, walker
from streakbench.datagen import lgd_scale, yago
from streakbench.datagen.common import relabel

MIXES = sorted((ROOT / "streakbench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_sampler_is_fixed_by_the_seed(path):
    mix = traffic.Mix.load(path)
    seed = 2**31 + 77
    assert mix.take(seed, 0, 50) == mix.take(seed, 0, 50)
    assert mix.take(seed, 0, 50) != mix.take(seed + 1, 0, 50)
    assert mix.take(seed, 0, 50) != mix.take(seed, 1, 50)
    n_spec, n_k = len(mix.queries), len(mix.ks)
    for s in (seed, 3):
        draws = mix.take(s, 0, 2 * n_spec * n_k)
        # each cycle of the specs holds every spec once, and so for the ks
        specs = [mix.queries.index({key: v for key, v in spec.items()
                                    if key not in mix.vary})
                 for spec, _ in draws]
        for c in range(0, len(draws), n_spec):
            assert sorted(specs[c:c + n_spec]) == list(range(n_spec))
        for c in range(0, len(draws), n_k):
            assert sorted(k for _, k in draws[c:c + n_k]) == sorted(mix.ks)
    for name in mix.vary:
        grid = mix.grid(name, 0)
        n = len(grid)
        window = [spec[name] for spec, _ in mix.take(seed, 0, 2 * n)]
        assert sorted(window[:n]) == sorted(window[n:]) == sorted(grid)
        # the warm-up's values lie between the window's: no query repeats
        warm = {spec[name] for spec, _ in mix.take(seed, 1, n)}
        assert warm.isdisjoint(grid)
        assert min(grid) < min(warm) and max(warm) < max(grid)
    if mix.loop == "open":
        a = mix.arrivals(seed, 10.0)
        assert np.array_equal(a, mix.arrivals(seed, 10.0))
        assert len(a) == round(mix.rate_qps * 10.0)
        assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 10.0
        # another seed: the same gaps in another order
        b = mix.arrivals(seed + 1, 10.0)
        assert not np.array_equal(a, b)
        assert np.allclose(np.sort(np.diff(a, prepend=0.0)),
                           np.sort(np.diff(b, prepend=0.0)))


@pytest.mark.parametrize("gen,cfg", [
    (lgd_scale, {"n_quads": 2000, "n_conf_bins": 4096}),
    (yago, {"n_places": 200})])
def test_relabel_keeps_the_deployment(gen, cfg):
    raw = gen.generate(cfg, 3)
    a, b = relabel(raw, 11), relabel(raw, 2**31 + 11)
    assert not np.array_equal(a.quads, b.quads)
    for r in (a, b):
        back = np.zeros(raw.next_id, dtype=np.int64)
        back[[r.terms[t] for t in raw.terms]] = list(raw.terms.values())
        back[r.geom_entities] = raw.geom_entities
        assert r.geometry_predicate == r.terms["hasGeometry"]
        assert {r.terms[t]: v for t, v in ((t, raw.numeric[i]) for t, i in
                raw.terms.items() if i in raw.numeric)} == r.numeric
        # the same quads up to ids: term and spatial ids map back
        pos = np.isin(r.quads[:, 1], r.geom_entities)
        assert np.array_equal(np.sort(back[r.quads[pos, 1]]),
                              np.sort(raw.quads[np.isin(raw.quads[:, 1],
                                                        raw.geom_entities),
                                                1]))
        assert len(r.quads) == len(raw.quads)


def test_generators_copy_the_programs():
    """Same seed, same quads as the program's own generators."""
    from repro.data import synth_rdf
    raw = lgd_scale.generate({"n_quads": 3000, "n_conf_bins": 4096}, 5)
    ds = synth_rdf.make_scale(3000, seed=5, block=64)
    assert raw.quads.shape == ds.store.quads.shape
    assert np.array_equal(np.sort(raw.quads[:, 3]),
                          np.sort(ds.store.quads[:, 3]))
    raw = yago.generate({"n_places": 300}, 9)
    ds = synth_rdf.make_yago(n_places=300, seed=9, block=64)
    assert raw.quads.shape == ds.store.quads.shape
    # literal and term ids agree where no spatial id was given
    assert raw.terms["hasGeometry"] == ds.ns["hasGeometry"]
    assert np.array_equal(np.sort(raw.quads[:, 2]),
                          np.sort(ds.store.quads[:, 2]))


def _brute_within(ref, ea, eb, dist):
    out = set()
    for a in ea:
        pa, _ = ref._points_of(np.array([a]))
        for b in eb:
            pb, _ = ref._points_of(np.array([b]))
            d = pa[:, None, :] - pb[None, :, :]
            core = d[..., 0] * d[..., 0] + d[..., 1] * d[..., 1]
            if np.sqrt(core.astype(np.float64)).min() <= dist:
                out.add((int(a), int(b)))
    return out


@pytest.mark.parametrize("gen,cfg", [
    (lgd_scale, {"n_quads": 2000, "n_conf_bins": 4096}),
    (yago, {"n_places": 200})])
def test_grid_join_equals_brute_force(gen, cfg):
    raw = gen.generate(cfg, 3)
    ref = reference.Reference(raw)
    ents = raw.geom_entities
    ea, eb = ents[: len(ents) // 2], ents[len(ents) // 2:]
    dist = gen.EXTENT * 0.03
    pa, pb, d = ref.within(ea, eb, dist)
    got = set(zip(pa.tolist(), pb.tolist()))
    assert got == _brute_within(ref, ea, eb, dist)
    assert got       # the distance finds pairs to compare
    # each pair's distance: a shorter query distance keeps the same pairs
    near = dist / 2
    assert set(zip(pa[d <= near].tolist(), pb[d <= near].tolist())) \
        == _brute_within(ref, ea, eb, near)


@pytest.mark.parametrize("gen,cfg,spec", [
    (lgd_scale, {"n_quads": 3000, "n_conf_bins": 4096},
     {"a": "class:poi", "b": "class:site"}),
    (yago, {"n_places": 600}, {"template": "Q5"}),
    (yago, {"n_places": 600}, {"template": "Q1"})])
def test_queries_ranked_together_equal_each_alone(gen, cfg, spec):
    raw = gen.generate(cfg, 4)
    ref = reference.Reference(raw)
    qs = [gen.query(raw, {**spec, "dist_frac": f}, 30)
          for f in (0.03, 0.004, 0.011, 0.02)]
    for one, many in zip((ref.rank(q, 30) for q in qs),
                         ref.rank_many(qs, 30)):
        assert one.columns == many.columns
        assert np.array_equal(one.scores, many.scores)
        assert np.array_equal(one.rows, many.rows)


def test_same_answer_allows_any_tie_at_the_kth_score():
    cols = ["a", "b"]
    ref = reference.Ranked(cols, np.array([1.0, 2.0, 2.0, 2.0]),
                           np.array([[1, 1], [2, 2], [3, 3], [4, 4]]))
    rows = {"a": np.array([1, 4]), "b": np.array([1, 4])}
    assert check.same_answer(np.array([1.0, 2.0]), rows, ref, 2) is None
    rows = {"a": np.array([1, 5]), "b": np.array([1, 5])}
    assert check.same_answer(np.array([1.0, 2.0]), rows, ref, 2)
    rows = {"a": np.array([2, 3]), "b": np.array([2, 3])}
    assert check.same_answer(np.array([2.0, 2.0]), rows, ref, 2)
    assert check.same_answer(np.array([1.0]), {"a": [1], "b": [1]}, ref, 2)


def test_walker_counts_each_buffer_once():
    import jax.numpy as jnp
    a = np.zeros(1000, dtype=np.int64)
    view = a[10:20]

    class Holder:
        def __init__(self):
            self.x = {"a": a, "again": [a, view]}
            self.fn = test_walker_counts_each_buffer_once
            self.mod = np
            self.dev = jnp.zeros(256, dtype=jnp.float32)

    assert walker.array_bytes(Holder()) == a.nbytes + 1024
    assert walker.array_bytes((view,)) == a.nbytes


def test_chip_trace_reduces_to_fixed_numbers():
    """A 4-s traced window of lgd1m.hot recorded on a TPU v5 lite: 77 engine
    steps, every device op named by its HLO text."""
    r = trace_reduce.reduce(ROOT / "streakbench" / "fixtures"
                            / "lgd1m_hot_4s.xplane.pb.gz")
    assert r.n_devices == 1
    assert r.window_s == 4.009285302
    assert r.busy_s == pytest.approx(0.031341069, abs=1e-9)
    assert r.kernel_s == pytest.approx(0.02742046, abs=1e-9)
    assert r.idle_pct == pytest.approx(99.2182879, abs=1e-6)
    assert r.kernel_pct == pytest.approx(0.6839239, abs=1e-6)
    names = [n for n, _ in r.device_ops]
    assert names[:3] == ["tree_descend", "fusion", "merge_join_ranks"]
    assert "bloom_probe" in names
    assert r.device_ops[0][1] == pytest.approx(0.025792858, abs=1e-9)
    # between arrivals the host sleeps: the longest gaps are those waits
    assert r.idle_gaps[0] == ["bench.wait_arrival",
                              pytest.approx(0.484027009, abs=1e-9)]
    assert len(r.device_ops) == len(r.idle_gaps) == trace_reduce.TOP


def test_every_config_file_is_described():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert Path(ROOT / "streakbench" / "datagen"
                    / f"{cfg['generator']}.py").is_file()
