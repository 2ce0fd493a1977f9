"""Whole runs of the harness on the CPU, past its look for a chip.

Each cell's configuration at a tiny size, served through the Pallas
kernels in interpret mode and checked against the plain reference; then
the same runs with the timed path broken underneath, which must come out
not correct; then a traffic mix and a cell added as new files only.
"""
from __future__ import annotations

import json
import time

import numpy as np
import pytest

from conftest import ROOT, make_root
from streakbench import harness

SECONDS = 1.5
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(root, cell, seed=7, **kw):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return harness.run(root, bench, cell, seed, SECONDS, False,
                       time.perf_counter(), require_tpu=False, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_through_the_kernels(tiny_root, interpreted_chip,
                                             cell):
    out = _run(tiny_root, cell)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    assert out["device"]["platform"] == "cpu"


def test_large_seed_is_accepted(tiny_root):
    out = _run(tiny_root, "lgd1m.hot", seed=2**31 + 12345)
    assert out["correct"], out["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_bfloat16_control_is_not_correct(tiny_root, cell):
    out = _run(tiny_root, cell, control=True)
    assert not out["correct"]
    assert out["check"]["wrong_answers"]["value"] > 0


def _altered_score(monkeypatch):
    """A served answer altered where the serve loop produces it."""
    from repro.serve.spatial import SpatialServeEngine
    retire = SpatialServeEngine._retire

    def bad(self, slot):
        req, _ = self.slots[slot]
        retire(self, slot)
        if req.rid % 3 == 0 and len(req.scores):
            req.scores = req.scores.copy()
            req.scores[-1] = np.nextafter(req.scores[-1], np.inf)
    monkeypatch.setattr(SpatialServeEngine, "_retire", bad)


def _altered_row(monkeypatch):
    """A row of an answer swapped for another entity."""
    from repro.serve.spatial import SpatialServeEngine
    retire = SpatialServeEngine._retire

    def bad(self, slot):
        req, _ = self.slots[slot]
        retire(self, slot)
        if req.rid % 3 == 0 and req.rows.n > 1:
            col = sorted(req.rows)[0]
            v = req.rows[col].copy()
            v[0], v[-1] = v[-1], v[0]
            if not np.array_equal(v, req.rows[col]):
                req.rows[col] = v
            else:
                req.scores = req.scores[:-1]
    monkeypatch.setattr(SpatialServeEngine, "_retire", bad)


def _dropped(monkeypatch):
    """Some answers never come: their requests fail in the serve loop."""
    from repro.serve.spatial import SpatialServeEngine
    retire = SpatialServeEngine._retire

    def bad(self, slot):
        req, _ = self.slots[slot]
        if req.rid % 4 == 1:
            self.slots[slot] = None
            self._fail(req, RuntimeError("dropped"))
            return
        retire(self, slot)
    monkeypatch.setattr(SpatialServeEngine, "_retire", bad)


def _kernel_falls_back(monkeypatch):
    """Every refine kernel call fails over to its oracle."""
    from repro.core import fault
    from streakbench import program
    reset = program.reset_fault_counters

    def with_plan():
        reset()
        fault.install_plan(fault.FaultPlan(rate=1.0,
                                           ops=("bucketed_min_core",)))
    monkeypatch.setattr(program, "reset_fault_counters", with_plan)


@pytest.mark.parametrize("fault_in", [_altered_score, _altered_row,
                                      _dropped, _kernel_falls_back])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          fault_in):
    fault_in(monkeypatch)
    out = _run(tiny_root, cell)
    assert not out["correct"], out["check"]


def test_a_mix_a_cell_and_a_metric_are_added_as_files_only(tmp_path):
    """A throwaway mix, a cell that uses it and a metric it reports: one new
    file each and new BENCHMARK.json entries, nothing else."""
    mix = {"loop": "open", "rate_qps": 6.0,
           "queries": [{"a": "class:site", "b": "class:poi",
                        "dist_frac": 0.004}],
           "ks": [7, 30], "warmup_requests": 2, "drain_s": 30}
    cell = {"name": "lgd1m.throwaway", "config": "lgd_scale_1m",
            "traffic": "throwaway", "chips": 1, "why": "test"}
    metric = {"name": "answered_share", "unit": "%", "better": "higher",
              "bound": 0.01, "source": "host_clock",
              "workloads": ["lgd1m.throwaway"]}
    root = make_root(tmp_path, files={
        "streakbench/traffic/throwaway.json": json.dumps(mix),
        "streakbench/metrics/answered_share.py":
            "def read(rec):\n"
            "    return 100.0 * len(rec.answered()) / len(rec.requests)\n"},
        entries={"workloads": [cell], "end_to_end": [metric]})
    out = _run(root, "lgd1m.throwaway")
    assert out["correct"], out["check"]
    assert out["metrics"]["answered_share"]["value"] == 100.0
    # end-to-end metrics without a "workloads" list reach every cell
    assert {"bytes_per_quad", "setup_s"} <= set(out["metrics"])


# A deployment of ways, as a later configuration would bring it: polylines
# of heavy-tailed vertex counts, one of them past the program's refinement
# size class of 128 points, beside single points; reified type facts with a
# confidence, and the LGD pair query between the two classes.
WAYS_GENERATOR = '''
import numpy as np

from .common import RawData

EXTENT = 100.0
TERMS = ("rdf:type", "hasGeometry", "hasConfidence", "class:way",
         "class:poi")


def generate(cfg, seed):
    n = int(cfg["n_entities"])
    rng = np.random.default_rng(seed)
    terms = {t: i + 1 for i, t in enumerate(TERMS)}
    numeric = {}
    conf_ids = []
    for v in np.round(np.linspace(0.0, 1.0, 64), 6):
        terms[repr(float(v))] = len(terms) + 1
        numeric[terms[repr(float(v))]] = float(v)
        conf_ids.append(terms[repr(float(v))])
    ent = len(terms) + 1 + np.arange(n, dtype=np.int64)
    fact, geo = ent + n, ent + 2 * n
    is_way = np.arange(n) % 2 == 0
    counts = np.where(is_way, np.minimum(1 + rng.zipf(1.5, n), 200), 1)
    counts[0] = 300
    walks = [np.cumsum(np.concatenate(
        [rng.uniform(0.0, EXTENT, (1, 2)),
         rng.normal(0.0, 0.4, (c - 1, 2))]), axis=0) for c in counts]
    points = np.clip(np.concatenate(walks), 0.0, EXTENT)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    boxes = np.array([np.concatenate([points[a:b].min(0), points[a:b].max(0)])
                      for a, b in zip(offsets[:-1], offsets[1:])])
    cls = np.where(is_way, terms["class:way"], terms["class:poi"])
    conf = np.asarray(conf_ids)[rng.integers(0, len(conf_ids), n)]
    zeros = np.zeros(n, dtype=np.int64)
    quads = np.concatenate([
        np.stack([zeros, ent, np.full(n, terms["hasGeometry"]), geo], 1),
        np.stack([fact, ent, np.full(n, terms["rdf:type"]), cls], 1),
        np.stack([zeros, fact, np.full(n, terms["hasConfidence"]), conf], 1),
    ]).astype(np.int64)
    return RawData(
        quads=quads, terms=terms, numeric=numeric, next_id=int(geo[-1]) + 1,
        geometry_predicate=terms["hasGeometry"], geom_entities=ent,
        geom_boxes=boxes, geom_offsets=offsets, geom_points=points,
        exact=True)


def query(data, spec, k):
    t = data.terms
    return {
        "patterns": [
            ["?r", "?place", "?typePred1", t[spec["a"]]],
            [None, "?r", t["hasConfidence"], "?conf"],
            [None, "?place", t["hasGeometry"], "?g1"],
            ["?r1", "?nplace", "?typePred2", t[spec["b"]]],
            [None, "?r1", t["hasConfidence"], "?conf1"],
            [None, "?nplace", t["hasGeometry"], "?g2"],
        ],
        "spatial": ["?g1", "?g2", EXTENT * float(spec["dist_frac"])],
        "rank": [["?conf", 1.0], ["?conf1", 1.0]],
        "descending": False,
        "k": int(k),
    }
'''

WAYS_CONFIG = {
    "name": "throwaway_ways", "generator": "throwaway_ways", "data_seed": 0,
    "n_entities": 100000, "l_max": 6, "leaf_capacity": 32, "block": 1024,
    "max_slots": 4, "rehearsal": {"n_entities": 300, "block": 128}}


def _ways_root(tmp_path, config=WAYS_CONFIG):
    mix = {"loop": "closed", "clients": 4,
           "queries": [{"a": "class:way", "b": "class:poi"},
                       {"a": "class:poi", "b": "class:way"},
                       {"a": "class:way", "b": "class:way"}],
           "ks": [5, 20], "warmup_requests": 6, "drain_s": 60,
           "vary": {"dist_frac": {"geomspace": [0.005, 0.02], "n": 16}}}
    cfg = {"name": "throwaway_ways", "source": "test",
           "file": "streakbench/configs/throwaway_ways.json",
           "reduced": [], "why": "test"}
    cell = {"name": "ways.throwaway", "config": "throwaway_ways",
            "traffic": "throwaway_ways", "chips": 1, "why": "test"}
    return make_root(tmp_path, files={
        "streakbench/configs/throwaway_ways.json": json.dumps(config),
        "streakbench/datagen/throwaway_ways.py": WAYS_GENERATOR,
        "streakbench/traffic/throwaway_ways.json": json.dumps(mix)},
        entries={"configs": [cfg], "workloads": [cell]})


def test_a_configuration_is_added_as_files_only(tmp_path, monkeypatch,
                                                interpreted_chip):
    """A throwaway configuration, its generator, a mix and a cell: new files
    and new BENCHMARK.json entries only. Its exact geometries reach the
    refinement kernel, fragmented past 128 points; the bfloat16 control
    fails the same comparison."""
    from repro.core import fault, spatial_join
    widest = []
    pool_min_dist = spatial_join.pool_min_dist

    def spy(pool, rows_a, rows_b, *args, **kwargs):
        widest.append(int(max(pool.counts(np.asarray(rows_a)).max(),
                              pool.counts(np.asarray(rows_b)).max())))
        return pool_min_dist(pool, rows_a, rows_b, *args, **kwargs)
    monkeypatch.setattr(spatial_join, "pool_min_dist", spy)

    root = _ways_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = harness.load_cell(root, bench, "ways.throwaway")
    assert cell.config["n_entities"] == 300      # the rehearsal cut
    out = _run(root, "ways.throwaway")
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert fault.STATE.stats.calls[("bucketed_min_core", "kernel")] > 0
    assert max(widest) > spatial_join.REFINE_MAX_PTS
    out = _run(root, "ways.throwaway", control=True)
    assert not out["correct"]
    assert out["check"]["wrong_answers"]["value"] > 0


def test_a_configuration_without_a_rehearsal_cut_is_named(tmp_path):
    config = {k: v for k, v in WAYS_CONFIG.items() if k != "rehearsal"}
    with pytest.raises(ValueError, match="throwaway_ways.json"):
        _ways_root(tmp_path, config)
