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
    root = make_root(tmp_path, mixes={"throwaway": mix}, cells=[cell])
    (root / "streakbench" / "metrics" / "answered_share.py").write_text(
        "def read(rec):\n"
        "    return 100.0 * len(rec.answered()) / len(rec.requests)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append(
        {"name": "answered_share", "unit": "%", "better": "higher",
         "bound": 0.01, "source": "host_clock",
         "workloads": ["lgd1m.throwaway"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = _run(root, "lgd1m.throwaway")
    assert out["correct"], out["check"]
    assert out["metrics"]["answered_share"]["value"] == 100.0
    # end-to-end metrics without a "workloads" list reach every cell
    assert {"bytes_per_quad", "setup_s"} <= set(out["metrics"])
