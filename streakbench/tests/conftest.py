"""A tiny copy of the benchmark to rehearse runs on the CPU.

`tiny_root` is a checkout-shaped directory: BENCHMARK.json with the real
cells, each configuration cut to a few thousand quads, and the real
traffic mixes and metric readers. `interpreted_chip` steers the program's
kernel dispatch onto its TPU branch with every Pallas kernel in interpret
mode, so a run takes the kernels' path as it does on the chip.

Run from the repository root: ``python -m pytest streakbench/tests``.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY = {"lgd_scale_1m": {"n_quads": 4000, "block": 128},
        "yago3_20k": {"n_places": 600, "block": 128}}


def make_root(tmp: Path, mixes: dict | None = None,
              cells: list | None = None) -> Path:
    """A checkout-shaped directory with tiny configurations; `mixes` adds
    traffic files, `cells` adds BENCHMARK.json workloads."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    sb = tmp / "streakbench"
    shutil.copytree(ROOT / "streakbench" / "metrics", sb / "metrics")
    shutil.copytree(ROOT / "streakbench" / "traffic", sb / "traffic")
    (sb / "configs").mkdir()
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(TINY[c["name"]])
        (tmp / c["file"]).write_text(json.dumps(cfg))
    for name, mix in (mixes or {}).items():
        (sb / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench["workloads"] += cells or []
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)


def _interpret(fn):
    def run(*args, **kwargs):
        return fn(*args, **{**kwargs, "interpret": True})
    return run


@pytest.fixture
def interpreted_chip(monkeypatch):
    from repro.core import fault
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_auto_rank_backend", None)
    for mod, name in ((ops._ftj, "fused_topk_join"),
                      (ops._td, "tree_descend"),
                      (ops._bp, "bloom_probe"),
                      (ops._gr, "bucketed_min_core"),
                      (ops._mj, "merge_join_ranks"),
                      (ops._dj, "distance_join")):
        monkeypatch.setattr(mod, name, _interpret(getattr(mod, name)))
    fault.STATE.reset()
    yield
    fault.STATE.reset()
