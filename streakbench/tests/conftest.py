"""A tiny copy of the benchmark to rehearse runs on the CPU.

`tiny_root` is a checkout-shaped directory: BENCHMARK.json with the real
cells, the real configurations, generators, traffic mixes and metric
readers, and each configuration cut to the sizes under its own
``rehearsal`` key (a few thousand quads). `interpreted_chip` steers the
program's kernel dispatch onto its TPU branch with every Pallas kernel in
interpret mode, so a run takes the kernels' path as it does on the chip.

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

def make_root(tmp: Path, files: dict | None = None,
              entries: dict | None = None) -> Path:
    """A checkout-shaped directory with tiny configurations. `files` (path
    under the root -> text) adds files, `entries` (key -> list) appends to
    BENCHMARK.json's lists; then every configuration is cut to the sizes
    under its ``rehearsal`` key."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for part in ("configs", "datagen", "metrics", "traffic"):
        shutil.copytree(ROOT / "streakbench" / part,
                        tmp / "streakbench" / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for rel, text in (files or {}).items():
        (tmp / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp / rel).write_text(text)
    for key, more in (entries or {}).items():
        bench[key] += more
    for c in bench["configs"]:
        path = tmp / c["file"]
        cfg = json.loads(path.read_text())
        if "rehearsal" not in cfg:
            raise ValueError(f"{c['file']}: no 'rehearsal' key, the sizes "
                             "the CPU rehearsal cuts this configuration to")
        cfg.update(cfg["rehearsal"])
        path.write_text(json.dumps(cfg))
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
