"""The ways configuration's data and the exact refinement's metrics:
`refine_pairs_per_query`, `refine_vpairs_per_query` (the program's
counters, as the ``streak.step`` spans carry them) and
`geom_refine_roofline` (the refinement kernel's bytes over its device
time), read from records and profiles made here."""
from __future__ import annotations

import dataclasses
import gzip
import json
import types

import numpy as np
import pytest

from conftest import ROOT, make_root
from streakbench import harness, program, spans, trace_reduce
from streakbench.datagen import lgd_ways
from streakbench.record import Record, Request

CONFIG = json.loads((ROOT / "streakbench" / "configs"
                     / "lgd_ways_1m.json").read_text())


def _rehearsal() -> dict:
    cfg = dict(CONFIG)
    cfg.update(cfg["rehearsal"])
    return cfg


def test_rehearsal_data_has_every_class_and_ways_past_128_vertices():
    from repro.core import spatial_join
    raw = lgd_ways.generate(_rehearsal(), int(CONFIG["data_seed"]))
    fact = raw.quads[raw.quads[:, 2] == raw.terms["rdf:type"]]
    cls = dict(zip(fact[:, 1].tolist(), fact[:, 3].tolist()))
    counts = np.diff(raw.geom_offsets)
    of = np.array([cls[int(e)] for e in raw.geom_entities])
    for name, (lo, hi) in {"class:poi": (1, 1), "class:building": (6, 13),
                           "class:highway": (2, 2000),
                           "class:waterway": (2, 8000)}.items():
        c = counts[of == raw.terms[name]]
        assert len(c) and lo <= c.min() and c.max() <= hi, name
    ways = counts[of == raw.terms["class:waterway"]]
    assert (ways > spatial_join.REFINE_MAX_PTS).any()
    # a box is its vertices' MBR; rings are closed
    for i in range(len(counts)):
        g = raw.geometry(i)
        assert np.array_equal(raw.geom_boxes[i],
                              np.concatenate([g.min(0), g.max(0)]))
        if of[i] == raw.terms["class:building"]:
            assert np.array_equal(g[0], g[-1])


def _serve_traced(root, n_requests: int = 6) -> dict:
    """Ways-against-ways requests served under a profile recorded where
    `harness.run` puts a traced run's; returns what the counters added."""
    import jax
    cfg = _rehearsal()
    raw = lgd_ways.generate(cfg, int(cfg["data_seed"]))
    eng = program.engine(program.build(raw, cfg), cfg)
    for rid in range(n_requests):
        spec = {"a": "class:highway", "b": "class:waterway",
                "dist_frac": 0.002 + 0.001 * rid}
        eng.submit(program.SpatialRequest(rid=rid, query=program.to_query(
            lgd_ways.query(raw, spec, 20))))
    jax.profiler.start_trace(str(root / spans.TRACE_DIR))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            before = eng.counters()
            while eng.step():
                pass
            after = eng.counters()
    finally:
        jax.profiler.stop_trace()
    return {k: after[k] - before[k] for k in after}


def _record(n_answered: int) -> Record:
    done = [Request({}, 5, 0.0, 0.0, 1.0, "ok", {})] * n_answered
    late = [Request({}, 5, 0.0, 0.0, 9.0, "ok", {})]      # after the window
    return Record("closed", 0.0, 2.0, done + late, [], {}, 0, 1,
                  trace=object())


def test_refine_counters_read_per_answered_request(tmp_path):
    root = make_root(tmp_path)
    added = _serve_traced(root)
    assert added["refine_pairs"] > 0
    assert added["refine_fragment_pairs"] >= added["refine_pairs"]
    assert added["refine_vertex_pairs"] >= added["refine_fragment_pairs"]
    assert added["refine_launches"] > 0
    rec = _record(4)
    for metric, counter in (("refine_pairs_per_query.tput", "refine_pairs"),
                            ("refine_vpairs_per_query.tput",
                             "refine_vertex_pairs")):
        read = harness._reader(root, metric)
        assert read(rec) == pytest.approx(added[counter] / 4)
        assert read(dataclasses.replace(rec, trace=None)) is None


def test_a_program_without_the_refine_counters_reads_nothing(tmp_path):
    """The chip fixture, recorded before the program had the counters."""
    root = make_root(tmp_path)
    dst = root / spans.TRACE_DIR / "plugins" / "profile" / "run"
    dst.mkdir(parents=True)
    fixture = (harness.BENCH_DIR / "fixtures"
               / "lgd1m_hot_3s_spans.xplane.pb.gz")
    (dst / "host.xplane.pb").write_bytes(gzip.decompress(fixture.read_bytes()))
    for metric in ("refine_pairs_per_query.tput",
                   "refine_vpairs_per_query.tput",
                   "geom_refine_roofline.tput"):
        assert harness._reader(root, metric)(_record(3)) is None


def _event(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _xspace(host_events, device_events):
    line = types.SimpleNamespace
    return types.SimpleNamespace(planes=[
        line(name="/host:CPU", lines=[line(name="python",
                                           events=host_events)]),
        line(name="/device:TPU:0", lines=[line(name="XLA Ops",
                                               events=device_events)])])


KERNEL = ('%bucketed_min_core.{} = f32[1,256]{{1,0}} custom-call(f32[128,256]'
          '{{1,0}} %a), custom_call_target="tpu_custom_call"')


def test_roofline_reads_the_kernels_bytes_over_its_device_time(tmp_path):
    read = harness._reader(make_root(tmp_path), "geom_refine_roofline.tput")
    roof = read.__globals__
    big = "float32[256, 128] float32[256, 128] float32[256, 8] float32[256, 8]"
    small = "float32[4096, 1] float32[4096, 1] float32[4096, 1] float32[4096, 1]"
    host = [_event("bench.window", 1000, 10_000_000),
            _event("streak.kernel", 2000, 500, op="bucketed_min_core",
                   shapes=big),
            _event("streak.kernel", 3000, 500, op="bucketed_min_core",
                   shapes=small),
            _event("streak.kernel", 4000, 500, op="tree_descend",
                   shapes="int32[8, 8]"),
            # outside the window
            _event("streak.kernel", 20_000_000, 500, op="bucketed_min_core",
                   shapes=big)]
    device = [_event(KERNEL.format(1), 5000, 2000),
              _event(KERNEL.format(2), 9000, 3000),
              _event("%fusion.3 = f32[256,128] fusion(...)", 12000, 4000),
              _event(KERNEL.format(3), 20_000_000, 3000)]
    want_bytes = (256 * (128 + 8) * 2 * 4 + 256 * 4) + (4096 * 2 * 2 * 4
                                                       + 4096 * 4)
    assert roof["measure"](_xspace(host, device)) == (want_bytes, 5000e-9)
    peak = 819e9
    assert roof["share"](_xspace(host, device), peak) == pytest.approx(
        100.0 * want_bytes / 5000e-9 / peak)
    assert roof["share"](_xspace(host[:1], device), peak) is None
    assert roof["share"](_xspace(host, device[2:3]), peak) is None
