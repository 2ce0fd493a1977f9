"""`mbr_device_pct`: the share of Phase 3's block-product pairs that the
device tested, read from the counters the ``streak.step`` spans carry, on
profiles recorded on the CPU."""
from __future__ import annotations

import types

import pytest

from conftest import make_root
from streakbench import harness, spans, trace_reduce


def _record(root, join: str, device_min_pairs: int, monkeypatch):
    """A served workload's profile under `root`, where `harness.run` puts
    a traced run's; returns the counters the steps added."""
    import dataclasses
    import jax
    from repro.core import spatial_join
    from repro.core.executor import ExecConfig
    from repro.core.policy import BackendPolicy
    from repro.data.synth_rdf import make_yago
    from repro.serve.spatial import SpatialRequest, SpatialServeEngine

    monkeypatch.setattr(spatial_join, "DEVICE_MIN_PAIRS", device_min_pairs)
    yago = make_yago(n_places=600, seed=1, block=128)
    cfg = ExecConfig(policy=BackendPolicy(join=join))
    queries = [dataclasses.replace(q, k=k) for q in yago.queries[:4]
               for k in (5, 40)]
    srv = SpatialServeEngine(yago.store, cfg, max_slots=3)
    for rid, q in enumerate(queries):
        srv.submit(SpatialRequest(rid=rid, query=q))
    jax.profiler.start_trace(str(root / spans.TRACE_DIR))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            before = srv.counters()
            while srv.step():
                pass
            after = srv.counters()
    finally:
        jax.profiler.stop_trace()
    return {k: after[k] - before[k] for k in after}


@pytest.mark.parametrize("join,floor,share", [
    ("kernel", 0, 100.0), ("numpy", 0, 0.0)])
def test_share_of_pairs_tested_on_the_device(tmp_path, monkeypatch, join,
                                             floor, share):
    root = make_root(tmp_path)
    added = _record(root, join, floor, monkeypatch)
    assert added["mbr_pairs"] > 0
    assert added["mbr_device_pairs"] == added["mbr_pairs"] * share / 100
    read = harness._reader(root, "mbr_device_pct.tput")
    traced = types.SimpleNamespace(trace=object())
    assert read(traced) == pytest.approx(share)
    assert read(types.SimpleNamespace(trace=None)) is None


def test_a_program_without_the_counters_reads_nothing(tmp_path):
    """A traced run of a program whose steps carry no Phase 3 pair counters
    (the chip fixture, recorded before the program had them) reports no
    value."""
    import gzip
    root = make_root(tmp_path)
    dst = root / spans.TRACE_DIR / "plugins" / "profile" / "run"
    dst.mkdir(parents=True)
    fixture = (harness.BENCH_DIR / "fixtures"
               / "lgd1m_hot_3s_spans.xplane.pb.gz")
    (dst / "host.xplane.pb").write_bytes(gzip.decompress(fixture.read_bytes()))
    sp = spans.reduce(trace_reduce.find_xplane(root / spans.TRACE_DIR))
    assert sp is not None and "mbr_pairs" not in sp.counters
    read = harness._reader(root, "mbr_device_pct.tput")
    assert read(types.SimpleNamespace(trace=object())) is None
