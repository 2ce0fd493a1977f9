"""The reduction of the program's ``streak.*`` spans (`spans.reduce`): self
time per layer, and the device-idle time named by overlap."""
from __future__ import annotations

import pytest

from conftest import ROOT
from streakbench import spans, trace_reduce

FIXTURES = ROOT / "streakbench" / "fixtures"
LAYERS = ("streak.step", "streak.admit", "streak.scan", "streak.phase1",
          "streak.phase2", "streak.phase3", "streak.topk", "streak.kernel")


def test_no_spans_no_reduction():
    # an untraced run has no trace; the 4-s fixture is a trace of a
    # program without streak.* spans
    assert spans.reduce(None) is None
    assert spans.reduce(FIXTURES / "lgd1m_hot_4s.xplane.pb.gz") is None


def test_innermost_span_holds_each_stretch():
    events = [("A", 0, 10), ("B", 2, 5), ("C", 3, 4), ("D", 6, 8),
              ("E", 12, 14)]
    assert spans._innermost(events, 1, 13) == [
        (1, 2, "A"), (2, 3, "B"), (3, 4, "C"), (4, 5, "B"), (5, 6, "A"),
        (6, 8, "D"), (8, 10, "A"), (10, 12, "none"), (12, 13, "E")]


def test_idle_is_named_by_overlap_not_midpoint():
    # one gap over two spans: its midpoint lies in B, yet A holds 3 of it
    stretches = spans._innermost([("A", 0, 3), ("B", 3, 10)], 0, 12)
    assert spans.overlap(stretches, [(0, 10), (11, 12)]) == {
        "A": 3, "B": 7, "none": 1}


@pytest.fixture(scope="module")
def chip():
    path = FIXTURES / "lgd1m_hot_3s_spans.xplane.pb.gz"
    return spans.reduce(path), trace_reduce.reduce(path)


def test_chip_spans_self_times_add_up(chip):
    """A 3-s traced window of lgd1m.hot recorded on a TPU v5 lite with the
    program's spans (kept: the device's XLA ops and the serve loop's host
    line): the layers' self times and streak.step's own add up to the
    steps' total."""
    r, _ = chip
    assert set(LAYERS) <= set(r.count)
    assert r.steps == r.count["streak.step"] == 92
    assert r.window_s == 2.969921431
    assert r.self_s["streak.kernel"] == pytest.approx(1.551209388, abs=1e-9)
    assert r.self_s["streak.topk"] == pytest.approx(0.293966395, abs=1e-9)
    assert sum(r.self_s.values()) == pytest.approx(
        r.total_s["streak.step"], rel=1e-9)
    for name in r.count:
        assert 0 <= r.self_s.get(name, 0.0) <= r.total_s[name]
    assert r.per_step_ms("streak.step") == pytest.approx(
        1000.0 * r.self_s["streak.step"] / r.steps)


def test_chip_idle_is_all_given_out(chip):
    r, t = chip
    assert r.window_s == pytest.approx(t.window_s, abs=1e-9)
    # every idle nanosecond of the window goes to exactly one span
    assert sum(r.idle_s.values()) == pytest.approx(
        t.window_s - t.busy_s, abs=1e-6)
    assert r.idle_in_program() >= 0.95
    # the host waits for arrivals between bursts, yet most idle time falls
    # in kernel dispatch, which midpoint labels could not show
    assert r.idle_s["bench.wait_arrival"] == pytest.approx(0.692896194,
                                                           abs=1e-9)
    assert r.idle_s["streak.kernel"] == pytest.approx(1.49633992, abs=1e-8)
    assert max(r.idle_s, key=r.idle_s.get) == "streak.kernel"


@pytest.fixture(scope="module")
def cpu_run(tmp_path_factory):
    """A checkout-shaped directory whose profile (where `harness.run` puts
    a traced run's) holds a ``bench.window`` over a small served workload,
    recorded on the CPU, with the counters the served steps added."""
    import dataclasses
    import jax
    from conftest import make_root
    from repro.core.executor import ExecConfig
    from repro.core.policy import BackendPolicy
    from repro.data.synth_rdf import make_lgd
    from repro.serve.spatial import SpatialRequest, SpatialServeEngine

    root = make_root(tmp_path_factory.mktemp("root"))
    lgd = make_lgd(n_per_class=150, seed=0, block=128)
    cfg = ExecConfig(policy=BackendPolicy(descend="kernel", probe="kernel",
                                          rank="kernel"))
    queries = [dataclasses.replace(q, k=k) for q in lgd.queries
               for k in (5, 40)]
    SpatialServeEngine(lgd.store, cfg, max_slots=3).serve(queries)
    srv = SpatialServeEngine(lgd.store, cfg, max_slots=3)
    for rid, q in enumerate(queries):
        srv.submit(SpatialRequest(rid=rid, query=q))
    jax.profiler.start_trace(str(root / spans.TRACE_DIR))
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            before = srv.counters()
            steps = 0
            while srv.step():
                steps += 1
            after = srv.counters()
    finally:
        jax.profiler.stop_trace()
    return root, steps, {k: after[k] - before[k] for k in after}


def test_step_spans_carry_the_counters(cpu_run):
    root, steps, added = cpu_run
    r = spans.reduce(trace_reduce.find_xplane(root / spans.TRACE_DIR))
    # the last call found no slot busy and returned 0: a step all the same
    assert r.steps == steps + 1
    assert added["h2d_bytes"] > 0 and added["share_lookups"] > 0
    assert r.counters == added
    assert r.counter_per_step("h2d_bytes") == added["h2d_bytes"] / r.steps


def test_readers_find_the_traced_runs_profile(cpu_run):
    import types
    from streakbench import harness
    root, _, added = cpu_run
    traced, untraced = (types.SimpleNamespace(trace=object()),
                        types.SimpleNamespace(trace=None))
    read = {n: harness._reader(root, n) for n in (
        "h2d_kb_per_step.lat", "d2h_kb_per_step.tput", "share_hit_pct.lat",
        "kernel_call_ms.tput", "topk_ms.lat")}
    for f in read.values():
        assert f(untraced) is None
    r = spans.of_run(traced, root)
    assert read["h2d_kb_per_step.lat"](traced) == pytest.approx(
        added["h2d_bytes"] / 1000.0 / r.steps)
    assert read["d2h_kb_per_step.tput"](traced) == pytest.approx(
        added["d2h_bytes"] / 1000.0 / r.steps)
    assert read["share_hit_pct.lat"](traced) == pytest.approx(
        100.0 * added["share_hits"] / added["share_lookups"])
    assert read["kernel_call_ms.tput"](traced) > 0
    assert read["topk_ms.lat"](traced) > 0
