"""Spans and counters of the served top-k path.

One profiler trace of `SpatialServeEngine` serving a small LGD-shaped store
through the kernel dispatchers (their jitted CPU routes): every ``streak.*``
span is recorded, nests under the step that ran it, carries its request or
its pooled rows, and the layers' self times add up to the step's time. The
answers do not change with the profiler on, the share cache counts its hits
and misses, and a dispatch counts the bytes it moves.
"""
import collections
import dataclasses
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import fault, spans
from repro.core.executor import ExecConfig
from repro.core.policy import BackendPolicy
from repro.data.synth_rdf import make_lgd
from repro.kernels import ops
from repro.serve.spatial import SpatialServeEngine

NAMES = ("streak.step", "streak.admit", "streak.scan", "streak.phase1",
         "streak.phase2", "streak.phase3", "streak.refine", "streak.topk",
         "streak.kernel")
PER_SLOT = ("streak.admit", "streak.scan", "streak.phase3", "streak.refine",
            "streak.topk")
KERNELS = ExecConfig(policy=BackendPolicy(descend="kernel", probe="kernel",
                                          rank="kernel"))

Span = collections.namedtuple("Span", "name start end meta parent")


@pytest.fixture(scope="module")
def lgd():
    return make_lgd(n_per_class=150, seed=0, block=128)


@pytest.fixture(scope="module")
def tenants(lgd):
    ks = (5, 20, 60, 120)
    return [dataclasses.replace(q, k=ks[i % len(ks)])
            for i, q in enumerate(lgd.queries)]


def _serve(store, queries):
    srv = SpatialServeEngine(store, KERNELS, max_slots=3)
    reqs = srv.serve(queries)
    assert srv.stats.pooled_fallbacks == srv.stats.faults == 0
    return srv, reqs


def _host_spans(path) -> list:
    """The ``streak.*`` events of the trace, each with its enclosing
    ``streak.*`` event on the same host line (or None)."""
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((e.start_ns, -(e.start_ns + e.duration_ns), e)
                          for e in line.events
                          if e.name.startswith("streak.")),
                         key=lambda t: t[:2])
            stack: list = []
            for s, neg_end, e in evs:
                while stack and stack[-1].end <= s:
                    stack.pop()
                sp = Span(e.name, s, -neg_end,
                          {k: str(v) for k, v in list(e.stats)},
                          stack[-1] if stack else None)
                out.append(sp)
                stack.append(sp)
    return out


@pytest.fixture(scope="module")
def traced(lgd, tenants, tmp_path_factory):
    out = tmp_path_factory.mktemp("trace")
    warm, _ = _serve(lgd.store, tenants)    # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    # process-wide, unlike the share cache's
    moved = ("h2d_bytes", "d2h_bytes", "mbr_pairs", "mbr_device_pairs")
    before = warm.counters()
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        srv, reqs = _serve(lgd.store, tenants)
    finally:
        jax.profiler.stop_trace()
    counted = srv.counters()
    for k in moved:
        counted[k] -= before[k]
    return reqs, _host_spans(sorted(out.rglob("*.xplane.pb"))[-1]), counted


def test_span_is_a_profiler_annotation():
    sp = spans.span("streak.step", rid=3)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert not sp.is_enabled()          # no profiler: a flag check
    with sp:
        pass


def test_every_serve_span_is_recorded(traced):
    _, evs, _ = traced
    assert {e.name for e in evs} == set(NAMES)


def test_every_span_nests_under_a_step(traced):
    _, evs, _ = traced
    for e in evs:
        top = e
        while top.parent is not None:
            top = top.parent
        assert top.name == "streak.step", e
        assert (e.parent is None) == (e.name == "streak.step")


def test_spans_carry_their_request_or_their_pool(traced):
    reqs, evs, _ = traced
    rids = {str(r.rid) for r in reqs}
    for e in evs:
        if e.name in PER_SLOT:
            assert e.meta["rid"] in rids, e
    pooled = [e for e in evs if e.name in ("streak.phase1", "streak.phase2")]
    assert pooled and all(int(e.meta["rows"]) >= int(e.meta["slots"]) >= 1
                          for e in pooled)
    kernels = [e for e in evs if e.name == "streak.kernel"]
    assert {e.meta["op"] for e in kernels} >= {"tree_descend",
                                               "merge_join_ranks"}
    for e in kernels:
        assert e.meta["backend"] in ("kernel", "jit"), e
        # the dtype and shape of each array handed to the device
        assert re.fullmatch(r"[a-z0-9]+\[[0-9, ]*\]( [a-z0-9]+\[[0-9, ]*\])*",
                            e.meta["shapes"]), e


def test_self_times_add_up_to_the_step(traced):
    _, evs, _ = traced
    child = collections.Counter()
    for e in evs:
        if e.parent is not None:
            child[id(e.parent)] += e.end - e.start
    steps = [e for e in evs if e.name == "streak.step"]
    self_ns = collections.Counter()
    for e in evs:
        self_ns[e.name] += e.end - e.start - child[id(e)]
    total = sum(e.end - e.start for e in steps)
    assert all(v >= 0 for v in self_ns.values())
    assert sum(self_ns.values()) == pytest.approx(total, rel=0.01)
    assert self_ns["streak.step"] < total


def test_steps_carry_what_they_added_to_the_counters(traced):
    _, evs, counted = traced
    steps = [e for e in evs if e.name == "streak.step"]
    assert counted["h2d_bytes"] > 0 and counted["share_hits"] > 0
    for name, total in counted.items():
        assert sum(int(e.meta[name]) for e in steps) == total, name


def test_answers_are_identical_with_the_profiler_on(lgd, tenants, traced):
    on, _, _ = traced
    _, off = _serve(lgd.store, tenants)
    for a, b in zip(on, off):
        assert a.error is None and b.error is None
        np.testing.assert_array_equal(a.scores, b.scores)
        assert sorted(a.rows.keys()) == sorted(b.rows.keys())
        for c in a.rows.keys():
            np.testing.assert_array_equal(a.rows[c], b.rows[c])


def test_share_cache_counts_hits_and_misses(lgd):
    q = lgd.queries[0]
    srv = SpatialServeEngine(lgd.store, ExecConfig(), max_slots=4)
    srv.serve([dataclasses.replace(q, k=k) for k in (5, 20, 60, 120)])
    sc = srv.engine.share_cache
    # same-shape tenants share their driver blocks and MBR pairs
    assert sc.hits["mat"] > 0 and sc.hits["mbr"] > 0
    assert all(sc.hits[k] <= sc.lookups[k] for k in sc.lookups)
    mbr = (sc.lookups["mbr"], sc.hits["mbr"])
    fresh = dataclasses.replace(
        q, spatial=dataclasses.replace(q.spatial, dist=q.spatial.dist * 1.37))
    srv.serve([fresh])
    # a fresh distance misses every MBR join it looks up
    assert sc.lookups["mbr"] > mbr[0] and sc.hits["mbr"] == mbr[1]
    assert srv.stats.share_evictions == sc.evictions == 0


def test_tree_descend_counts_its_bytes():
    rng = np.random.default_rng(0)
    n, b, m = 37, 3, 5
    node_keys = rng.integers(-2**40, 2**40, (4, n))
    cs = rng.random(n) < 0.5
    box_keys = rng.integers(-2**40, 2**40, (b, m, 4))
    fault.STATE.reset()
    try:
        out = ops.tree_descend(node_keys, cs, box_keys, backend="kernel")
        st = fault.STATE.stats
        bp, mp = 4, 8                  # padded to powers of two
        # hi and lo int32 planes of nodes and boxes, the int32 cs mask
        assert st.h2d_bytes["tree_descend"] == (2 * 4 * n * 4 + n * 4
                                                + 2 * bp * mp * 4 * 4)
        # the (b, n) int32 masks of the real blocks come back
        assert st.d2h_bytes["tree_descend"] == b * n * 4
        assert out.shape == (b, n)
    finally:
        fault.STATE.reset()


def test_fused_join_fetches_only_what_is_asked():
    rng = np.random.default_rng(1)
    m, n, k = 6, 40, 8
    lo = rng.random((m, 2)).astype(np.float32)
    drv = np.concatenate([lo, lo + 0.05], axis=1)
    lo = rng.random((n, 2)).astype(np.float32)
    dvn = np.concatenate([lo, lo + 0.05], axis=1)
    args = (drv, dvn, rng.random(m).astype(np.float32),
            rng.random(n).astype(np.float32), 0.3, -np.inf)
    fault.STATE.reset()
    try:
        scores, idx, counts = ops.fused_topk_join(*args, k=k)
        full = fault.STATE.stats.d2h_bytes["fused_topk_join"]
        # the (m, k) float32 scores, (m, k) int32 indices, (m,) counts
        assert full == m * k * 4 + m * k * 4 + m * 4
        none, idx2, counts2 = ops.fused_topk_join(*args, k=k,
                                                  fetch_scores=False)
        assert none is None
        np.testing.assert_array_equal(idx2, idx)
        np.testing.assert_array_equal(counts2, counts)
        assert isinstance(scores, np.ndarray)
        assert fault.STATE.stats.d2h_bytes["fused_topk_join"] == (
            2 * full - m * k * 4)
    finally:
        fault.STATE.reset()
