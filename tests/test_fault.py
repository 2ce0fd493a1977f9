"""Fault-tolerance layer: failover chains, breakers, deadlines, isolation.

The load-bearing property mirrors the serving suite's: every backend of
every op is bit-identical, so *any* injected failure — op exceptions,
watchdog timeouts, detected corruption, whole-launch crashes — must leave
engine and serve results exactly equal to a fault-free run. Deadlines trade
completeness for latency instead: a truncated query returns `partial=True`
results whose θ-derived `score_bound` certifiably dominates everything it
left out (verified against the full-scan oracle).
"""
import dataclasses
import logging
import time

import numpy as np
import pytest

from repro.core import fault, spatial_join
from repro.core.baselines import FullScanEngine
from repro.core.executor import ExecConfig, StreakEngine
from repro.core.policy import BackendPolicy
from repro.core.topk import TopK
from repro.data.synth_rdf import make_lgd
from repro.serve.spatial import SpatialRequest, SpatialServeEngine

FaultPlan, FaultRule, QueryDeadline = (fault.FaultPlan, fault.FaultRule,
                                       fault.QueryDeadline)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """Every test starts and ends with no plan, no breakers, no watchdog."""
    fault.STATE.reset()
    yield
    fault.STATE.reset()


@pytest.fixture(scope="module")
def lgd():
    return make_lgd(n_per_class=60, seed=0, block=64)


def _run(lgd, q, policy=None, deadline=None, **cfg):
    if policy is not None:
        cfg["policy"] = policy
    eng = StreakEngine(lgd.store, ExecConfig(fused_batch_cols=256, **cfg))
    return eng.execute(q, deadline=deadline)


def _assert_same(a, b):
    np.testing.assert_array_equal(a[0], b[0])
    assert a[1].keys() == b[1].keys()
    for c in b[1]:
        np.testing.assert_array_equal(a[1][c], b[1][c])


# ------------------------------------------------------- kernel failover ---
# each instrumented op, with a policy whose plan actually dispatches it
OP_CONFIGS = [
    ("mbr_candidates", BackendPolicy(join="kernel")),
    ("fused_topk_join", BackendPolicy(join="fused")),
    ("bucketed_min_core", BackendPolicy()),
    ("merge_join_ranks", BackendPolicy(impl="merge")),
    ("tree_descend", BackendPolicy(descend="kernel")),
    ("bloom_probe", BackendPolicy(probe="kernel")),
]


@pytest.mark.parametrize("op,policy", OP_CONFIGS, ids=[o for o, _ in OP_CONFIGS])
def test_each_op_failing_once_is_bit_identical(lgd, op, policy,
                                               monkeypatch):
    # the tiny store's join blocks would all stay on the host
    monkeypatch.setattr(spatial_join, "DEVICE_MIN_PAIRS", 0)
    q = lgd.queries[0]
    want = _run(lgd, q, policy=policy)
    plan = FaultPlan(rules=(FaultRule(op=op, call=0),))
    with fault.fault_plan(plan):
        got = _run(lgd, q, policy=policy)
    assert plan.injected > 0, f"{op} was never dispatched under {policy}"
    assert fault.STATE.stats.fallbacks > 0
    _assert_same(got, want)


def _fault_warnings(caplog) -> list[str]:
    return [r.getMessage() for r in caplog.records
            if r.name == fault.__name__ and r.levelno == logging.WARNING]


def test_failing_backend_warns_once_per_op_and_backend(lgd, caplog):
    """A backend that fails is logged on its first failure, naming the op,
    the backend and the exception; repeats of that (op, backend) stay quiet
    and every other (op, backend) gets its own warning."""
    q = lgd.queries[0]
    pol = BackendPolicy(descend="kernel", probe="kernel")
    plan = FaultPlan(rules=(FaultRule(op="tree_descend"),
                            FaultRule(op="bloom_probe")))
    with caplog.at_level(logging.WARNING, logger=fault.__name__), \
            fault.fault_plan(plan):
        _run(lgd, q, policy=pol)
        _run(lgd, q, policy=pol)
    assert plan.calls["tree_descend"] >= 2 and plan.calls["bloom_probe"] >= 2
    warned = _fault_warnings(caplog)
    assert len(warned) == 2, warned
    # the CPU's live routes: the descent's jitted oracle is its "kernel"
    # backend, the probe's jnp twin its "jit" backend
    for op, backend in (("tree_descend", "kernel"), ("bloom_probe", "jit")):
        [msg] = [m for m in warned if m.startswith(op)]
        assert f"{backend!r}" in msg and "InjectedFault" in msg


def test_clean_run_logs_nothing_and_counts_routes(lgd, caplog):
    q = lgd.queries[0]
    pol = BackendPolicy(join="fused", descend="kernel", probe="kernel")
    with caplog.at_level(logging.WARNING, logger=fault.__name__):
        _run(lgd, q, policy=pol)
    assert _fault_warnings(caplog) == []
    st = fault.STATE.stats
    assert st.failures == 0 and st.fallbacks == 0
    assert st.calls[("fused_topk_join", "jit")] > 0
    assert st.calls[("tree_descend", "kernel")] > 0
    assert st.calls[("bloom_probe", "jit")] > 0


def test_seeded_random_failure_rate_is_bit_identical(lgd):
    pol = BackendPolicy(join="fused", descend="kernel", impl="merge")
    wants = [_run(lgd, q, policy=pol) for q in lgd.queries[:3]]
    plan = FaultPlan(rate=0.05, seed=3)
    with fault.fault_plan(plan):
        gots = [_run(lgd, q, policy=pol) for q in lgd.queries[:3]]
    assert plan.injected > 0
    for got, want in zip(gots, wants):
        _assert_same(got, want)


def test_corrupt_then_detect_recovers_bit_identical(lgd):
    q = lgd.queries[0]
    pol = BackendPolicy(join="fused")
    want = _run(lgd, q, policy=pol)
    plan = FaultPlan(rules=(FaultRule(op="fused_topk_join", mode="corrupt"),))
    with fault.fault_plan(plan):
        got = _run(lgd, q, policy=pol)
    assert plan.injected > 0
    assert fault.STATE.stats.corruptions_detected > 0
    _assert_same(got, want)


def test_watchdog_timeout_falls_back_bit_identical(lgd, monkeypatch):
    monkeypatch.setattr(spatial_join, "DEVICE_MIN_PAIRS", 0)
    q = lgd.queries[0]
    pol = BackendPolicy(join="kernel")
    want = _run(lgd, q, policy=pol)
    plan = FaultPlan(rules=(
        FaultRule(op="mbr_candidates", call=0, mode="delay",
                  delay_s=0.5),))
    with fault.fault_plan(plan), fault.watchdog(0.05):
        got = _run(lgd, q, policy=pol)
    assert fault.STATE.stats.timeouts > 0
    _assert_same(got, want)


def test_fallback_exhausted_when_every_attempt_fails():
    from repro.kernels import ops
    plan = FaultPlan(rules=(FaultRule(op="bloom_probe", attempts=99),))
    bits = np.zeros((4, 8), np.uint32)
    keys = np.arange(4, dtype=np.int64)
    with fault.fault_plan(plan):
        with pytest.raises(fault.FallbackExhausted):
            ops.bloom_probe(bits, keys)
    assert fault.STATE.stats.exhausted == 1
    # clean chain works again (and closes the breakers it failed)
    assert not ops.bloom_probe(bits, keys).any()


# -------------------------------------------------------- circuit breaker ---
def test_circuit_breaker_state_machine():
    br = fault.CircuitBreaker(threshold=3, cooldown_s=0.05)
    assert br.allow() and not br.open
    br.fail(), br.fail()
    assert br.allow() and not br.open        # under threshold: still closed
    br.fail()
    assert br.open and not br.allow()        # opened, inside cooldown
    time.sleep(0.06)
    assert br.allow()                        # half-open: exactly one probe
    assert not br.allow()
    br.fail()                                # probe failed: reopen + recool
    assert br.open and not br.allow()
    time.sleep(0.06)
    assert br.allow()
    br.ok()                                  # probe succeeded: closed again
    assert not br.open and br.allow()


def test_open_breaker_demotes_policy_resolution():
    from repro.kernels import ops
    node_keys = np.zeros((4, 4), np.int64)
    boxes = np.zeros((1, 2, 4), np.int64)
    cs = np.ones(4, bool)
    plan = FaultPlan(rules=(FaultRule(op="tree_descend", attempts=99),))
    with fault.fault_plan(plan):
        for _ in range(fault.STATE.breaker_threshold):
            with pytest.raises(fault.FallbackExhausted):
                ops.tree_descend(node_keys, cs, boxes, backend="kernel")
    assert fault.STATE.breaker("tree_descend", "kernel").open
    # plan-time reroute: later plans skip the broken backend entirely
    assert BackendPolicy(descend="kernel").resolve().descend == "numpy"
    assert fault.STATE.stats.policy_demotions > 0
    # untouched stages resolve as requested
    assert BackendPolicy(probe="kernel").resolve().probe == "kernel"
    fault.STATE.reset()
    assert BackendPolicy(descend="kernel").resolve().descend == "kernel"


def test_open_mbr_breaker_demotes_the_join_stage():
    """The join stage's `kernel` route rides on `mbr_candidates`: a breaker
    open there reroutes later plans to the numpy join."""
    from repro.kernels import ops
    boxes = np.zeros((2, 4))
    plan = FaultPlan(rules=(FaultRule(op="mbr_candidates", attempts=99),))
    with fault.fault_plan(plan):
        for _ in range(fault.STATE.breaker_threshold):
            with pytest.raises(fault.FallbackExhausted):
                ops.mbr_candidates(boxes, boxes, 0.1)
    assert fault.STATE.breaker("mbr_candidates", "jit").open
    assert BackendPolicy(join="kernel").resolve().join == "numpy"
    assert fault.STATE.stats.policy_demotions > 0
    fault.STATE.reset()
    assert BackendPolicy(join="kernel").resolve().join == "kernel"


# ----------------------------------------------------- deadlines / anytime --
def _oracle_all(lgd, q):
    """Every result's key (not just top-k), via the full-scan oracle."""
    scores, _, _ = FullScanEngine(lgd.store).execute(
        dataclasses.replace(q, k=10 ** 7))
    return scores if q.ranking.descending else -scores


def test_deadline_block_budget_returns_certified_partial(lgd):
    q = dataclasses.replace(lgd.queries[0], k=120)
    scores, rows, stats = _run(lgd, q, deadline=QueryDeadline(max_blocks=1))
    assert stats.partial and stats.deadline_expired
    assert stats.driver_blocks == 1
    assert stats.score_bound is not None
    assert rows.n == len(scores) < 120      # genuinely truncated
    # certification: every result OUTSIDE the returned set has a key at or
    # below the bound (exact multiset difference — both engines accumulate
    # identical f64 keys)
    keys = scores if q.ranking.descending else -scores
    leftover = list(np.sort(_oracle_all(lgd, q))[::-1])
    for k in np.sort(keys)[::-1]:
        leftover.remove(k)                  # raises if not a true result
    if leftover:
        assert max(leftover) <= stats.score_bound


def test_deadline_already_expired_returns_empty_partial(lgd):
    q = lgd.queries[0]
    dl = QueryDeadline(seconds=0.0)
    scores, rows, stats = _run(lgd, q, deadline=dl)
    assert stats.partial and len(scores) == 0 and rows.n == 0
    # nothing returned: the bound must dominate EVERY result
    assert _oracle_all(lgd, q).max() <= stats.score_bound


def test_no_deadline_complete_run_unchanged(lgd):
    q = lgd.queries[0]
    scores, _, stats = _run(lgd, q, deadline=QueryDeadline(max_blocks=10 ** 6))
    want, _, wstats = _run(lgd, q)
    np.testing.assert_array_equal(scores, want)
    assert not stats.partial and not stats.deadline_expired
    # a complete run's bound is the final θ
    assert stats.score_bound == wstats.score_bound


def test_serve_deadline_tenant_partial_others_exact(lgd):
    qs = [dataclasses.replace(q, k=40) for q in lgd.queries[:4]]
    serial = [_run(lgd, q) for q in qs]
    srv = SpatialServeEngine(lgd.store, ExecConfig(), max_slots=2)
    reqs = [SpatialRequest(rid=i, query=q) for i, q in enumerate(qs)]
    reqs[1].deadline = QueryDeadline(max_blocks=1)
    for r in reqs:
        srv.submit(r)
    srv.run()
    assert all(r.done and r.error is None for r in reqs)
    assert reqs[1].stats.partial
    assert srv.stats.deadline_partials == 1
    for i in (0, 2, 3):
        np.testing.assert_array_equal(reqs[i].scores, serial[i][0])


# --------------------------------------------------- serve crash isolation --
def _serve(lgd, queries, **kw):
    cfg = ExecConfig(policy=BackendPolicy(join="fused"), fused_batch_cols=256)
    srv = SpatialServeEngine(lgd.store, cfg, max_slots=3, **kw)
    return srv, srv.serve(queries)


def test_serve_transient_fault_retries_bit_identical(lgd):
    qs = [dataclasses.replace(q, k=30) for q in lgd.queries[:4]]
    # probe run: an empty plan's per-op counters reveal how many dispatches
    # the clean serve makes, so the injected call index is always mid-serve
    probe = FaultPlan()
    with fault.fault_plan(probe):
        _, clean = _serve(lgd, qs)
    ncalls = probe.calls.get("fused_topk_join", 0)
    assert ncalls >= 2, "serve run never reached the fused join"
    fault.STATE.reset()
    # defeat the whole chain on one mid-serve dispatch: FallbackExhausted
    # surfaces to the slot loop, the riders restart from fresh cursors
    plan = FaultPlan(rules=(
        FaultRule(op="fused_topk_join", call=ncalls // 2, attempts=99),))
    with fault.fault_plan(plan):
        srv, reqs = _serve(lgd, qs)
    assert plan.injected > 0
    assert srv.stats.faults >= 1 and srv.stats.retries >= 1
    assert all(r.done and r.error is None for r in reqs)
    for req, want in zip(reqs, clean):
        np.testing.assert_array_equal(req.scores, want.scores)
        assert req.rows.n == want.rows.n


def test_serve_retries_exhausted_surfaces_error_and_terminates(lgd):
    qs = [dataclasses.replace(q, k=30) for q in lgd.queries[:3]]
    plan = FaultPlan(rules=(
        FaultRule(op="fused_topk_join", attempts=99),))   # every call dies
    with fault.fault_plan(plan):
        srv, reqs = _serve(lgd, qs, max_retries=1)
    assert all(r.done for r in reqs)                      # loop terminated
    assert all(isinstance(r.error, fault.TRANSIENT) for r in reqs)
    assert all(len(r.scores) == 0 for r in reqs)
    assert srv.stats.failed_requests == len(qs)
    assert srv.stats.retries >= 1


def test_admission_failure_surfaces_not_drops(lgd):
    good = [dataclasses.replace(q, k=20) for q in lgd.queries[:2]]
    bad = dataclasses.replace(good[0], spatial=None)      # cursor ctor raises
    serial = [_run(lgd, q) for q in good]
    srv = SpatialServeEngine(lgd.store, ExecConfig(), max_slots=2)
    reqs = srv.serve([good[0], bad, good[1]])
    assert all(r.done for r in reqs)
    assert reqs[1].error is not None and len(reqs[1].scores) == 0
    assert srv.stats.admission_failures == 1
    for req, want in zip((reqs[0], reqs[2]), serial):
        np.testing.assert_array_equal(req.scores, want[0])


def test_stream_entry_fault_isolates_one_rider():
    from repro.core.spatial_join import StreamEntry, fused_stream_join_multi
    rng = np.random.default_rng(9)

    def boxes(n):
        lo = rng.random((n, 2))
        return np.concatenate([lo, lo + 0.03 * rng.random((n, 2))], axis=1)

    drv, dvn = boxes(30), boxes(120)
    dk, vk = rng.random(30), rng.random(120)
    acc: list = []

    def boom(pi, pj):
        raise RuntimeError("tenant bug")

    entries = [
        StreamEntry(drv, dvn, dk, vk, 0.4, 8, theta_fn=lambda: -np.inf,
                    emit=boom),
        StreamEntry(drv, dvn, dk, vk, 0.4, 8, theta_fn=lambda: -np.inf,
                    emit=lambda pi, pj: acc.append((pi, pj))),
    ]
    fused_stream_join_multi(entries, batch_cols=64)
    assert isinstance(entries[0].error, RuntimeError)     # faulted rider
    assert entries[1].error is None and acc               # survivor emitted


# ------------------------------------------------ TopK anytime θ property ---
def test_topk_theta_bounds_every_dropped_score():
    """Backbone of the anytime guarantee: at ANY truncation point, θ is a
    valid upper bound on every score the heap has seen and dropped."""
    from repro.core.join import Relation
    rng = np.random.default_rng(11)
    topk = TopK(k=12, descending=True)
    seen: list = []
    for step in range(30):
        batch = rng.normal(size=rng.integers(1, 9)) * 10
        rows = Relation({"r": np.arange(len(batch))})
        topk.push(batch, rows)
        seen.extend(batch.tolist())
        kept, _ = topk.results()
        assert len(kept) == min(len(seen), 12)
        dropped = list(np.sort(seen))
        for s in kept:                       # exact multiset difference
            dropped.remove(s)
        if not topk.full:
            assert topk.theta == -np.inf and not dropped
        elif dropped:
            assert max(dropped) <= topk.theta
            # and θ is attained, not loose: it IS the k-th kept score
            assert topk.theta == min(kept)
