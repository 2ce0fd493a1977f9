"""BackendPolicy: resolution, ExecConfig legacy shims, plan stamping, and
the stable public API surface.

The contract under test: every way of naming a backend configuration — the
policy form, the deprecated per-stage ExecConfig kwargs, or nothing at all —
must resolve to the same concrete `BackendPolicy` and produce bit-identical
query results; and `repro.__all__` is a frozen snapshot that only changes
deliberately.
"""
import dataclasses
import warnings

import numpy as np
import pytest

import repro
from repro import BackendPolicy, ExecConfig, StreakEngine
from repro.core.planner import plan_query
from repro.data import synth_rdf


# ------------------------------------------------------------ resolution ----
def test_resolve_pins_autos_and_is_idempotent():
    p = BackendPolicy().resolve()
    assert p.resolved
    assert p.impl == "merge"            # auto impl -> the two-phase core
    assert p.join == "numpy"            # auto Phase-3 join -> dense numpy
    assert p.kcap == "fixed"
    assert p.resolve() == p             # idempotent


def test_auto_join_resolves_to_the_device_route_on_a_tpu(monkeypatch):
    from repro.core import spatial_join
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(ops, "_auto_rank_backend", None)
    assert spatial_join.resolve_join_backend("auto") == "kernel"
    assert spatial_join.resolve_join_backend(None) == "kernel"
    assert BackendPolicy().resolve().join == "kernel"
    assert BackendPolicy(join="numpy").resolve().join == "numpy"


def test_resolve_keeps_explicit_choices():
    p = BackendPolicy(join="fused", impl="looped", rank="interpret",
                      probe="kernel", descend="interpret",
                      kcap="auto").resolve()
    assert p == BackendPolicy(join="fused", impl="looped", rank="interpret",
                              probe="kernel", descend="interpret",
                              kcap="auto")


@pytest.mark.parametrize("field", ["join", "impl", "rank", "probe",
                                   "descend", "kcap"])
def test_resolve_validates_each_stage(field):
    bad = dataclasses.replace(BackendPolicy(), **{field: "no-such-backend"})
    with pytest.raises(ValueError):
        bad.resolve()


# ------------------------------------------------------------ legacy shims --
def test_legacy_knobs_warn_and_fold_into_policy():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = ExecConfig(join_backend="fused", join_impl="looped",
                         probe_backend="kernel", rank_backend="interpret",
                         kcap_auto=True)
    msgs = [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert msgs and "BackendPolicy" in str(msgs[0].message)
    assert cfg.policy.join == "fused"
    assert cfg.policy.impl == "looped"
    assert cfg.policy.probe == "kernel"
    assert cfg.policy.rank == "interpret"
    assert cfg.policy.kcap == "auto"
    # resolved write-back: legacy readers observe concrete backends
    assert cfg.join_backend == "fused" and cfg.join_impl == "looped"
    assert cfg.probe_backend == "kernel" and cfg.rank_backend == "interpret"
    assert cfg.kcap_auto is True


def test_policy_form_does_not_warn():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        cfg = ExecConfig(policy=BackendPolicy(join="fused", kcap="auto"))
        default = ExecConfig()
    assert not [x for x in w if issubclass(x.category, DeprecationWarning)]
    assert cfg.policy.join == "fused" and cfg.kcap_auto is True
    assert default.policy.resolved     # defaults resolve too


def test_legacy_knob_overrides_policy_stage():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cfg = ExecConfig(policy=BackendPolicy(join="kernel"),
                         rank_backend="cpu")
    assert cfg.policy.join == "kernel" and cfg.policy.rank == "cpu"


# --------------------------------------------------------- plan stamping ----
@pytest.fixture(scope="module")
def lgd():
    return synth_rdf.make_lgd(n_per_class=120, seed=3, block=128)


def test_plan_stamps_resolved_backends(lgd):
    plan = plan_query(lgd.store, lgd.queries[0],
                      policy=BackendPolicy(descend="interpret"))
    assert plan.join_impl == "merge"
    assert plan.rank_backend in ("numpy", "kernel")     # resolved, not None
    assert plan.probe_backend in ("numpy", "kernel")
    assert plan.join_backend == "numpy"
    assert plan.descend_backend == "interpret"


# -------------------------------------------- legacy/policy equivalence ----
def test_legacy_and_policy_engines_bit_identical(lgd):
    q = lgd.queries[0]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        legacy = StreakEngine(lgd.store, ExecConfig(
            join_backend="fused", join_impl="merge",
            kcap_auto=True, fused_batch_cols=256)).execute(q)
    pol = StreakEngine(lgd.store, ExecConfig(
        policy=BackendPolicy(join="fused", impl="merge", kcap="auto"),
        fused_batch_cols=256)).execute(q)
    np.testing.assert_array_equal(legacy[0], pol[0])
    assert legacy[1].keys() == pol[1].keys()
    for c in pol[1]:
        np.testing.assert_array_equal(legacy[1][c], pol[1][c])


# ------------------------------------------------------------- public API ---
PUBLIC_API = (
    "BackendPolicy", "ExecConfig", "ExecStats", "FaultPlan", "FaultRule",
    "QuadStore", "Query", "QueryDeadline", "Ranking", "Relation",
    "ShardedQuadStore", "SpatialFilter", "StreakEngine", "TriplePattern",
    "Var", "build_store", "shard_store",
)


def test_public_api_snapshot():
    """`repro.__all__` is the stable surface — additions/removals must be
    deliberate (update this snapshot AND the README when they are)."""
    assert tuple(sorted(repro.__all__)) == PUBLIC_API
    for name in PUBLIC_API:
        assert getattr(repro, name) is not None
    from repro import core
    assert tuple(sorted(core.__all__)) == PUBLIC_API
