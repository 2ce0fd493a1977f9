"""CPU rehearsal of chip_smoke.py: its phases at a tiny size, with every
Pallas kernel of the path running in interpret mode.

The script itself refuses a host without a TPU; these tests call its phase
functions directly. `patch_kernels` steers `kernels/ops` onto its TPU
branch and forces `interpret=True` into the kernels it launches, so the
same dispatch, failover accounting and result checks run as on the chip.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.core import fault, spatial_join  # noqa: E402
from repro.data import synth_rdf  # noqa: E402
from repro.kernels import geom_refine, ops  # noqa: E402


def _interpret(fn):
    def run(*args, **kwargs):
        return fn(*args, **{**kwargs, "interpret": True})
    return run


def patch_kernels(setattr_) -> None:
    """Route ops' TPU branch to the Pallas kernels in interpret mode."""
    setattr_(ops, "_on_tpu", lambda: True)
    setattr_(ops, "_auto_rank_backend", None)
    for mod, name in ((ops._ftj, "fused_topk_join"),
                      (ops._td, "tree_descend"),
                      (ops._bp, "bloom_probe"),
                      (ops._gr, "bucketed_min_core"),
                      (ops._mj, "merge_join_ranks"),
                      (ops._dj, "distance_join")):
        setattr_(mod, name, _interpret(getattr(mod, name)))


# two tenant k per query: kcap 64 keeps the interpreted fused kernel quick
TINY_KS = (5, 40)


def tiny_scale():
    return synth_rdf.make_scale(2_000, seed=0, block=64)


def cpu_loop_core(a_planes, b_planes):
    """The kernel's core as XLA:CPU computes it: the host twin's loop,
    whose multiply-adds XLA:CPU contracts into fused multiply-adds, as it
    does inside the interpret-mode kernel. On the chip the reference is
    `chip_smoke.min_core_numpy`."""
    return geom_refine.bucketed_min_core_host(a_planes, b_planes)


@pytest.fixture
def on_interpreted_chip(monkeypatch):
    patch_kernels(monkeypatch.setattr)
    # the tiny stores' Phase-3 joins reach the device route too
    monkeypatch.setattr(spatial_join, "DEVICE_MIN_PAIRS", 0)
    fault.STATE.reset()
    yield
    fault.STATE.reset()


def test_one_chip_phases_rehearsed(on_interpreted_chip):
    ds = tiny_scale()
    out = chip_smoke.serve_phase(ds, ks=TINY_KS)
    assert out["requests"] == 2 * len(TINY_KS)
    chip_smoke.refine_phase(ds.store, 0, n_pairs=64,
                            reference=cpu_loop_core)
    geo = synth_rdf.make_lgd(n_per_class=80, seed=3, block=64)
    shapes = chip_smoke.shapes_phase(geo)
    assert sorted(shapes) == ["join", "knn", "range", "within"]
    chip_smoke.check_clean(chip_smoke.ONE_CHIP_OPS)
    # the routes that ran are the kernels', each on its first attempt
    calls = fault.STATE.stats.calls
    assert all(calls[(op, "kernel")] > 0 for op in chip_smoke.ONE_CHIP_OPS)


def test_check_clean_fails_on_a_fallback(on_interpreted_chip):
    def broken():
        raise RuntimeError("kernel refused")
    fault.run_op("bloom_probe", [("kernel", broken), ("oracle", lambda: 1)])
    with pytest.raises(chip_smoke.SmokeFailure, match="failover fired"):
        chip_smoke.check_clean(kernel_ops=())


def test_refine_phase_catches_a_wrong_minimum(on_interpreted_chip):
    ds = tiny_scale()

    def off_by_one_ulp(a_planes, b_planes):
        import numpy as np
        core = np.asarray(cpu_loop_core(a_planes, b_planes))
        return np.nextafter(core, np.float32(np.inf))

    with pytest.raises(chip_smoke.SmokeFailure, match="refine/euclid"):
        chip_smoke.refine_phase(ds.store, 0, n_pairs=16,
                                reference=off_by_one_ulp)


def test_shapes_phase_refuses_an_empty_shape(on_interpreted_chip):
    # at this seed no hotel falls inside the range window
    geo = synth_rdf.make_lgd(n_per_class=80, seed=11, block=64)
    with pytest.raises(chip_smoke.SmokeFailure, match="shape range: no rows"):
        chip_smoke.shapes_phase(geo)


_FOUR_CHIPS = """
import sys
sys.path[:0] = [{root!r}, {tests!r}]
import test_chip_smoke as t
t.patch_kernels(setattr)
import jax
assert len(jax.devices()) == 4, jax.devices()
import chip_smoke
chip_smoke.sharded_phase(t.tiny_scale(), n_shards=4, ks=t.TINY_KS)
chip_smoke.check_clean()
print("FOUR_CHIP_PHASE_OK")
"""


def test_four_chip_phase_rehearsed():
    """`--chips 4` on four virtual CPU devices, in a fresh process (the
    device count is fixed when JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    code = _FOUR_CHIPS.format(root=str(ROOT), tests=str(ROOT / "tests"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "FOUR_CHIP_PHASE_OK" in res.stdout


def test_main_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    assert '"ok"' not in out.out


_CACHE = """
import sys
sys.path[:0] = [{src!r}]
import jax, jax.numpy as jnp
from repro.launch.compile_cache import configure_compile_cache
print("CACHE_DIR", configure_compile_cache({root!r}))
jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [False, True])
def test_compile_cache_written_only_where_configured(tmp_path, env_dir):
    root = tmp_path / "repo"
    root.mkdir()
    elsewhere = tmp_path / "elsewhere"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(elsewhere)
    code = _CACHE.format(src=str(ROOT / "src"), root=str(root))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-4000:]
    want = elsewhere if env_dir else root / ".jax_cache"
    assert f"CACHE_DIR {want}" in res.stdout
    assert want.is_dir() and any(want.iterdir())
    other = root / ".jax_cache" if env_dir else elsewhere
    assert not other.exists()
