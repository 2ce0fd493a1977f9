#!/usr/bin/env python3
"""Smoke run of the STREAK serve path on a TPU, through its public entry points.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py [--seed S] [--quads N]   # one chip
    python chip_smoke.py --chips 4 [--seed S]      # the sharded store, four chips

One chip: builds an LGD-shaped store of ~1M quads (`synth_rdf.make_scale`),
serves its two top-k queries at eight per-tenant k each through
`SpatialServeEngine` (8 slots, the Pallas kernels pinned for join, descent,
probe and rank), and checks every result against the same queries run with
the all-numpy policy. It checks the exact-refinement kernel's minima
against numpy, and runs the four Geographica shapes (range, within, kNN,
join) on the Geographica suite's large lgd dataset against `FullScanEngine`,
their Phase-3 joins on the device route.

Four chips: shards the same store over a 4-device `make_shard_mesh`, runs
the queries through the sharded descent (`shard_map`), and compares them
with the unsharded run on one device in the same process.

Every run fails unless the failover chains stayed silent: no failed,
fallen-back or demoted kernel call, no serve-loop fault, no errored
request. Each kernel of the path must report at least one launch. The last
line of stdout is the JSON result; with no TPU, or on any failed check, the
script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import numpy as np  # noqa: E402

from benchmarks import bench_geo  # noqa: E402
from repro import BackendPolicy, ExecConfig, StreakEngine  # noqa: E402
from repro.core import fault, spatial_join  # noqa: E402
from repro.core.baselines import FullScanEngine  # noqa: E402
from repro.core.shard import shard_store  # noqa: E402
from repro.data import synth_rdf  # noqa: E402
from repro.serve.spatial import SpatialServeEngine  # noqa: E402

KS = (5, 10, 20, 40, 60, 80, 100, 120)     # per-tenant k (bench_serve.KS)
MAX_SLOTS = 8
KERNELS = BackendPolicy(join="fused", descend="kernel", probe="kernel",
                        rank="kernel")
NUMPY = BackendPolicy(join="numpy", descend="numpy", probe="numpy",
                      rank="numpy")
# the shapes' Phase-3 joins take the device route, the TPU's default
SHAPES = dataclasses.replace(KERNELS, join="kernel")
# ops whose Pallas kernel must have run, and on one chip the shapes'
# device join besides
KERNEL_OPS = ("fused_topk_join", "tree_descend", "bloom_probe",
              "merge_join_ranks", "bucketed_min_core")
ONE_CHIP_OPS = KERNEL_OPS + ("mbr_candidates",)


class SmokeFailure(RuntimeError):
    """A result differed from its reference, or a fallback fired."""


def _log(msg: str) -> None:
    print(msg, flush=True)


def _assert_same(what: str, got, want) -> None:
    """(scores, rows) pairs must be equal, every row column included."""
    gs, grows = got[0], got[1]
    ws, wrows = want[0], want[1]
    if not np.array_equal(gs, ws):
        raise SmokeFailure(f"{what}: scores differ ({len(gs)} vs {len(ws)})")
    if sorted(grows.keys()) != sorted(wrows.keys()):
        raise SmokeFailure(f"{what}: row columns differ")
    for c in wrows.keys():
        if not np.array_equal(grows[c], wrows[c]):
            raise SmokeFailure(f"{what}: rows differ in column {c!r}")


def tenant_queries(ds, ks=KS) -> list:
    """Each top-k query of the dataset once per tenant k."""
    return [dataclasses.replace(q, k=k) for q in ds.queries for k in ks]


def serve_phase(ds, ks=KS) -> dict:
    """The served top-k path against the all-numpy serial reference."""
    queries = tenant_queries(ds, ks)
    t0 = time.perf_counter()
    ref = StreakEngine(ds.store, ExecConfig(policy=NUMPY))
    want = [ref.execute(q) for q in queries]
    _log(f"serve: numpy reference, {len(queries)} queries: "
         f"{time.perf_counter() - t0:.3f} s")
    out = {"requests": len(queries)}
    for label in ("compile+run", "run"):
        srv = SpatialServeEngine(ds.store, ExecConfig(policy=KERNELS),
                                 max_slots=MAX_SLOTS)
        t0 = time.perf_counter()
        reqs = srv.serve(queries)      # results come back as host arrays
        dt = time.perf_counter() - t0
        st = srv.stats
        _log(f"serve: {label}: {dt:.3f} s for {len(reqs)} requests, "
             f"{st.steps} steps, {st.join_launches} shared join launches")
        errored = sum(r.error is not None for r in reqs)
        if errored or st.faults or st.pooled_fallbacks:
            raise SmokeFailure(
                f"serve: {errored} errored requests, {st.faults} slot "
                f"faults, {st.pooled_fallbacks} pooled fallbacks")
        for r, w in zip(reqs, want):
            _assert_same(f"serve request {r.rid} (k={r.query.k})",
                         (r.scores, r.rows), w)
        out[label] = dt
    _log(f"serve: all {len(queries)} results equal the numpy reference")
    return out


def min_core_numpy(a_planes, b_planes) -> np.ndarray:
    """The refine kernel's per-pair cores in numpy float32: for each pair
    row, the min over all point pairs of ``sum_d (a_d - b_d)²``, each
    product and sum rounded on its own (IEEE, no fused multiply-add)."""
    v = None
    for ad, bd in zip(a_planes, b_planes):
        d = ad[:, :, None] - bd[:, None, :]
        v = d * d if v is None else v + d * d
    return v.min(axis=(1, 2))


def refine_phase(store, seed: int, n_pairs: int = 2048,
                 reference=min_core_numpy) -> dict:
    """Exact-refinement minima (the bucketed kernel) against `reference`,
    for both metrics' planes, on random pairs of single-bucket geometries.
    Every engine route refines through this kernel, the numpy policy and
    FullScanEngine included, so the query comparisons cannot see it."""
    pool = store.geom_pool
    cnt = pool.counts(np.arange(pool.n_entities))
    width = int(np.bincount(cnt).argmax())        # the commonest size
    rows = np.flatnonzero(cnt == width)
    rng = np.random.default_rng(seed)
    rows_a = rng.choice(rows, n_pairs)
    rows_b = rng.choice(rows, n_pairs)
    pts = np.arange(width)
    out = {}
    for metric, planes in (("euclid", pool.planes2d()),
                           ("haversine", pool.planes3d())):
        t0 = time.perf_counter()
        got = spatial_join.pool_min_dist(pool, rows_a, rows_b, metric)
        dt = time.perf_counter() - t0
        ia = pool.offsets[rows_a][:, None] + pts
        ib = pool.offsets[rows_b][:, None] + pts
        core = reference(tuple(p[ia] for p in planes),
                         tuple(p[ib] for p in planes))
        want = spatial_join.core_to_dist(np.asarray(core), metric)
        bad = np.flatnonzero(got != want)
        if len(bad):
            raise SmokeFailure(
                f"refine/{metric}: {len(bad)} of {n_pairs} minima differ "
                f"from the reference, e.g. {got[bad[:3]]} vs {want[bad[:3]]}")
        _log(f"refine: {metric}: {n_pairs} pair minima of {width}-point "
             f"geometries equal the reference ({dt:.3f} s incl. compile)")
        out[metric] = dt
    return out


def shapes_phase(ds) -> dict:
    """The four Geographica shapes against the brute-force reference."""
    eng = StreakEngine(ds.store, ExecConfig(policy=SHAPES))
    oracle = FullScanEngine(ds.store)
    out = {}
    for shape, q in bench_geo.queries(ds.ns):
        t0 = time.perf_counter()
        eng.execute(q)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        got = eng.execute(q)
        warm = time.perf_counter() - t0
        _assert_same(f"shape {shape}", got, oracle.execute(q))
        if not len(got[0]):
            raise SmokeFailure(f"shape {shape}: no rows, nothing compared")
        _log(f"shape {shape}: {len(got[0])} rows equal FullScanEngine; "
             f"compile+run {cold:.3f} s, run {warm:.3f} s")
        out[shape] = (cold, warm)
    return out


def sharded_phase(ds, n_shards: int = 4, ks=KS) -> dict:
    """The Morton-prefix sharded store over an `n_shards`-device mesh
    against the unsharded store on one device, same queries, same process.
    The descent must have taken the shard_map route."""
    from repro.launch.mesh import make_shard_mesh

    mesh = make_shard_mesh(n_shards)
    if mesh.devices.size != n_shards:
        raise SmokeFailure(f"shard mesh spans {mesh.devices.size} devices, "
                           f"not {n_shards}")
    t0 = time.perf_counter()
    sharded = shard_store(ds.store, n_shards=n_shards)
    _log(f"shard: {len(sharded.tree_shards)} shards built in "
         f"{time.perf_counter() - t0:.3f} s over mesh "
         f"{[str(d) for d in mesh.devices.flat]}")
    queries = tenant_queries(ds, ks)
    single = StreakEngine(ds.store, ExecConfig(policy=KERNELS))
    t0 = time.perf_counter()
    want = [single.execute(q) for q in queries]
    _log(f"shard: unsharded, one device: {time.perf_counter() - t0:.3f} s "
         f"for {len(queries)} queries")
    multi = StreakEngine(sharded, ExecConfig(policy=KERNELS))
    out = {}
    for label in ("compile+run", "run"):
        t0 = time.perf_counter()
        got = [multi.execute(q) for q in queries]
        out[label] = time.perf_counter() - t0
        _log(f"shard: {n_shards} shards: {label}: {out[label]:.3f} s")
        for q, g, w in zip(queries, got, want):
            _assert_same(f"sharded query k={q.k}", g, w)
    calls = fault.STATE.stats.calls
    if not calls.get(("tree_descend_sharded", "shard_map")):
        raise SmokeFailure("the sharded descent never ran over the mesh")
    _log(f"shard: all {len(queries)} results equal the unsharded run")
    return out


def check_clean(kernel_ops=KERNEL_OPS) -> None:
    """Fail unless every kernel call succeeded on its first route."""
    st = fault.STATE.stats
    launches = {f"{op}/{b}": n for (op, b), n in sorted(st.calls.items())}
    _log(f"kernel launches: {json.dumps(launches)}")
    if st.failures or st.fallbacks or st.policy_demotions:
        raise SmokeFailure(
            f"failover fired: {st.failures} failures, {st.fallbacks} "
            f"fallbacks, {st.policy_demotions} policy demotions")
    idle = [op for op in kernel_ops if not st.calls.get((op, "kernel"))]
    if idle:
        raise SmokeFailure(f"no kernel launch recorded for {idle}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quads", type=int, default=1_000_000,
                    help="size of the make_scale store (default 1M)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-store phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import configure_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    cache = configure_compile_cache(ROOT)
    _log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
         f"jax {jax.__version__}; compile cache {cache}")
    fault.STATE.reset()
    t_all = time.perf_counter()
    failures = []

    def phase(fn, *a, **kw):
        # a failed check ends its phase, not the run: one run reports all
        try:
            fn(*a, **kw)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
            failures.append(str(e))

    t0 = time.perf_counter()
    ds = synth_rdf.make_scale(args.quads, seed=args.seed)
    _log(f"data: make_scale({args.quads}, seed={args.seed}): "
         f"{ds.store.n_quads} quads, {ds.store.tree.n_objects} spatial "
         f"entities, {ds.store.tree.n_nodes} tree nodes, "
         f"{ds.store.nbytes()} bytes stored, built in "
         f"{time.perf_counter() - t0:.3f} s")
    if args.chips == 4:
        phase(sharded_phase, ds, n_shards=4)
    else:
        phase(serve_phase, ds)
        phase(refine_phase, ds.store, args.seed)
        t0 = time.perf_counter()
        # the Geographica suite's own large-scale data: a fixed seed, on
        # which every shape has rows to compare
        geo = bench_geo.dataset(6000)
        _log(f"data: bench_geo.dataset(6000): {geo.store.n_quads} quads, "
             f"built in {time.perf_counter() - t0:.3f} s")
        phase(shapes_phase, geo)
        phase(refine_phase, geo.store, args.seed)
    phase(check_clean, ONE_CHIP_OPS if args.chips == 1 else KERNEL_OPS)
    _log(f"total: {time.perf_counter() - t_all:.3f} s")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
