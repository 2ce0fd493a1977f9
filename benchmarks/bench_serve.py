"""Multi-tenant serving: 8 concurrent lgd queries through the slot-based
admission loop vs serial per-query execution.

Two request mixes bracket the serving layer's win:

- ``hotq``: 8 tenants all running the hot lgd query shape with per-tenant
  ``k`` — the classic serving workload (many users, one popular query).
  Cross-tenant sharing (driver-block materialization, S/N-Plan retrieval,
  pooled+deduped SIP rows, MBR pairs, refine verdicts — all θ-independent,
  hence bit-exact) collapses the redundant per-tenant work; this is the
  headline ≥2x row.
- ``mixed``: the 8 distinct lgd query shapes with mixed ``k`` — no
  cross-tenant redundancy to harvest, so this isolates the pure
  batching/scheduling overhead of the serve loop (must stay ~parity).

And two serial baselines per mix:

- ``serial_perquery``: a fresh StreakEngine per query — the deployment
  without a serving layer (per-request engine instantiation, no shared
  caches, no cross-query batching). This is the headline comparison.
- ``serial_warm``: one shared engine executing the batch back-to-back with
  hot caches — the upper bound a perfectly warmed sequential executor can
  reach without the serving layer's cross-tenant sharing.

Every run asserts per-query results are bit-identical to serial execution.

A third ``faulted`` row (fused config) re-runs the serve batch under a
seeded 1% fault-injection plan at the kernel dispatch seam: the failover
chains must absorb every injected failure with zero result drift, and the
``throughput_ratio_vs_fault_free`` derived metric tracks the recovery
overhead (the acceptance floor is 0.8).

The ``openloop`` section drives the same serve loop open-loop: requests
arrive on a fixed virtual-time schedule at an offered load set as a
fraction of the measured closed-loop capacity (0.5x / 0.8x / 1.2x),
independent of completions, so queueing delay is part of the measured
latency. Per load it reports mean and p50/p95/p99 latency — the 1.2x row
shows the queue growing (p99 >> p50), the 0.5x row the uncongested floor.

Standalone: ``python -m benchmarks.bench_serve`` prints the rows.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro import BackendPolicy, ExecConfig, StreakEngine
from repro.core import fault
from repro.serve.spatial import SpatialRequest, SpatialServeEngine

from . import common

N_CONCURRENT = 8
MAX_SLOTS = 8
KS = (5, 10, 20, 40, 60, 80, 100, 120)   # per-tenant k mix

CONFIGS = {
    "numpy": ExecConfig(),
    "fused": ExecConfig(policy=BackendPolicy(join="fused", kcap="auto")),
}


def _mixes(ds) -> dict:
    return {
        "hotq": [dataclasses.replace(ds.queries[0], k=k) for k in KS],
        "mixed": [dataclasses.replace(q, k=k)
                  for q, k in zip(ds.queries, KS)],
    }


def _assert_identical(reqs, serial) -> None:
    for req, (scores, rows, _) in zip(reqs, serial):
        assert req.done
        np.testing.assert_array_equal(req.scores, scores)
        assert req.rows.n == rows.n


def run() -> list:
    ds = common.dataset("lgd")
    rows = []
    for mname, queries in _mixes(ds).items():
        for cname, cfg in CONFIGS.items():
            # ---- serial baselines ---------------------------------------
            def serial_perquery():
                return [StreakEngine(ds.store, cfg).execute(q)
                        for q in queries]

            serial = serial_perquery()                   # also warms jit
            t_cold = common.timeit(serial_perquery, warmup=0, repeat=3)
            warm_eng = StreakEngine(ds.store, cfg)
            t_warm = common.timeit(
                lambda: [warm_eng.execute(q) for q in queries])

            # ---- serving loop (fresh serve engine per repeat: a batch of
            # 8 arriving tenants, caches shared only within the batch) -----
            def serve_batch():
                srv = SpatialServeEngine(ds.store, cfg, max_slots=MAX_SLOTS)
                return srv, srv.serve(queries)

            srv, reqs = serve_batch()             # warm + correctness check
            _assert_identical(reqs, serial)
            assert srv.stats.slot_reuse >= 0 and srv.stats.sip_batches > 0
            t_srv = common.timeit(lambda: serve_batch()[1])

            qps = N_CONCURRENT / (t_srv / 1e6)
            rows.append(common.row(
                f"serve/lgd/{mname}/{cname}_batched_{N_CONCURRENT}q", t_srv,
                f"speedup_vs_serial_perquery={t_cold / max(t_srv, 1):.2f}x"
                f";speedup_vs_serial_warm={t_warm / max(t_srv, 1):.2f}x"
                f";qps={qps:.1f};bit_identical=true"))
            rows.append(common.row(
                f"serve/lgd/{mname}/{cname}_serial_perquery"
                f"_{N_CONCURRENT}q", t_cold, ""))
            rows.append(common.row(
                f"serve/lgd/{mname}/{cname}_serial_warm"
                f"_{N_CONCURRENT}q", t_warm, ""))

            if cname != "fused":
                continue
            # ---- fault-injected serving: seeded 1% failures at the kernel
            # dispatch seam; failover absorbs them bit-identically and the
            # throughput ratio vs the fault-free run tracks the overhead ---
            def serve_faulted():
                fault.STATE.reset()
                # seed picked so the 1% rate actually lands hits in both
                # mixes' dispatch streams (hotq makes only ~65 op calls)
                fault.install_plan(fault.FaultPlan(rate=0.01, seed=8))
                try:
                    srv = SpatialServeEngine(ds.store, cfg,
                                             max_slots=MAX_SLOTS)
                    reqs = srv.serve(queries)
                    return srv, reqs, fault.STATE.plan.injected
                finally:
                    fault.STATE.reset()

            fsrv, freqs, injected = serve_faulted()
            assert injected > 0, "1% plan must actually fire at bench scale"
            assert all(r.error is None for r in freqs)
            _assert_identical(freqs, serial)
            t_fault = common.timeit(lambda: serve_faulted()[1])
            rows.append(common.row(
                f"serve/lgd/{mname}/{cname}_batched_{N_CONCURRENT}q_faulted",
                t_fault,
                f"injected={injected}"
                f";throughput_ratio_vs_fault_free="
                f"{t_srv / max(t_fault, 1):.2f}"
                f";bit_identical=true"))
    rows += openloop(ds)
    return rows


OPENLOOP_N_REQ = 48
OPENLOOP_LOADS = (0.5, 0.8, 1.2)


def openloop(ds) -> list:
    """Open-loop arrival-rate sweep: latency percentiles vs offered load.

    Arrivals advance on a virtual clock fed by the measured wall time of
    each `step()` call — request i arrives at ``i / offered_qps`` whether
    or not the loop has kept up, so above capacity the queue (and the tail
    latency) grows, which a closed-loop batch bench can never show.
    """
    cfg = CONFIGS["fused"]
    queries = _mixes(ds)["mixed"]

    def batch():
        return SpatialServeEngine(ds.store, cfg,
                                  max_slots=MAX_SLOTS).serve(queries)

    batch()                                            # warm jit caches
    t_batch = common.timeit(batch, warmup=0, repeat=3)
    cap_qps = len(queries) / (t_batch / 1e6)
    rows = [common.row("serve/lgd/openloop/capacity", t_batch,
                       f"closed_loop_qps={cap_qps:.1f}")]
    n = OPENLOOP_N_REQ
    for frac in OPENLOOP_LOADS:
        qps = cap_qps * frac
        arrivals = np.arange(n) / qps                  # virtual seconds
        srv = SpatialServeEngine(ds.store, cfg, max_slots=MAX_SLOTS)
        reqs = [SpatialRequest(rid=i, query=queries[i % len(queries)])
                for i in range(n)]
        now, nxt = 0.0, 0
        done_at: dict[int, float] = {}
        while len(done_at) < n:
            while nxt < n and arrivals[nxt] <= now:
                srv.submit(reqs[nxt])
                nxt += 1
            if not any(srv.slots) and not srv.queue:
                now = arrivals[nxt]                    # idle: jump ahead
                continue
            t0 = time.perf_counter()
            srv.step()
            now += time.perf_counter() - t0
            for r in reqs[:nxt]:
                if r.done and r.rid not in done_at:
                    done_at[r.rid] = now
        assert all(r.error is None for r in reqs)
        lat = np.array([done_at[i] - arrivals[i] for i in range(n)]) * 1e6
        p50, p95, p99 = (np.percentile(lat, p) for p in (50, 95, 99))
        rows.append(common.row(
            f"serve/lgd/openloop/load{frac:g}x", float(lat.mean()),
            f"offered_qps={qps:.1f};p50_us={p50:.0f};p95_us={p95:.0f};"
            f"p99_us={p99:.0f};n_req={n};max_queue={srv.stats.max_queue}"))
    return rows


def main() -> None:
    print("name,us_per_call,derived")
    for r in run():
        print(r)


if __name__ == "__main__":
    main()
