#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate whose queue does
not grow across the window. One process builds the cell once, then offers
each rate in turn for --seconds, waiting for the backlog to drain between
rates, and prints one JSON line per rate.

    python3 streakbench/sweep.py --workload lgd1m.hot --seed 7 \
        --seconds 20 --rates 2 4 8 16

A rate holds when, at the window's close, fewer requests are in flight
than the engine has slots, and the requests due in the window's last third
waited no more than twice as long as those due in its first third. The
rate found goes into the cell's traffic file by hand; the benchmark never
searches for one.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from streakbench import harness  # noqa: E402
from streakbench.loop import Loop  # noqa: E402
from streakbench.record import percentile  # noqa: E402


def offer(p, rate: float, seconds: float, seed: int) -> dict:
    mix = p.cell.mix
    n = int(round(rate * seconds))
    rng = np.random.default_rng([seed, int(rate * 1000)])
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    draws = mix.take(seed + int(rate * 1000), 0, n)
    loop = Loop(p.eng, p.make)
    backlog = []
    w = loop.open(draws, list(due), seconds, mix.drain_s,
                  on_close=lambda: backlog.append(len(loop.inflight)))
    lat = [(tr.due - w.t0, tr.finished - tr.due) for tr in w.tracked
           if tr.finished is not None]
    first = [x for d, x in lat if d < seconds / 3]
    last = [x for d, x in lat if d >= 2 * seconds / 3]
    growth = (np.mean(last) / np.mean(first)) if first and last else np.inf
    slots = int(p.cell.config["max_slots"])
    done = sum(tr.finished is not None and tr.finished <= w.t1
               for tr in w.tracked)
    return {"rate_qps": rate, "offered": n,
            "answered_in_window": int(done),
            "answered": len(lat),
            "in_flight_at_close": backlog[0] if backlog else 0,
            "p50_ms": 1000 * percentile([x for _, x in lat], 50),
            "p95_ms": 1000 * percentile([x for _, x in lat], 95),
            "last_over_first_third": float(growth),
            "holds": bool((backlog[0] if backlog else 0) < slots
                          and growth <= 2.0),
            "drain_s": w.end - w.t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    try:
        p = harness.prepare(ROOT, bench, args.workload, args.seed, T_START)
    except harness.CellError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    if p.cell.mix.loop != "open":
        print("sweep: the cell's traffic is not an open loop",
              file=sys.stderr)
        return 2
    harness.log(f"setup: {json.dumps(p.setup)}")
    for rate in sorted(args.rates):
        res = offer(p, rate, args.seconds, args.seed)
        print(json.dumps(res), flush=True)
        if not res["holds"]:          # the rates above would not hold either
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
