#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 streakbench/run.py --workload <cell> --seed <n> \
        --seconds <window> --trace <0|1>

The cells, configurations, traffic mixes and metrics are those of
BENCHMARK.json at the root of the checkout. The run builds the cell's
deployment from the seed, warms up, serves the traffic for the window,
checks its answers (all, or as many as the traffic mix's
`check_requests`, drawn from the seed) against the plain reference, and
prints one JSON line last on standard output. With --trace 1 it reports the per-layer metrics
from a profiler trace of the window; with --trace 0 the end-to-end ones.
It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from streakbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the bfloat16 reference in the program's "
                         "place (the comparison has to fail it)")
    args = ap.parse_args(argv)
    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.is_file():
        print(f"run: no {bench_file}", file=sys.stderr)
        return 2
    try:
        out = harness.run(ROOT, json.loads(bench_file.read_text()),
                          args.workload, args.seed, args.seconds,
                          bool(args.trace), T_START, control=args.control)
    except harness.CellError as e:
        print(f"run: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
