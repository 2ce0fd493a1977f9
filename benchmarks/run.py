"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; each section's label names
the paper artifact it stands for. These are timings of whatever backend
JAX finds; the chip benchmark is ``streakbench/`` (see PERF.md).
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from pathlib import Path

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache(Path(__file__).resolve().parents[1])
    from . import (bench_aps, bench_engines, bench_geo, bench_join,
                   bench_kernels, bench_refine, bench_serve, bench_sip,
                   bench_sizes, bench_vary_k)
    suites = [
        ("table1/3 sizes", bench_sizes),
        ("fig7 SIP", bench_sip),
        ("fig8 join algorithms", bench_join),
        ("fig9 APS", bench_aps),
        ("fig10/11 engines", bench_engines),
        ("fig12 vary k", bench_vary_k),
        ("refinement", bench_refine),
        ("kernels", bench_kernels),
        ("serving", bench_serve),
        ("geographica shapes", bench_geo),
    ]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    for label, mod in suites:
        if only and only not in label and only not in mod.__name__:
            continue
        t0 = time.time()
        for row in mod.run():
            print(row)
        print(f"# {label}: {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
