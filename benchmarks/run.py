"""Benchmark driver: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. See DESIGN.md §6 for the
paper-artifact -> benchmark index.

``--json`` additionally writes one ``BENCH_<suite>.json`` per suite run
(e.g. ``BENCH_refine.json``, ``BENCH_join.json``, ``BENCH_sip.json``) into
the current directory — the perf trajectory future changes are compared
against. ``python -m benchmarks.run sip --json`` refreshes the Phase 1-2
trajectory after touching the SIP path.
"""
from __future__ import annotations

import json
import sys
import time


def _parse_row(row: str) -> dict:
    name, us, derived = row.split(",", 2)
    return {"name": name, "us_per_call": float(us), "derived": derived}


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
    args = [a for a in sys.argv[1:] if a != "--json"]
    write_json = "--json" in sys.argv[1:]
    only = args[0] if args else None
    print("name,us_per_call,derived")
    for label, mod in suites:
        if only and only not in label and only not in mod.__name__:
            continue
        t0 = time.time()
        rows = []
        for row in mod.run():
            print(row)
            rows.append(row)
        print(f"# {label}: {time.time()-t0:.1f}s", file=sys.stderr)
        if write_json:
            short = mod.__name__.rsplit(".", 1)[-1].removeprefix("bench_")
            path = f"BENCH_{short}.json"
            with open(path, "w") as fh:
                json.dump([_parse_row(r) for r in rows], fh, indent=1)
            print(f"# wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
