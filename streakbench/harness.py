"""One run of one cell: build, warm up, serve the window, check, report.

Everything that belongs to one configuration, traffic mix or metric is
found by its name in BENCHMARK.json:

- the configuration's ``file`` (JSON) holds its sizes and names its
  generator, ``datagen/<generator>.py`` (a ``rehearsal`` key, the CPU
  tests' cut, is not read here);
- the traffic mix is ``traffic/<traffic>.json``, read by `traffic.Mix`;
- each metric is read by ``metrics/<name>.py``, or, for a name with a
  suffix after a dot (``step_ms.lat``), by ``metrics/<name before the
  dot>.py`` when there is no file of the full name.

The last line of standard output is the result; the numbers compared,
each with its limit, are the last lines of standard error and the last
key of the result.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

import numpy as np

from . import check, reference, traffic, walker
from .datagen.common import relabel
from .loop import Loop, clock
from .record import Record, Request, percentile

BENCH_DIR = Path(__file__).resolve().parent
# limits of the numbers compared; each is an exact comparison
LIMITS = {"wrong_answers": 0, "missing_answers": 0, "kernel_faults": 0}


class CellError(RuntimeError):
    """The cell cannot run here (no chip, or a broken definition)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration's file, parsed
    gen: object            # the configuration's generator module
    mix: traffic.Mix
    metrics: dict          # name -> (entry, reader) for --trace 0 / 1
    traced_metrics: dict


def _reader(root: Path, name: str):
    mdir = root / BENCH_DIR.name / "metrics"
    for stem in (name, name.split(".")[0]):
        path = mdir / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"streakbench_metric_{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise CellError(f"no reader for metric {name!r} under {mdir}")


def _generator(root: Path, name: str):
    """``datagen/<name>.py`` under `root`, loaded as a module of this
    package's ``datagen``, so that its sibling imports (``.common``)
    resolve."""
    path = root / BENCH_DIR.name / "datagen" / f"{name}.py"
    if not path.is_file():
        raise CellError(f"no generator {name!r} under {path.parent}")
    spec = importlib.util.spec_from_file_location(
        f"{BENCH_DIR.name}.datagen.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: Path, bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[w["config"]]["file"]).read_text())
    gen = _generator(root, cfg["generator"])
    mix = traffic.Mix.load(root / BENCH_DIR.name / "traffic"
                           / f"{w['traffic']}.json")
    e2e = {m["name"]: (m, _reader(root, m["name"]))
           for m in bench["end_to_end"] if _reports(m, name)}
    layer = {m["name"]: (m, _reader(root, m["name"]))
             for m in bench["per_layer"] if _reports(m, name)}
    return Cell(name, int(w["chips"]), cfg, gen, mix, e2e, layer)


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise CellError(f"needs a TPU; JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise CellError(f"needs {chips} chips; JAX found {len(devs)}")
    return devs


def _compile_cache(root: Path) -> str:
    """The persistent compilation cache: $JAX_COMPILATION_CACHE_DIR when
    set, else a fixed directory inside the checkout."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root.resolve() / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


class CompileCounter:
    """Programs made (compiled or loaded from the persistent cache) and
    persistent-cache hits, as jax.monitoring reports them."""

    def __init__(self):
        import jax.monitoring as mon
        self.made = self.hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.made += 1

    def _event(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.made, self.hits


class _Tracer:
    """A profiler trace of the window, its span, and the loop's spans."""

    def __init__(self, out: Path):
        import jax
        self.jax = jax
        self.out = out
        self.window = None

    def span(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        shutil.rmtree(self.out, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(str(self.out), profiler_options=opts)
        self.window = self.span("bench.window")
        self.window.__enter__()

    def stop(self) -> None:
        if self.window is not None:
            self.window.__exit__(None, None, None)
            self.window = None
            self.jax.profiler.stop_trace()


def _make_requests(gen, raw, program):
    """rid, spec, k -> a fresh program request; one Query per (spec, k)."""
    queries: dict = {}

    def make(rid: int, spec: dict, k: int):
        key = (json.dumps(spec, sort_keys=True), k)
        if key not in queries:
            queries[key] = program.to_query(gen.query(raw, spec, k))
        return program.SpatialRequest(rid=rid, query=queries[key])
    return make


@dataclasses.dataclass
class Prepared:
    """A cell's deployment built and its engine warmed up."""
    cell: Cell
    devs: list
    cache: str
    compiles: CompileCounter
    program: object         # the benchmark's adapter module
    raw: object
    store: object
    eng: object
    make: object
    setup: dict
    warm_fail: list         # states of warm-up requests not answered
    store_bytes: int


def prepare(root: Path, bench: dict, name: str, seed: int, t_start: float,
            require_tpu: bool = True) -> Prepared:
    """Build cell `name`'s deployment from `seed` and warm its engine up."""
    cell = load_cell(root, bench, name)
    devs = _devices(cell.chips, require_tpu)
    cache = _compile_cache(root)
    compiles = CompileCounter()
    t_jax = clock()
    from . import program

    cfg, gen, mix = cell.config, cell.gen, cell.mix
    setup: dict = {"jax_start_s": t_jax - t_start}
    t = clock()
    raw = relabel(gen.generate(cfg, int(cfg["data_seed"])), seed)
    setup["datagen_s"] = clock() - t
    t = clock()
    store = program.build(raw, cfg)
    setup["store_build_s"] = clock() - t
    program.reset_fault_counters()
    eng = program.engine(store, cfg)
    make = _make_requests(gen, raw, program)
    # warm-up: `warmup_requests` from a stream of their own, as many in
    # flight as the window keeps, so the window's shapes are made
    t = clock()
    clients = mix.clients if mix.loop == "closed" else 2 * int(
        cfg["max_slots"])
    warm = [tr.req for tr in Loop(eng, make).warm(
        mix.take(seed, 1, mix.warmup_requests), clients)]
    setup["warmup_s"] = clock() - t
    t = clock()
    store_bytes = walker.array_bytes(store)
    setup["walk_s"] = clock() - t
    warm_fail = [program.request_state(r) for r in warm
                 if program.request_state(r) != "ok"]
    return Prepared(cell, devs, cache, compiles, program, raw, store, eng,
                    make, setup, warm_fail, store_bytes)


def run(root: Path, bench: dict, name: str, seed: int, seconds: float,
        trace: bool, t_start: float, require_tpu: bool = True,
        control: bool = False) -> dict:
    """One run of cell `name`; returns the result line's object.
    `control` puts the reference in lower precision in the program's place,
    to show that the comparison fails it."""
    p = prepare(root, bench, name, seed, t_start, require_tpu)
    cell, devs, compiles, program = p.cell, p.devs, p.compiles, p.program
    gen, raw, eng, setup = cell.gen, p.raw, p.eng, p.setup
    mix = cell.mix
    made0 = compiles.snapshot()

    tracer = _Tracer(root / ".bench_out" / "trace") if trace else None
    loop = Loop(eng, p.make, span=tracer.span if tracer else None)
    on_close = tracer.stop if tracer else None
    if tracer:
        tracer.start()
    setup["setup_s"] = clock() - t_start
    if mix.loop == "open":
        due = mix.arrivals(seed, seconds)
        w = loop.open(mix.take(seed, 0, len(due)), list(due), seconds,
                      mix.drain_s, on_close=on_close)
    else:
        w = loop.closed(mix.stream(seed, 0), mix.clients, seconds,
                        mix.drain_s, on_close=on_close)
    if tracer:
        tracer.stop()
    made1 = compiles.snapshot()
    faults = program.fault_counters(eng)
    peak = _peak_bytes(devs)
    idmap = program.IdMap.of(p.store)
    requests, answers = [], []
    for tr in w.tracked:
        state = program.request_state(tr.req)
        counters = program.request_counters(tr.req) if state == "ok" else {}
        requests.append(Request(tr.spec, tr.k, tr.due, tr.admitted,
                                tr.finished, state, counters))
        answers.append(program.answer(tr.req, idmap) if state == "ok"
                       else None)
    rec = Record(mix.loop, w.t0, w.t1, requests, w.steps, setup,
                 p.store_bytes, len(raw.quads))
    # how late the generator handed each request over, ms
    late = sorted(1000.0 * (tr.submitted - tr.due) for tr in w.tracked)
    missing = sum(r.state != "ok" for r in requests) + len(p.warm_fail)
    log(f"setup: {json.dumps({k: round(v, 4) for k, v in setup.items()})}")
    log(f"compile cache: {p.cache}")
    # the program's state goes before the reference runs
    del p, eng, loop, w
    log(f"window: {len(requests)} requests, {len(rec.steps)} steps; "
        f"programs made in the window {made1[0] - made0[0]} "
        f"(persistent-cache hits {made1[1] - made0[1]}); "
        f"before it {made0[0]} (hits {made0[1]})")
    if mix.loop == "open" and requests:
        lat = rec.latencies()
        log("latency ms: " + ", ".join(
            f"p{q} {1000.0 * percentile(lat, q):.3f}" for q in (50, 90, 95, 99)))
        log(f"generator: {len(late)} arrivals over {seconds} s, submitted "
            f"after their due time by {late[len(late) // 2]:.1f} ms "
            f"(median), {late[-1]:.1f} ms (most)")
    log(f"launches: {json.dumps(faults['launches'])}")

    t = clock()
    checked = _checked(requests, mix.check_requests, seed)
    wrong, first_wrong, n_ranked = compare(
        gen, raw, [requests[i] for i in checked],
        [answers[i] for i in checked], control)
    ref_s = clock() - t
    kernel_faults = (faults["kernel_failures"] + faults["kernel_fallbacks"]
                     + faults["policy_demotions"] + faults["serve_faults"])
    numbers = {"wrong_answers": wrong, "missing_answers": missing,
               "kernel_faults": kernel_faults}
    correct = all(numbers[n] <= LIMITS[n] for n in LIMITS) and n_ranked > 0
    log(f"reference: {n_ranked} queries ranked, {len(checked)} of "
        f"{sum(a is not None for a in answers)} answers compared "
        f"in {ref_s:.3f} s" + (" (control in the program's place)"
                               if control else ""))
    if first_wrong:
        log(f"first wrong answer: {first_wrong}")

    if tracer:
        from . import trace_reduce
        rec.trace = trace_reduce.reduce(trace_reduce.find_xplane(tracer.out))
    chosen = cell.traced_metrics if trace else cell.metrics
    metrics = {}
    for mname, (entry, read) in chosen.items():
        v = read(rec)
        if v is not None:
            metrics[mname] = {"value": float(v), "unit": entry["unit"]}
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": len(requests),
           "failed": missing + wrong, "metrics": metrics, "device": device}
    if rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = {"device_ops": rec.trace.device_ops,
                            "idle_gaps": rec.trace.idle_gaps}
    out["check"] = {n: {"value": v, "limit": LIMITS[n]}
                    for n, v in numbers.items()}
    for n, v in numbers.items():
        log(f"check: {n} {v} limit {LIMITS[n]}")
    return out


def _checked(requests, n: int, seed: int) -> list:
    """Indices of the answered requests the check compares: all when n is
    0, else n drawn from the seed, the slowest answered request among
    them."""
    ok = [i for i, r in enumerate(requests) if r.state == "ok"]
    if not n or n >= len(ok):
        return ok
    slowest = max(ok, key=lambda i: requests[i].finished - requests[i].due)
    rng = np.random.default_rng([int(seed) % (1 << 63), 3])
    rest = [i for i in ok if i != slowest]
    return sorted([slowest] + [rest[j] for j in
                               rng.choice(len(rest), n - 1, replace=False)])


def compare(gen, raw, requests, answers, control: bool = False):
    """(wrong answers, the first of them, queries ranked): each answer
    against the plain reference's top-k of its query. With `control`, the
    reference in bfloat16 stands in the program's place."""
    ref = reference.Reference(raw)
    ctl = None
    if control:
        import ml_dtypes
        ctl = reference.Reference(raw, coord_dtype=ml_dtypes.bfloat16)
    depth = max((r.k for r in requests), default=0)
    # one query per distinct spec; queries that differ in their distance
    # alone share the reference's join
    queries = {}
    for r, a in zip(requests, answers):
        if a is not None:
            key = json.dumps(r.spec, sort_keys=True)
            queries.setdefault(key, gen.query(raw, r.spec, depth))
    keys = sorted(queries)
    ranked = dict(zip(keys, ref.rank_many([queries[k] for k in keys],
                                          depth)))
    ranked_ctl = {}
    if ctl is not None:
        ranked_ctl = dict(zip(keys, ctl.rank_many(
            [queries[k] for k in keys], depth)))
    wrong, first_wrong = 0, None
    for r, a in zip(requests, answers):
        if a is None:
            continue
        key = json.dumps(r.spec, sort_keys=True)
        if ctl is not None:
            c = ranked_ctl[key]
            n = min(r.k, len(c.scores))
            a = (c.scores[:n], {col: c.rows[:n, i]
                                for i, col in enumerate(c.columns)})
        why = check.same_answer(a[0], a[1], ranked[key], r.k)
        if why is not None:
            wrong += 1
            first_wrong = first_wrong or f"spec {key} k {r.k}: {why}"
    return wrong, first_wrong, len(queries)


def _peak_bytes(devs) -> int:
    try:
        return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
                   for d in devs)
    except (AttributeError, TypeError):       # backend keeps no statistics
        return 0
