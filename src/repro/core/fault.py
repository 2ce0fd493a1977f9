"""Query-engine fault tolerance: failover chains, breakers, deadlines.

The θ bound makes every in-flight top-k query an *anytime* query — the
current TopK heap plus θ is a principled partial answer at any instant —
and every dispatchable op has a bit-identical oracle twin. This module
turns those two facts into a serving-grade degradation story:

- ``run_op``: the failover runner behind every `kernels/ops` dispatch. An
  op call is a chain of (backend, thunk) attempts — kernel → interpret →
  oracle — and on exception, watchdog timeout, or detected corruption the
  next backend runs instead. Backends are bit-identical, so failover never
  changes results.
- ``CircuitBreaker``: per (op, backend) failure memory. N consecutive
  failures open the breaker (the backend is skipped without being tried);
  after a cooldown one half-open probe is allowed, and a success closes it
  again. `BackendPolicy.resolve` consults the breakers (``demote_stage``)
  so *later plans* route around a broken backend at zero per-block cost.
- ``QueryDeadline``: per-query wall-clock (or driver-block) budget. On
  expiry the cursor stops admitting driver blocks and returns the current
  TopK tagged ``partial=True`` with a certified score bound
  (core/executor.QueryCursor).
- ``FaultPlan``: deterministic fault injection at the ops dispatch seam —
  fail op X on call k, delay it past the watchdog, corrupt-then-detect —
  used by tests/test_fault.py to prove bit-identical results under every
  injected failure mode.

The training-loop counterpart is `train/fault.py` (StepGuard /
FailureInjector / run_with_recovery): same philosophy — deadlines, bounded
retries, deterministic injection — applied to the training step instead of
the query block. The serving-layer admission isolation (one tenant's crash
retires only that request) lives in `serve/spatial.py`.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import threading
import time
import zlib

import numpy as np

log = logging.getLogger(__name__)


class InjectedFault(RuntimeError):
    """Raised at the ops dispatch seam by a matching FaultPlan rule."""


class CorruptionDetected(RuntimeError):
    """An op result failed its structural validator (corrupt-then-detect)."""


class OpTimeout(RuntimeError):
    """A guarded op launch overran the watchdog deadline."""


class FallbackExhausted(RuntimeError):
    """Every backend in an op's failover chain failed (or was skipped by an
    open breaker). The serving layer treats this as transient (the breaker
    half-opens after its cooldown) and retries with backoff."""


# exception types the serving layer retries with backoff; anything else is
# treated as a permanent per-request failure (a real bug, a bad query)
TRANSIENT = (InjectedFault, CorruptionDetected, OpTimeout, FallbackExhausted)


# ---------------------------------------------------------------- deadline --
@dataclasses.dataclass
class QueryDeadline:
    """Per-query execution budget: wall-clock seconds, driver blocks, or
    both. The clock starts at construction (for served requests: at
    submission). ``max_blocks`` is the deterministic form tests use."""
    seconds: float | None = None
    max_blocks: int | None = None
    start: float = dataclasses.field(default_factory=time.monotonic)

    def expired(self, blocks: int = 0) -> bool:
        if self.max_blocks is not None and blocks >= self.max_blocks:
            return True
        return (self.seconds is not None
                and time.monotonic() - self.start >= self.seconds)

    @classmethod
    def after(cls, seconds: float) -> "QueryDeadline":
        return cls(seconds=seconds)


# ---------------------------------------------------------- circuit breaker --
@dataclasses.dataclass
class CircuitBreaker:
    """Per (op, backend) failure memory: closed → open → half-open.

    ``threshold`` consecutive failures open the breaker; while open,
    ``allow()`` is False and the backend is skipped without being tried.
    After ``cooldown_s`` one half-open probe is allowed — a success closes
    the breaker, a failure reopens it (and restarts the cooldown).
    """
    threshold: int = 3
    cooldown_s: float = 30.0
    failures: int = 0
    opened_at: float | None = None
    half_open: bool = False

    @property
    def open(self) -> bool:
        """True until a successful call closes the breaker again."""
        return self.opened_at is not None

    def allow(self) -> bool:
        if self.opened_at is None:
            return True
        if time.monotonic() - self.opened_at < self.cooldown_s:
            return False
        if self.half_open:          # one probe per cooldown window
            return False
        self.half_open = True
        return True

    def ok(self) -> None:
        self.failures = 0
        self.opened_at = None
        self.half_open = False

    def fail(self) -> None:
        self.failures += 1
        if self.half_open or self.failures >= self.threshold:
            self.opened_at = time.monotonic()
            self.half_open = False


# -------------------------------------------------------------- fault plan --
@dataclasses.dataclass(frozen=True)
class FaultRule:
    """One deterministic injection: hit `op` on dispatch call `call`
    (0-based per-op counter; None = every call) with `mode`:

    - ``fail``:    raise InjectedFault before the backend runs
    - ``delay``:   sleep ``delay_s`` inside the guarded launch (pairs with
                   the watchdog to exercise the timeout path)
    - ``corrupt``: poison the backend's result so the op's structural
                   validator rejects it (corrupt-then-detect)

    ``attempts`` is how many chain attempts of the matching call are hit:
    1 (default) fails only the primary backend — the chain recovers
    bit-identically; >= the chain length defeats the whole chain so
    FallbackExhausted surfaces to the serving layer's retry path.
    """
    op: str
    call: int | None = None
    mode: str = "fail"
    delay_s: float = 0.0
    attempts: int = 1


@dataclasses.dataclass
class FaultPlan:
    """Deterministic fault injection, hookable at the ops dispatch seam.

    ``rules`` target specific (op, call) coordinates; ``rate`` adds a
    seeded random primary-attempt failure with probability `rate` per
    dispatch (decided by a stable hash of (seed, op, call index), so the
    draw is independent of op interleaving — the same plan injects the
    same faults whether queries run serially or batched).
    """
    rules: tuple = ()
    rate: float = 0.0
    seed: int = 0
    ops: tuple | None = None          # restrict `rate` to these ops
    calls: dict = dataclasses.field(default_factory=dict)   # op -> count
    injected: int = 0

    def begin_call(self, op: str) -> int:
        idx = self.calls.get(op, 0)
        self.calls[op] = idx + 1
        return idx

    def _rate_hit(self, op: str, call: int) -> bool:
        if self.rate <= 0.0 or (self.ops is not None and op not in self.ops):
            return False
        h = zlib.crc32(f"{self.seed}:{op}:{call}".encode())
        return (h / 0xFFFFFFFF) < self.rate

    def action(self, op: str, call: int, attempt: int) -> tuple | None:
        """Injection for attempt `attempt` of dispatch call `call` of `op`:
        None, ("fail",), ("delay", s) or ("corrupt",)."""
        for r in self.rules:
            if r.op == op and (r.call is None or r.call == call) \
                    and attempt < r.attempts:
                self.injected += 1
                return (r.mode, r.delay_s) if r.mode == "delay" else (r.mode,)
        if attempt == 0 and self._rate_hit(op, call):
            self.injected += 1
            return ("fail",)
        return None


# ------------------------------------------------------------ global state --
@dataclasses.dataclass
class FaultStats:
    failures: int = 0             # backend attempts that raised
    timeouts: int = 0             # ... of which watchdog overruns
    corruptions_detected: int = 0  # validator rejections
    fallbacks: int = 0            # successful non-primary attempts
    exhausted: int = 0            # chains with no surviving backend
    breaker_opens: int = 0
    policy_demotions: int = 0     # plan-time reroutes around open breakers
    # successful attempts per (op, backend): which route really ran
    calls: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    # bytes per op that its `kernels/ops` dispatches handed to the device
    # (host arrays uploaded) and brought back (device results fetched)
    h2d_bytes: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    d2h_bytes: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)


class FaultState:
    """Process-global failover state: the installed FaultPlan, the
    per-(op, backend) breakers, and the watchdog deadline. Single-writer
    (the query path is single-threaded); watchdog threads never touch it.
    """

    def __init__(self):
        self.plan: FaultPlan | None = None
        self.watchdog_s: float | None = None
        self.breakers: dict[tuple, CircuitBreaker] = {}
        self.breaker_threshold = 3
        self.breaker_cooldown_s = 30.0
        self.stats = FaultStats()
        self.route: str | None = None   # backend of the last op that ran

    def breaker(self, op: str, backend: str) -> CircuitBreaker:
        key = (op, backend)
        br = self.breakers.get(key)
        if br is None:
            br = self.breakers[key] = CircuitBreaker(
                threshold=self.breaker_threshold,
                cooldown_s=self.breaker_cooldown_s)
        return br

    def reset(self) -> None:
        self.plan = None
        self.watchdog_s = None
        self.breakers.clear()
        self.stats = FaultStats()
        self.route = None


STATE = FaultState()


def install_plan(plan: FaultPlan | None) -> None:
    STATE.plan = plan


@contextlib.contextmanager
def fault_plan(plan: FaultPlan):
    """Install `plan` for the duration of the block (tests)."""
    prev = STATE.plan
    STATE.plan = plan
    try:
        yield plan
    finally:
        STATE.plan = prev


@contextlib.contextmanager
def watchdog(seconds: float | None):
    """Arm the per-launch watchdog for the duration of the block. With no
    watchdog armed (the default) launches run inline at zero overhead."""
    prev = STATE.watchdog_s
    STATE.watchdog_s = seconds
    try:
        yield
    finally:
        STATE.watchdog_s = prev


# ------------------------------------------------------------ failover run --
def _guarded(thunk, watchdog_s: float | None, op: str, backend: str):
    """Run `thunk` under the watchdog. A launch that overruns raises
    OpTimeout and is abandoned (the worker is a daemon thread: a truly hung
    backend no longer stalls the serving loop; a merely-slow one finishes
    into the void — results are discarded, the fallback's are used)."""
    if watchdog_s is None:
        return thunk()
    box: dict = {}

    def work():
        try:
            box["out"] = thunk()
        except Exception as e:      # noqa: BLE001 — relayed below
            box["err"] = e

    t = threading.Thread(target=work, daemon=True,
                         name=f"op-watchdog-{op}-{backend}")
    t.start()
    t.join(watchdog_s)
    if t.is_alive():
        raise OpTimeout(f"{op}/{backend} exceeded {watchdog_s}s watchdog")
    if "err" in box:
        raise box["err"]
    return box["out"]


def _corrupt(out):
    """Poison a result so a structural validator can detect it: the first
    array of the result gets an out-of-domain element 0 (NaN for floats,
    int-min for ints). Only FaultPlan `corrupt` rules call this, and only
    ops with validators should be targeted."""
    arrs = out if isinstance(out, tuple) else (out,)
    first = np.array(np.asarray(arrs[0]))
    flat = first.reshape(-1)
    if len(flat):
        flat[0] = (np.nan if np.issubdtype(first.dtype, np.floating)
                   else np.iinfo(first.dtype).min)
    poisoned = (first,) + tuple(arrs[1:])
    return poisoned if isinstance(out, tuple) else poisoned[0]


def run_op(op: str, attempts: list, validate=None):
    """Run an op through its failover chain.

    `attempts` is the ordered chain [(backend_name, thunk), ...] — every
    backend bit-identical, the last one the always-available oracle. Each
    attempt runs under the watchdog (when armed) and the installed
    FaultPlan's injections; on exception / timeout / validation failure the
    per-(op, backend) breaker records the failure and the next backend
    runs. `validate` is the op's cheap structural check (the
    corrupt-then-detect hook); it runs only under an installed plan so the
    fault-free hot path never pays for it. The first failure of each
    (op, backend) is logged as a warning, so a backend that cannot run on
    this platform (a kernel the compiler refuses, say) does not hide behind
    its bit-identical fallback; `STATE.stats.calls` counts the successes
    and `STATE.route` names the backend of the last one.

    Raises FallbackExhausted when no backend survives.
    """
    st = STATE
    plan = st.plan
    call_idx = plan.begin_call(op) if plan is not None else 0
    last_err = None
    for ai, (backend, thunk) in enumerate(attempts):
        br = st.breakers.get((op, backend)) if st.breakers else None
        if br is not None and not br.allow():
            continue
        try:
            act = plan.action(op, call_idx, ai) if plan is not None else None
            if act is not None and act[0] == "fail":
                raise InjectedFault(
                    f"injected failure: {op}[{call_idx}]/{backend}")
            if act is not None and act[0] == "delay":
                delay = act[1]

                def run(thunk=thunk, delay=delay):
                    time.sleep(delay)
                    return thunk()
            else:
                run = thunk
            out = _guarded(run, st.watchdog_s, op, backend)
            if act is not None and act[0] == "corrupt":
                out = _corrupt(out)
            if validate is not None and plan is not None \
                    and not validate(out):
                st.stats.corruptions_detected += 1
                raise CorruptionDetected(
                    f"{op}/{backend} result failed validation")
            if br is not None:
                br.ok()
            if ai:
                st.stats.fallbacks += 1
            st.stats.calls[(op, backend)] += 1
            st.route = backend
            return out
        except Exception as e:      # noqa: BLE001 — any failure fails over
            if (op, backend) not in st.breakers:   # its first failure
                log.warning("%s: backend %r failed, failing over: %s: %s",
                            op, backend, type(e).__name__, e)
            br = st.breaker(op, backend)
            was_open = br.open
            br.fail()
            if not was_open and br.open:
                st.stats.breaker_opens += 1
            st.stats.failures += 1
            if isinstance(e, OpTimeout):
                st.stats.timeouts += 1
            last_err = e
    st.stats.exhausted += 1
    raise FallbackExhausted(f"every backend failed for {op}") from last_err


# ------------------------------------------------------- policy demotion ----
# Non-oracle backend names per failover-chained op. A breaker open on one of
# these marks the op degraded; breakers on the last-resort oracle/numpy
# fallbacks never demote (there is nothing safer to route to).
_FRAGILE = {"kernel", "interpret", "cpu", "jit", "fused"}

# stage -> {policy backend: (op whose breaker gates it, safe fallback)}
_STAGE_DEMOTIONS = {
    "join": {"fused": ("fused_topk_join", "numpy"),
             "kernel": ("mbr_candidates", "numpy")},
    "rank": {"kernel": ("merge_join_ranks", "numpy"),
             "interpret": ("merge_join_ranks", "numpy"),
             "cpu": ("merge_join_ranks", "numpy")},
    "probe": {"kernel": ("bloom_probe", "numpy"),
              "interpret": ("bloom_probe", "numpy")},
    "descend": {"kernel": ("tree_descend", "numpy"),
                "interpret": ("tree_descend", "numpy")},
}


def op_degraded(op: str) -> bool:
    """Is any non-oracle backend of `op` currently breaker-open?"""
    return any(o == op and b in _FRAGILE and br.open
               for (o, b), br in STATE.breakers.items())


def demote_stage(stage: str, backend: str) -> str:
    """Plan-time reroute: if the op behind a stage's resolved backend is
    breaker-open, resolve to the safe fallback instead — later plans skip
    the broken backend entirely (zero per-block cost). Called from
    `BackendPolicy.resolve`; a clean breaker registry is a no-op."""
    if not STATE.breakers:
        return backend
    ent = _STAGE_DEMOTIONS.get(stage, {}).get(backend)
    if ent is not None and op_degraded(ent[0]):
        STATE.stats.policy_demotions += 1
        return ent[1]
    return backend
