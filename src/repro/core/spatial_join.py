"""The block spatial join: Phases 1-3 + refinement (paper §3.2).

Phase 1 (candidate nodes) lives on SQuadTree.candidate_nodes; Phase 2 is
node_select.select + SIP filter material; this module is Phase 3 — the
pairwise MBR distance join between a driver block and the SIP-filtered driven
candidates — plus the exact-geometry refinement step.

The MBR join is the compute hot spot. Three backends:

- ``numpy``  — dense float64 broadcast via geometry.box_min_dist; the
  portable fallback and the oracle for tests.
- ``kernel`` — the device forms the candidate list (kernels/ops.py
  `mbr_candidates`: a float32 test widened to a superset, compacted on
  the device) and the host rechecks only those candidates in float64, so
  the pairs equal numpy's element for element. Blocks of fewer than
  `DEVICE_MIN_PAIRS` pairs stay on the numpy broadcast, which costs less
  there than a dispatch. The "auto" choice on a TPU.
- ``fused``  — the streaming top-k kernel (kernels/fused_topk_join.py):
  driven entities are fed in score-key order, each column batch is reduced
  in VMEM to per-row top-k partials under the current top-k threshold θ, and
  the (M, N) matrix never exists. `fused_stream_join` below is the driver:
  it re-reads θ between batches (so early termination prunes *inside* an
  executor block) and recovers overflowing rows densely so the candidate
  set stays exact.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import geometry, topk as topk_mod

# Phase-3 MBR-join backend registry (see module docstring). "auto" resolves
# to the device route on a TPU and to the dense numpy broadcast elsewhere;
# the fused path only wins with real score keys + a live θ, which the
# executor supplies explicitly when configured.
JOIN_BACKENDS = ("auto", "numpy", "kernel", "fused")

# The "kernel" route's cut-over, in block-product pairs: a device join
# costs about 3.9 ms however small the block, the numpy broadcast about
# 10.9 ns a pair below a million pairs (TPU v5e host; PERF.md §6).
DEVICE_MIN_PAIRS = 360_000


def resolve_join_backend(backend: str | None) -> str:
    b = backend or "auto"
    if b not in JOIN_BACKENDS:
        raise ValueError(f"unknown spatial join backend {b!r}")
    if b != "auto":
        return b
    from ..kernels import ops
    return "kernel" if ops._on_tpu() else "numpy"


@dataclasses.dataclass
class PairCounters:
    """Block-product pairs `mbr_distance_join` tested since the process
    started, and how many of them the device tested."""
    tested: int = 0
    on_device: int = 0


PAIRS = PairCounters()


@dataclasses.dataclass
class JoinStats:
    candidates: int = 0       # MBR-level candidate pairs emitted
    refined: int = 0          # pairs surviving exact refinement
    pairs_tested: int = 0     # full MBR pairs evaluated (block product)
    refine_skipped: int = 0   # candidate pairs never refined (θ-aware skip)
    overflow_rows: int = 0    # driver rows recovered densely (partial width
    #                           overflow in the fused kernel)
    overflow_batches: int = 0  # column batches with >= 1 overflowing row


@dataclasses.dataclass
class KcapTuner:
    """EWMA autotuner for the fused kernel's per-row partial width.

    The fixed ``min(max(k, 64), batch_cols)`` floor pays worst-case partial
    widths on every launch even when θ has tightened enough that almost no
    pairs survive. The tuner tracks an EWMA of the observed per-launch MAX
    survivor count and suggests ``headroom`` times that, quantized to the
    next power of two (bounding jit recompiles to one per pow2 class) and
    clamped to ``[max(k, floor), min(ceiling, batch_cols)]``. Undershooting
    a survivor burst is *safe* — overflowing rows are recovered densely by
    the caller (see fused_stream_join) — it only costs recompute, which
    JoinStats.overflow_* makes observable.
    """
    alpha: float = 0.25       # EWMA smoothing weight for the newest sample
    headroom: float = 1.5     # width multiplier over the smoothed max
    floor: int = 8            # never suggest below this (absent a larger k)
    ceiling: int = 1024       # never suggest above this
    ewma: float | None = None

    def update(self, counts: np.ndarray) -> None:
        """Fold one launch's per-row survivor counts into the EWMA."""
        if len(counts) == 0:
            return
        obs = float(np.max(counts))
        self.ewma = obs if self.ewma is None else (
            self.alpha * obs + (1.0 - self.alpha) * self.ewma)

    def suggest(self, k: int, batch_cols: int) -> int:
        if self.ewma is None:               # cold start: the old fixed floor
            width = max(int(k), 64)
        else:
            width = int(np.ceil(self.ewma * self.headroom))
        width = max(width, int(k), self.floor)
        width = 1 << max(int(width - 1).bit_length(), 0)   # next pow2
        return int(max(min(width, self.ceiling, batch_cols),
                       min(self.floor, batch_cols)))


def mbr_distance_join(driver_boxes: np.ndarray, driven_boxes: np.ndarray,
                      dist_norm: float, backend: str = "numpy",
                      stats: JoinStats | None = None
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Candidate pairs (i, j) with box_min_dist <= dist (normalized space),
    in row-major order."""
    if len(driver_boxes) == 0 or len(driven_boxes) == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    pairs = len(driver_boxes) * len(driven_boxes)
    PAIRS.tested += pairs
    if backend == "fused":
        # pure-distance use of the streaming kernel: zero keys, θ = -inf.
        # With nothing to prune this does MORE work than the matrix paths —
        # it exists for drop-in equivalence (tests, ablations); the perf
        # path is fused_stream_join with real keys via the executor.
        PAIRS.on_device += pairs
        pi, pj = [], []
        for bi, bj in fused_stream_join(
                driver_boxes, driven_boxes,
                np.zeros(len(driver_boxes)), np.zeros(len(driven_boxes)),
                dist_norm, k=64, stats=stats):
            pi.append(bi)
            pj.append(bj)
        i = np.concatenate(pi) if pi else np.empty(0, np.int64)
        j = np.concatenate(pj) if pj else np.empty(0, np.int64)
        order = np.lexsort((j, i))      # match the dense row-major order
        return i[order], j[order]
    if backend == "kernel" and pairs >= DEVICE_MIN_PAIRS:
        from ..kernels import ops as kops
        PAIRS.on_device += pairs
        ci, cj = kops.mbr_candidates(driver_boxes, driven_boxes, dist_norm)
        # the numpy route's own float64 test, on the device's candidates
        keep = geometry.box_min_dist(driver_boxes[ci],
                                     driven_boxes[cj]) <= dist_norm
        i, j = ci[keep], cj[keep]
    else:
        d = geometry.box_min_dist(driver_boxes[:, None, :],
                                  driven_boxes[None, :, :])
        i, j = np.nonzero(d <= dist_norm)
    if stats is not None:
        stats.pairs_tested += pairs
        stats.candidates += len(i)
    return i.astype(np.int64), j.astype(np.int64)


def _sanitize_keys(keys: np.ndarray, n: int) -> np.ndarray:
    """Per-entity score-key upper bounds as f32; NaN (no value -> the row can
    never produce a scored result) maps to -inf so the kernel drops it.

    Engine score keys are f64; round-to-nearest f32 conversion may round a
    bound *below* the true key, which would make θ pruning unsound. Nudge
    any rounded-down value one ulp toward +inf so the f32 bound stays a true
    upper bound (false survivors are harmless — scoring decides).
    """
    if keys is None:
        return np.zeros(n, dtype=np.float32)
    keys64 = np.asarray(keys, dtype=np.float64)
    k32 = keys64.astype(np.float32)
    low = k32.astype(np.float64) < keys64
    k32 = np.where(low, np.nextafter(k32, np.float32(np.inf)), k32)
    return np.where(np.isnan(k32), -np.inf, k32).astype(np.float32)


def _theta32_lower(theta: float) -> np.float32:
    """θ as f32 rounded toward -inf: the kernel must never prune with a θ
    above the true f64 threshold."""
    t32 = np.float32(theta)
    if np.isfinite(t32) and float(t32) > theta:
        t32 = np.nextafter(t32, np.float32(-np.inf))
    return t32


def fused_stream_join(driver_boxes: np.ndarray, driven_boxes: np.ndarray,
                      driver_keys: np.ndarray, driven_keys: np.ndarray,
                      dist_norm: float, k: int,
                      theta_fn=None, batch_cols: int = 4096,
                      interpret: bool | None = None,
                      stats: JoinStats | None = None,
                      tuner: KcapTuner | None = None):
    """Streaming Phase-3 join: yields (pi, pj) candidate batches.

    Driven entities are processed in descending score-key order, one
    `batch_cols`-wide column batch per fused-kernel call, so:

    - `theta_fn()` (the shared TopK threshold) is re-read before every batch
      and pushed into the kernel's VMEM predicate — results the caller pushes
      between batches tighten the filter mid-block;
    - once ``max(driver_keys) + driven_keys[next] <= θ`` no later pair can
      enter the top-k (keys are sorted), and the scan stops — the paper's
      early termination applied *inside* a block;
    - peak intermediate memory is O(M * batch_cols), independent of N.

    The kernel emits fixed-width (M, k) per-row partials plus exact survivor
    counts; rows whose survivors overflow the width are recovered densely
    (only those rows, only this batch), keeping the candidate set exactly
    equal to the matrix backends'. Pairs are (driver row, driven row) indices
    into the *original* (unsorted) arrays.
    """
    from ..kernels import ops as kops

    m, n = len(driver_boxes), len(driven_boxes)
    if m == 0 or n == 0:
        return
    ds = _sanitize_keys(driver_keys, m)
    vs = _sanitize_keys(driven_keys, n)
    ds_max = float(ds.max()) if m else -np.inf
    order = np.argsort(-vs, kind="stable")
    dvn_sorted = np.ascontiguousarray(driven_boxes[order], dtype=np.float32)
    vs_sorted = vs[order]
    drv = np.ascontiguousarray(driver_boxes, dtype=np.float32)

    for start in range(0, n, batch_cols):
        theta = float(theta_fn()) if theta_fn is not None else -np.inf
        # early termination inside the block: the best remaining pair bound
        # cannot beat theta, and keys only decrease from here
        if ds_max + float(vs_sorted[start]) <= theta:
            break
        # partial width: autotuned from observed survivor counts when a
        # tuner is threaded through; otherwise the fixed floor above k
        # keeps the (rare but expensive) dense overflow recovery off the
        # common path when θ is still loose
        kcap = (tuner.suggest(int(k), batch_cols) if tuner is not None
                else min(max(int(k), 64), batch_cols))
        theta32 = _theta32_lower(theta)
        chunk = dvn_sorted[start:start + batch_cols]
        ck = vs_sorted[start:start + batch_cols]
        _, idx, counts = kops.fused_topk_join(
            drv, chunk, ds, ck, float(dist_norm), theta32, k=kcap,
            interpret=interpret, fetch_scores=False)
        if tuner is not None:
            tuner.update(counts)
        if stats is not None:
            stats.pairs_tested += m * len(chunk)

        ok_rows = counts <= kcap
        sel = (idx >= 0) & ok_rows[:, None]
        pi = np.nonzero(sel)[0].astype(np.int64)
        pj_local = idx[sel].astype(np.int64)
        over = np.flatnonzero(~ok_rows)
        if len(over):
            # width overflow: recover those rows densely — same f32 arrays,
            # same f32 distance formula and θ the kernel used, so recovered
            # rows see exactly the kernel's predicate
            if stats is not None:
                stats.overflow_rows += len(over)
                stats.overflow_batches += 1
            d = np.asarray(kops.distance_join_matrix(
                drv[over], chunk, interpret=interpret))
            bound = ds[over][:, None] + ck[None, :]
            oi, oj = np.nonzero((d <= np.float32(dist_norm))
                                & (bound > theta32))
            pi = np.concatenate([pi, over[oi].astype(np.int64)])
            pj_local = np.concatenate([pj_local, oj.astype(np.int64)])
        if len(pi) == 0:
            continue
        pj = order[start + pj_local]
        srt = np.lexsort((pj, pi))
        pi, pj = pi[srt], pj[srt]
        if stats is not None:
            stats.candidates += len(pi)
        yield pi, pj


@dataclasses.dataclass
class StreamEntry:
    """One query's Phase-3 work registered with fused_stream_join_multi.

    `emit(pi, pj)` receives candidate-pair batches (indices into the
    original driver/driven arrays) and is expected to refine + push them
    into the query's TopK so the next `theta_fn()` read is tighter.

    `error` is the crash-isolation channel: an exception in one entry's
    per-span work (overflow recovery, emit/refine) lands here and retires
    only that entry from subsequent launches — the other entries' streams
    proceed. A faulted entry's TopK may hold a partial batch, so the owner
    must restart the query from a fresh cursor, not resume it.
    """
    driver_boxes: np.ndarray
    driven_boxes: np.ndarray
    driver_keys: np.ndarray
    driven_keys: np.ndarray
    dist_norm: float
    k: int
    theta_fn: object                  # () -> float, the query's live θ
    emit: object                      # (pi, pj) -> None
    stats: JoinStats | None = None
    error: Exception | None = None    # set ⟹ entry retired by a fault


def fused_stream_join_multi(entries: list[StreamEntry],
                            batch_cols: int = 4096,
                            interpret: bool | None = None,
                            tuner: KcapTuner | None = None) -> int:
    """Cross-query streaming Phase-3 join: several queries' driver blocks in
    ONE kernel grid per launch.

    Each entry is the per-query state fused_stream_join would process alone;
    here the driver rows of all live entries are concatenated (tagged with a
    per-row query id, distance threshold, and θ) and each launch takes the
    next ≈ batch_cols / n_live columns from EVERY live entry's key-sorted
    driven side. The kernel's query-id mask keeps pairs within their query,
    so per-query results are bit-identical to running fused_stream_join
    serially: same column order, same θ reads at batch granularity, same
    dense overflow recovery per (query, batch).

    Entries retire independently — when a query's remaining key bound cannot
    beat its θ (or its columns are exhausted) its rows leave the launch and
    the survivors' column share grows. Returns the number of kernel
    launches (the bench asserts batching actually happened).
    """
    from ..kernels import ops as kops

    class _Cur:
        def __init__(self, e: StreamEntry):
            self.e = e
            self.m = len(e.driver_boxes)
            self.n = len(e.driven_boxes)
            self.ds = _sanitize_keys(e.driver_keys, self.m)
            vs = _sanitize_keys(e.driven_keys, self.n)
            self.ds_max = float(self.ds.max()) if self.m else -np.inf
            self.order = np.argsort(-vs, kind="stable")
            self.dvn = np.ascontiguousarray(e.driven_boxes[self.order],
                                            dtype=np.float32)
            self.vs = vs[self.order]
            self.drv = np.ascontiguousarray(e.driver_boxes,
                                            dtype=np.float32)
            self.pos = 0

        def live(self) -> bool:
            if self.e.error is not None:
                return False
            if self.m == 0 or self.pos >= self.n:
                return False
            theta = float(self.e.theta_fn())
            return self.ds_max + float(self.vs[self.pos]) > theta

    curs = [_Cur(e) for e in entries]
    launches = 0
    while True:
        live = [c for c in curs if c.live()]
        if not live:
            break
        cols_per = max(1, batch_cols // len(live))
        kmax = max(int(c.e.k) for c in live)
        kcap = (tuner.suggest(kmax, batch_cols) if tuner is not None
                else min(max(kmax, 64), batch_cols))
        # pow2-quantize so retiring entries (shrinking kmax) don't force a
        # fresh jit signature per launch
        kcap = min(1 << max(int(kcap - 1).bit_length(), 0), batch_cols)
        # assemble the launch: driver rows / driven columns of every live
        # query, tagged with qid + per-row (dist, θ)
        drv_l, ds_l, rq_l, dist_l, th_l = [], [], [], [], []
        col_l, ck_l, cq_l = [], [], []
        spans = []                       # (cur, row_off, col_off, ncols, θ32)
        row_off = col_off = 0
        for qid, c in enumerate(live):
            ncols = min(cols_per, c.n - c.pos)
            theta32 = _theta32_lower(float(c.e.theta_fn()))
            drv_l.append(c.drv)
            ds_l.append(c.ds)
            rq_l.append(np.full(c.m, qid, np.int32))
            dist_l.append(np.full(c.m, np.float32(c.e.dist_norm)))
            th_l.append(np.full(c.m, theta32))
            col_l.append(c.dvn[c.pos:c.pos + ncols])
            ck_l.append(c.vs[c.pos:c.pos + ncols])
            cq_l.append(np.full(ncols, qid, np.int32))
            spans.append((c, row_off, col_off, ncols, theta32))
            row_off += c.m
            col_off += ncols
        # pad rows/columns up to pow2 buckets with sentinel qids (-1 rows
        # never match -2 columns, dist=-1 kills the distance predicate) so
        # per-step size drift — queries retiring, column shares growing —
        # reuses a handful of jit signatures instead of compiling each launch
        m_tot, n_tot = row_off, col_off
        m_pad = max(128, 1 << int(m_tot - 1).bit_length()) - m_tot
        n_pad = max(128, 1 << int(n_tot - 1).bit_length()) - n_tot
        if m_pad:
            drv_l.append(np.zeros((m_pad, 4), np.float32))
            ds_l.append(np.full(m_pad, -np.inf, np.float32))
            rq_l.append(np.full(m_pad, -1, np.int32))
            dist_l.append(np.full(m_pad, -1.0, np.float32))
            th_l.append(np.full(m_pad, np.inf, np.float32))
        if n_pad:
            col_l.append(np.zeros((n_pad, 4), np.float32))
            ck_l.append(np.full(n_pad, -np.inf, np.float32))
            cq_l.append(np.full(n_pad, -2, np.int32))
        try:
            _, idx, counts = kops.fused_topk_join(
                np.concatenate(drv_l), np.concatenate(col_l),
                np.concatenate(ds_l), np.concatenate(ck_l),
                np.concatenate(dist_l), np.concatenate(th_l), k=kcap,
                row_qid=np.concatenate(rq_l), col_qid=np.concatenate(cq_l),
                interpret=interpret, fetch_scores=False)
        except Exception as exc:    # noqa: BLE001 — whole-launch failure
            # the shared launch died past the failover chain: every rider
            # faults (their owners restart from fresh cursors); entries not
            # in this launch are untouched
            for c, *_ in spans:
                c.e.error = exc
            continue
        launches += 1
        if tuner is not None:
            tuner.update(counts)
        for c, r0, c0, ncols, theta32 in spans:
            e = c.e
            try:
                eidx = idx[r0:r0 + c.m]
                ecnt = counts[r0:r0 + c.m]
                if e.stats is not None:
                    e.stats.pairs_tested += c.m * ncols
                ok_rows = ecnt <= kcap
                sel = (eidx >= 0) & ok_rows[:, None]
                pi = np.nonzero(sel)[0].astype(np.int64)
                # qid masking confines survivors to this entry's column span
                pj_local = eidx[sel].astype(np.int64) - c0
                over = np.flatnonzero(~ok_rows)
                if len(over):
                    if e.stats is not None:
                        e.stats.overflow_rows += len(over)
                        e.stats.overflow_batches += 1
                    chunk = c.dvn[c.pos:c.pos + ncols]
                    ck = c.vs[c.pos:c.pos + ncols]
                    d = np.asarray(kops.distance_join_matrix(
                        c.drv[over], chunk, interpret=interpret))
                    bound = c.ds[over][:, None] + ck[None, :]
                    oi, oj = np.nonzero((d <= np.float32(e.dist_norm))
                                        & (bound > theta32))
                    pi = np.concatenate([pi, over[oi].astype(np.int64)])
                    pj_local = np.concatenate([pj_local, oj.astype(np.int64)])
                if len(pi):
                    pj = c.order[c.pos + pj_local]
                    srt = np.lexsort((pj, pi))
                    pi, pj = pi[srt], pj[srt]
                    if e.stats is not None:
                        e.stats.candidates += len(pi)
                    e.emit(pi, pj)
            except Exception as exc:    # noqa: BLE001 — per-entry isolation
                # one entry's overflow recovery / emit / refine crashed:
                # retire it (owner restarts it) and keep the others going
                e.error = exc
            c.pos += ncols
    return launches


def fused_topk_pairs(driver_boxes: np.ndarray, driven_boxes: np.ndarray,
                     driver_keys: np.ndarray, driven_keys: np.ndarray,
                     dist_norm: float, k: int, theta: float = -np.inf,
                     batch_cols: int = 4096,
                     interpret: bool | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Global per-row top-k of the fused join, without densifying.

    Runs the streaming kernel batch by batch and absorbs the per-batch
    (M, k) partials through topk.merge_row_partials (the two-level merge:
    tiles fold in-kernel, batches fold here). Returns (scores (M, k),
    idx (M, k) into the original driven array), -inf/-1 padded.
    """
    from ..kernels import ops as kops

    m, n = len(driver_boxes), len(driven_boxes)
    ds = _sanitize_keys(driver_keys, m)
    vs = _sanitize_keys(driven_keys, n)
    kcap = max(int(k), 1)
    theta32 = _theta32_lower(float(theta))
    parts = []
    for start in range(0, n, batch_cols):
        chunk = np.ascontiguousarray(
            driven_boxes[start:start + batch_cols], dtype=np.float32)
        scores, idx, _ = kops.fused_topk_join(
            np.ascontiguousarray(driver_boxes, dtype=np.float32), chunk,
            ds, vs[start:start + batch_cols], float(dist_norm), theta32,
            k=kcap, interpret=interpret)
        idx = idx.astype(np.int64)
        parts.append((scores,
                      np.where(idx >= 0, idx + start, -1)))
    if not parts:
        return (np.full((m, kcap), -np.inf, np.float32),
                np.full((m, kcap), -1, np.int64))
    return topk_mod.merge_row_partials(parts, kcap)


# ---------------------------------------------------------------------------
# Exact-geometry refinement on the CSR pool (paper §3.2.4), bucketed kernel
# ---------------------------------------------------------------------------

REFINE_MAX_PTS = 128        # size-class cap; larger geometries are fragmented
# A size class's pairs go to the device in tiles of a fixed number of pairs,
# about REFINE_TILE_POINTS points a tile over both sides, at least the
# kernel's 256 lanes and at most 4096 pairs; so refinement makes one
# program per (m_pad, n_pad) class whatever the data, 64 at most.
REFINE_TILE_POINTS = 1 << 15


@dataclasses.dataclass
class RefineCounters:
    """Exact refinement done by `pool_min_dist` since the process started:
    geometry pairs, their fragment pairs, the vertex pairs the kernel
    computed (the padded tile area, m_pad · n_pad a tile row) and the
    kernel launches."""
    pairs: int = 0
    fragment_pairs: int = 0
    vertex_pairs: int = 0
    launches: int = 0


REFINE = RefineCounters()


def _size_class(n: np.ndarray) -> np.ndarray:
    """Next power of two >= n (n in [1, REFINE_MAX_PTS])."""
    return (1 << np.ceil(np.log2(np.maximum(n, 1))).astype(np.int64)) \
        .astype(np.int64)


def refine_tile(m_pad: int, n_pad: int) -> int:
    """Pairs in one refinement launch of size class (m_pad, n_pad)."""
    t = 1 << (REFINE_TILE_POINTS // (m_pad + n_pad)).bit_length() - 1
    return min(max(t, 256), 4096)


def refine_classes(pool) -> list:
    """The size classes of the fragments of `pool`'s geometries: every
    m_pad (and n_pad) a refinement over the pool can launch."""
    cnt = np.diff(pool.offsets)
    rest = cnt % REFINE_MAX_PTS
    lens = np.concatenate([rest[rest > 0],
                           [REFINE_MAX_PTS] if cnt.max() >= REFINE_MAX_PTS
                           else []])
    return sorted({int(c) for c in _size_class(lens.astype(np.int64))})


def compile_refine(pool) -> int:
    """Make the euclidean refinement programs `pool` can need, one launch
    of each (m_pad, n_pad) class on a throwaway tile, so that none is made
    while queries are served. Returns the launches made."""
    from ..kernels import ops as kops

    classes = refine_classes(pool)
    for m_pad in classes:
        for n_pad in classes:
            t = refine_tile(m_pad, n_pad)
            kops.bucketed_min_core(
                (np.zeros((t, m_pad), np.float32),) * 2,
                (np.zeros((t, n_pad), np.float32),) * 2)
    return len(classes) ** 2


def core_to_dist(core: np.ndarray, metric: str) -> np.ndarray:
    """Metric *core* minima -> distances, in float64 numpy.

    The bucketed kernel reduces the metric core — squared euclid distance,
    or squared unit-sphere chord (= 4·haversine-h) — both monotone in the
    true distance, so the transform commutes with the min and runs once per
    pair here, in f64 numpy because XLA's jitted ``asin`` is not exact at 0
    (a self-distance would come back as ~3e-4 km).
    """
    core = np.asarray(core, dtype=np.float64)
    if metric == "haversine":
        return (2.0 * geometry.EARTH_RADIUS_KM
                * np.arcsin(np.clip(np.sqrt(core) * 0.5, 0.0, 1.0)))
    return np.sqrt(core)


def pool_min_dist(pool, rows_a: np.ndarray, rows_b: np.ndarray,
                  metric: str = "euclid", interpret: bool | None = None,
                  max_pts: int = REFINE_MAX_PTS) -> np.ndarray:
    """Exact min distance per (rows_a[t], rows_b[t]) geometry-pool row pair.

    Vectorized replacement for the per-pair python loop: pairs are grouped by
    padded (m_pad, n_pad) size class (next power of two per side), each
    class's pairs are gathered from the CSR pool's coordinate planes — raw
    x/y for euclid, unit-sphere X/Y/Z for haversine (chord² = 4h, trig
    hoisted to pool build) — into dense (T, m_pad) / (T, n_pad) tiles of
    the class's fixed `refine_tile` T pairs, padding replicating the
    entity's last point (which can never change a min), and one kernel call
    per tile computes the pairwise minima (kernels/geom_refine.py; jnp
    oracle on CPU). A class's last tile is filled by replaying its first
    pair: min-scatter is idempotent. Geometries wider than `max_pts` are
    fragmented into <= max_pts chunks on both sides (min distance
    decomposes over point subsets) and min-scattered back. Counts its work
    on `REFINE`. Returns (n_pairs,) float64 distances (f32 cores, f64 final
    transform).
    """
    from ..kernels import ops as kops

    npairs = len(rows_a)
    out = np.full(npairs, np.inf, dtype=np.float32)
    if npairs == 0:
        return out
    rows_a = np.asarray(rows_a, dtype=np.int64)
    rows_b = np.asarray(rows_b, dtype=np.int64)
    off = pool.offsets
    cnt_a, cnt_b = pool.counts(rows_a), pool.counts(rows_b)
    na, nb = -(-cnt_a // max_pts), -(-cnt_b // max_pts)
    frags = na * nb
    if int(frags.max()) == 1:           # common case: no fragmentation
        pair_idx = np.arange(npairs, dtype=np.int64)
        start_a, len_a = off[rows_a], cnt_a
        start_b, len_b = off[rows_b], cnt_b
    else:
        pair_idx = np.repeat(np.arange(npairs, dtype=np.int64), frags)
        base = np.repeat(np.cumsum(frags) - frags, frags)
        local = np.arange(int(frags.sum()), dtype=np.int64) - base
        nb_r = nb[pair_idx]
        ca, cb = local // nb_r, local % nb_r
        start_a = off[rows_a][pair_idx] + ca * max_pts
        len_a = np.minimum(cnt_a[pair_idx] - ca * max_pts, max_pts)
        start_b = off[rows_b][pair_idx] + cb * max_pts
        len_b = np.minimum(cnt_b[pair_idx] - cb * max_pts, max_pts)
    REFINE.pairs += npairs
    REFINE.fragment_pairs += len(pair_idx)
    cls_a, cls_b = _size_class(len_a), _size_class(len_b)
    planes = pool.planes3d() if metric == "haversine" else pool.planes2d()
    key = cls_a * (2 * max_pts) + cls_b
    for kk in np.unique(key):
        sel = np.flatnonzero(key == kk)
        m_pad, n_pad = int(cls_a[sel[0]]), int(cls_b[sel[0]])
        tile = refine_tile(m_pad, n_pad)
        for t0 in range(0, len(sel), tile):
            part = sel[t0:t0 + tile]
            part = np.concatenate([part, np.full(tile - len(part), part[0],
                                                 dtype=np.int64)])
            # clamped gather: min(arange, len-1) replicates the last point
            ia = start_a[part, None] + np.minimum(
                np.arange(m_pad)[None, :], (len_a[part] - 1)[:, None])
            ib = start_b[part, None] + np.minimum(
                np.arange(n_pad)[None, :], (len_b[part] - 1)[:, None])
            c = np.asarray(kops.bucketed_min_core(
                tuple(p[ia] for p in planes), tuple(p[ib] for p in planes),
                interpret=interpret))
            np.minimum.at(out, pair_idx[part], c)
            REFINE.launches += 1
            REFINE.vertex_pairs += tile * m_pad * n_pad
    return core_to_dist(out, metric)


def refine(pairs_i: np.ndarray, pairs_j: np.ndarray,
           pool, rows_a: np.ndarray, rows_b: np.ndarray,
           dist_world: float, metric: str = "euclid",
           stats: JoinStats | None = None,
           interpret: bool | None = None) -> np.ndarray:
    """Exact-representation distance validation (paper §3.2.4), vectorized.

    rows_a / rows_b are geometry-pool rows per candidate pair (from
    ``store.geom_rows(ents[pairs_i])`` etc.). Returns a boolean keep mask;
    `refine_looped` is the per-pair oracle this must agree with.
    """
    d = pool_min_dist(pool, rows_a, rows_b, metric, interpret)
    # f64 compare: the threshold stays un-rounded (a f32-rounded threshold
    # could drop true survivors)
    keep = d <= float(dist_world)
    if stats is not None:
        stats.refined += int(keep.sum())
    return keep


def exact_pair_distance(pool, rows_a: np.ndarray, rows_b: np.ndarray,
                        metric: str = "euclid",
                        interpret: bool | None = None) -> np.ndarray:
    """Exact min distance per candidate pair, on the bucketed kernel path
    (shared by the engine's refinement and the baselines)."""
    return pool_min_dist(pool, rows_a, rows_b, metric, interpret)


def _pool_gather(pool, rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(point indices, owning row segment) for the given pool rows."""
    from .squadtree import csr_gather  # lazy: avoid a module cycle
    rows = np.asarray(rows, dtype=np.int64)
    cnt = pool.counts(rows)
    idx = csr_gather(pool.offsets[rows], cnt)
    seg = np.repeat(np.arange(len(rows), dtype=np.int64), cnt)
    return idx, seg


def pool_points_in_box(pool, rows: np.ndarray, box) -> np.ndarray:
    """Per pool row: does any exact point lie inside the CLOSED world box?

    ``box`` is (xmin, ymin, xmax, ymax) in world units. The boundary counts
    (consistent with `geometry.boxes_intersect`), and a zero-area box still
    matches coincident points exactly. Exact — no MBR approximation.
    """
    rows = np.asarray(rows, dtype=np.int64)
    out = np.zeros(len(rows), dtype=bool)
    if len(rows) == 0:
        return out
    idx, seg = _pool_gather(pool, rows)
    x = pool.points[idx, 0].astype(np.float64)
    y = pool.points[idx, 1].astype(np.float64)
    xmin, ymin, xmax, ymax = (float(box[0]), float(box[1]),
                              float(box[2]), float(box[3]))
    inb = (x >= xmin) & (x <= xmax) & (y >= ymin) & (y <= ymax)
    np.logical_or.at(out, seg, inb)
    return out


def pool_point_min_dist(pool, rows: np.ndarray, point,
                        metric: str = "euclid") -> np.ndarray:
    """Exact min distance from each pool row's point set to a world point.

    f64 throughout (over the pool's f32 coordinates), so coincident points
    come back as exactly 0.0 — the within-distance selection shape and its
    brute-force oracle both score with this routine.
    """
    rows = np.asarray(rows, dtype=np.int64)
    out = np.full(len(rows), np.inf, dtype=np.float64)
    if len(rows) == 0:
        return out
    idx, seg = _pool_gather(pool, rows)
    pts = pool.points[idx].astype(np.float64)
    p = np.asarray(point, dtype=np.float64)
    dist_fn = (geometry.haversine_km if metric == "haversine"
               else geometry.euclid_dist)
    d = dist_fn(pts, p[None, :])
    np.minimum.at(out, seg, d)
    return out


def refine_looped(pairs_i: np.ndarray, pairs_j: np.ndarray,
                  driver_geom: list, driven_geom: list,
                  dist_world: float, metric: str = "euclid",
                  stats: JoinStats | None = None) -> np.ndarray:
    """Per-pair refinement oracle (the pre-pool python loop, kept as the
    specification for `refine` and the looped side of bench_refine.py).

    driver_geom / driven_geom are per-candidate exact geometries: (m, 2)
    point arrays (points, polylines, polygon rings). Returns a keep mask.
    """
    keep = np.zeros(len(pairs_i), dtype=bool)
    dist_fn = geometry.euclid_dist if metric == "euclid" else geometry.haversine_km
    for n in range(len(pairs_i)):
        pa = driver_geom[n]
        pb = driven_geom[n]
        d = dist_fn(pa[:, None, :], pb[None, :, :])
        keep[n] = bool((d <= dist_world).any())
    if stats is not None:
        stats.refined += int(keep.sum())
    return keep


def exact_pair_distance_looped(driver_geom: list, driven_geom: list,
                               metric: str = "euclid") -> np.ndarray:
    dist_fn = geometry.euclid_dist if metric == "euclid" else geometry.haversine_km
    out = np.empty(len(driver_geom))
    for n in range(len(driver_geom)):
        d = dist_fn(driver_geom[n][:, None, :], driven_geom[n][None, :, :])
        out[n] = float(d.min())
    return out
