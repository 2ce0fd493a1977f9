"""Public jit'd wrappers for the Pallas kernels with CPU fallbacks.

On TPU the Pallas path compiles natively; on CPU we use interpret mode (for
tests) or the jnp reference (for the engine's `kernel` backend), keeping one
call site for both worlds.

Every query-path op here runs through `core/fault.run_op`: the dispatch is
an ordered failover chain (live route → interpret → oracle) so an exception,
watchdog timeout, or detected corruption in one backend degrades to the next
bit-identical one instead of failing the query. Per-(op, backend) circuit
breakers remember repeated failures; `BackendPolicy.resolve` consults them
so later plans skip a broken backend at plan time. The chains cost one
function call and a dict probe per *dispatch* (per driver block, not per
row); the structural validators only run when a `FaultPlan` is installed.

Each of those dispatchers is one `_Dispatch`: a ``streak.kernel`` span
from entry to its host result (padding, key split, upload, launch, fetch),
tagged with the op, the route `run_op` took and the shapes handed to the
device, and the bytes it uploads and fetches, counted per op on
`fault.STATE.stats` (``h2d_bytes``, ``d2h_bytes``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import fault as _fault
from ..core import spans as _spans
from . import block_scan as _bs
from . import bloom_probe as _bp
from . import distance_join as _dj
from . import flash_attention as _fa
from . import fused_topk_join as _ftj
from . import geom_refine as _gr
from . import mbr_candidates as _mc
from . import merge_join as _mj
from . import morton_kernel as _mk
from . import ref
from . import tree_descend as _td


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class _Dispatch:
    """One public dispatch: its ``streak.kernel`` span and the bytes it
    moves between host and device (see the module docstring). The route
    and the shapes are noted only while a profiler records."""

    __slots__ = ("op", "h2d", "d2h", "shapes", "span")

    def __init__(self, op: str):
        self.op = op
        self.h2d = self.d2h = 0
        self.shapes: list[str] | None = None

    def __enter__(self) -> "_Dispatch":
        self.span = _spans.span("streak.kernel", op=self.op)
        self.span.__enter__()
        if self.span.is_enabled():
            self.shapes = []
        return self

    def __exit__(self, *exc):
        if self.h2d or self.d2h:
            st = _fault.STATE.stats
            st.h2d_bytes[self.op] += self.h2d
            st.d2h_bytes[self.op] += self.d2h
        if self.shapes is not None:
            route = _fault.STATE.route if exc[0] is None else None
            self.span.set_metadata(backend=str(route),
                                   shapes=" ".join(self.shapes))
        return self.span.__exit__(*exc)

    def up(self, x, dtype=None):
        """`x` as a device array; a host array's bytes count as uploaded."""
        on_host = not isinstance(x, jax.Array)
        d = jnp.asarray(x, dtype=dtype)
        if on_host:
            self.h2d += d.nbytes
            if self.shapes is not None:
                self.shapes.append(f"{d.dtype.name}{list(d.shape)}")
        return d

    def down(self, x) -> np.ndarray:
        """`x` as a host array; a device array's bytes count as fetched."""
        if isinstance(x, jax.Array):
            self.d2h += x.nbytes
        return np.asarray(x)


def _v_dist_matrix(out) -> bool:
    a = np.asarray(out)
    return bool(np.isfinite(a).all() and (a >= 0).all())


def distance_join_matrix(driver, driven, interpret: bool | None = None):
    with _Dispatch("distance_join_matrix") as call:
        driver = call.up(driver, jnp.float32)
        driven = call.up(driven, jnp.float32)

        def oracle():
            return ref.distance_join_ref(driver, driven)

        if _on_tpu() or interpret:
            live = "interpret" if (interpret and not _on_tpu()) else "kernel"
            attempts = [
                (live, lambda: _dj.distance_join(
                    driver, driven,
                    interpret=bool(interpret) and not _on_tpu())),
                ("oracle", oracle),
            ]
        else:
            # numpy-free CPU route: the jnp oracle is already the live
            # backend; the trailing attempt retries the same pure function
            # (recovers injected/transient failures, not deterministic ones)
            attempts = [("jit", oracle), ("oracle", oracle)]
        return call.down(_fault.run_op("distance_join_matrix", attempts,
                                       validate=_v_dist_matrix))


def distance_join_mask(driver, driven, dist: float,
                       interpret: bool | None = None):
    return distance_join_matrix(driver, driven, interpret) <= dist


# Padded block classes of `mbr_candidates`: rows pad to MBR_ROWS, columns
# to a power of two in [MBR_MIN_COLS, MBR_MAX_COLS]; a larger block goes
# in chunks of those sizes. The compaction's capacity is a power of two
# from MBR_MIN_CAP up. That makes one test program per column class and
# one compaction program per capacity.
MBR_ROWS = 1024
MBR_MIN_COLS = 1024
MBR_MAX_COLS = 16384
MBR_MIN_CAP = 1024
_F32_EPS = 2.0 ** -24          # unit roundoff of float32


def mbr_threshold32(driver: np.ndarray, driven: np.ndarray,
                    dist: float) -> np.float32:
    """Squared float32 threshold for the device's MBR test.

    Every pair whose float64 `geometry.box_min_dist` is <= `dist` passes
    ``dx² + dy² <= t`` when the boxes are rounded to float32 and the test
    runs in float32. With u the float32 unit roundoff and C the largest
    finite coordinate magnitude, rounding the coordinates and subtracting
    moves dx and dy by at most e = 4uC(1 + u); squaring and summing adds
    under three roundings more. So t is (dist·(1 + 4u) + 2e)²·(1 + 4u),
    rounded up, and never below the smallest normal float32 (a device may
    flush subnormals). The factor on `dist` also covers boxes handed in
    float32, whose numpy test rounds in float32 too.
    """
    c = 0.0
    for a in (driver, driven):
        a = np.abs(a[np.isfinite(a)])
        if a.size:
            c = max(c, float(a.max()))
    e = 4.0 * _F32_EPS * c * (1.0 + _F32_EPS)
    t = (float(dist) * (1.0 + 4.0 * _F32_EPS) + 2.0 * e) ** 2 \
        * (1.0 + 4.0 * _F32_EPS)
    t32 = np.float32(t)
    if float(t32) < t:
        t32 = np.nextafter(t32, np.float32(np.inf))
    return max(t32, np.finfo(np.float32).tiny)


def mbr_candidates(driver, driven, dist: float):
    """Phase-3 MBR candidate pairs, tested and compacted on the device.

    driver (M, 4) / driven (N, 4) boxes (x0, y0, x1, y1), float64. Returns
    host int64 (i, j) in row-major order: every pair whose float64
    `geometry.box_min_dist` is <= `dist`, and the few more that pass the
    widened float32 test (`mbr_threshold32`) — the caller rechecks them in
    float64. The device never hands back the (M, N) matrix or mask: each
    block chunk comes back as its count and then its candidates' flat
    positions, 4 bytes each in a power-of-two capacity
    (kernels/mbr_candidates.py). The jnp oracle fetches the dense mask; it
    also takes a chunk with more candidates than its mask has words.
    """
    driver = np.asarray(driver)
    driven = np.asarray(driven)
    m, n = len(driver), len(driven)
    empty = np.empty(0, np.int64)
    if m == 0 or n == 0:
        return empty, empty
    thresh = mbr_threshold32(driver, driven, dist)
    drv32 = driver.astype(np.float32)
    dvn32 = driven.astype(np.float32)
    pi, pj = [], []
    with _Dispatch("mbr_candidates") as call:
        for c0 in range(0, n, MBR_MAX_COLS):
            nc = min(MBR_MAX_COLS, n - c0)
            ncols = max(MBR_MIN_COLS, 1 << int(nc - 1).bit_length())
            shift = ncols.bit_length() - 1
            dvn_t = np.zeros((4, ncols), np.float32)
            dvn_t[:, :nc] = dvn32[c0:c0 + nc].T
            dvn_dev = call.up(dvn_t)
            for r0 in range(0, m, MBR_ROWS):
                mr = min(MBR_ROWS, m - r0)
                drv = np.zeros((MBR_ROWS, 4), np.float32)
                drv[:mr] = drv32[r0:r0 + mr]
                flat = _mbr_chunk(call, drv, dvn_t, dvn_dev, mr, nc, thresh)
                pi.append(r0 + (flat >> shift))
                pj.append(c0 + (flat & (ncols - 1)))
    i, j = np.concatenate(pi), np.concatenate(pj)
    if n > MBR_MAX_COLS:
        # chunk by chunk, each row-major: a stable sort on the row restores
        # the row-major order of the whole block
        order = np.argsort(i, kind="stable")
        i, j = i[order], j[order]
    return i, j


def _mbr_chunk(call: _Dispatch, drv, dvn_t, dvn_dev, m: int, n: int,
               thresh) -> np.ndarray:
    """One padded chunk's candidates as int64 flat positions of the
    (MBR_ROWS, ncols) block, through the failover chain."""
    n_words = drv.shape[0] * dvn_t.shape[1] // 32

    def oracle():
        mask = call.down(_mbr_mask_jit(call.up(drv), dvn_dev, thresh))
        i, j = np.nonzero(mask[:m, :n])
        return i.astype(np.int64) * dvn_t.shape[1] + j

    def device():
        count, words, excl, slot_word = _mc.count_words(
            call.up(drv), dvn_dev, m, n, thresh)
        c = int(call.down(count))
        if c == 0:
            return np.empty(0, np.int64)
        if c > n_words:     # no room in the slot plane
            return oracle()
        cap = max(MBR_MIN_CAP, 1 << int(c - 1).bit_length())
        flat = call.down(_mc.compact(words, excl, slot_word, cap=cap))
        return flat[:c].astype(np.int64)

    live = "kernel" if _on_tpu() else "jit"
    return _fault.run_op(
        "mbr_candidates", [(live, device), ("oracle", oracle)],
        validate=functools.partial(_v_flat, m=m, n=n, ncols=dvn_t.shape[1]))


_mbr_mask_jit = jax.jit(ref.mbr_mask_ref)


def _v_flat(out, m: int, n: int, ncols: int) -> bool:
    f = np.asarray(out)
    return bool(f.size == 0 or (
        (np.diff(f) > 0).all() and f[0] >= 0
        and (f // ncols < m).all() and (f % ncols < n).all()))


def fused_topk_join(driver, driven, driver_keys, driven_keys,
                    dist, theta, k: int = 64,
                    row_qid=None, col_qid=None,
                    interpret: bool | None = None,
                    fetch_scores: bool = True):
    """Streaming per-row top-k distance join; see kernels/fused_topk_join.py.

    `dist` / `theta` may be scalars or per-driver-row (M,) arrays; `row_qid`
    / `col_qid` optional int32 query ids mask cross-query pairs so several
    queries' blocks share one launch (serve/spatial.py). Returns
    (scores (M, k), idx (M, k), counts (M,)) — the per-row partials the
    `fused` join backend consumes — as host arrays; with `fetch_scores`
    false the scores stay on the device and None stands in their place.
    On CPU without interpret mode this runs
    the dense jnp oracle (still per column *batch* when called through
    core/spatial_join.py, so peak memory stays independent of total N).
    """
    with _Dispatch("fused_topk_join") as call:
        driver = call.up(driver, jnp.float32)
        driven = call.up(driven, jnp.float32)
        dk = call.up(driver_keys, jnp.float32)
        vk = call.up(driven_keys, jnp.float32)
        m, n = driver.shape[0], driven.shape[0]
        # one jit signature for scalar and per-row callers: always
        # materialize the per-row threshold columns and the qid planes
        dist_arr = jnp.broadcast_to(call.up(dist, jnp.float32), (m,))
        theta_arr = jnp.broadcast_to(call.up(theta, jnp.float32), (m,))
        rq = (jnp.zeros(m, jnp.int32) if row_qid is None
              else call.up(row_qid, jnp.int32))
        cq = (jnp.zeros(n, jnp.int32) if col_qid is None
              else call.up(col_qid, jnp.int32))

        def oracle():
            return _fused_ref_jit(driver, driven, dk, vk, dist_arr,
                                  theta_arr, rq, cq, k)

        if _on_tpu() or interpret:
            live = "interpret" if (interpret and not _on_tpu()) else "kernel"
            attempts = [
                (live, lambda: _ftj.fused_topk_join(
                    driver, driven, dk, vk, dist_arr, theta_arr, k=k,
                    row_qid=rq, col_qid=cq,
                    interpret=bool(interpret) and not _on_tpu())),
                ("oracle", oracle),
            ]
        else:
            attempts = [("jit", oracle), ("oracle", oracle)]
        scores, idx, counts = _fault.run_op(
            "fused_topk_join", attempts,
            validate=functools.partial(_v_fused, n=n))
        return (call.down(scores) if fetch_scores else None,
                call.down(idx), call.down(counts))


def _v_fused(out, n: int) -> bool:
    # counts are *survivor* totals (they exceed k on overflow — that is the
    # recovery signal) so the structural bound is the column count
    scores, _, counts = out
    c = np.asarray(counts)
    return bool(not np.isnan(np.asarray(scores)).any()
                and (c >= 0).all() and (c <= n).all())


@functools.partial(jax.jit, static_argnames=("k",))
def _fused_ref_jit(driver, driven, dk, vk, dist, theta, rq, cq, k):
    return ref.fused_topk_join_ref(driver, driven, dk, vk, dist, theta, k,
                                   row_qid=rq, col_qid=cq)


def bucketed_min_core(a_planes, b_planes, interpret: bool | None = None):
    """Per-pair exact-geometry min squared distance over one padded
    size-class bucket; see kernels/geom_refine.py. a_planes / b_planes:
    dims-tuples of (B, m_pad) / (B, n_pad) float32 coordinate planes whose
    padding replicates real points (dims=2 raw x/y for euclid, dims=3
    unit-sphere X/Y/Z for haversine). Returns (B,) float32 core minima —
    the caller applies the metric's monotone distance transform in float64
    (core/spatial_join.py::core_to_dist)."""
    with _Dispatch("bucketed_min_core") as call:
        a_planes = tuple(call.up(p, jnp.float32) for p in a_planes)
        b_planes = tuple(call.up(p, jnp.float32) for p in b_planes)

        def host():
            # CPU: the loop-structured host twin (kernel numerics, no
            # (B, m, n) cube); ref.bucketed_min_core_ref stays the oracle
            return _gr.bucketed_min_core_host(a_planes, b_planes)

        if _on_tpu() or interpret:
            live = "interpret" if (interpret and not _on_tpu()) else "kernel"
            attempts = [
                (live, lambda: _gr.bucketed_min_core(
                    a_planes, b_planes,
                    interpret=bool(interpret) and not _on_tpu())),
                ("oracle", host),
            ]
        else:
            attempts = [("jit", host), ("oracle", host)]
        return call.down(_fault.run_op("bucketed_min_core", attempts,
                                       validate=_v_min_core))


def _v_min_core(out) -> bool:
    a = np.asarray(out)
    return bool(np.isfinite(a).all() and (a >= 0).all())


# Rank-pass backend dispatch for the relational merge join (core/join.py).
# "numpy" is the oracle (np.searchsorted, fastest on CPU); "cpu" is the
# jitted loop-structured twin; "kernel" routes through the Pallas kernel on
# TPU and the dense jnp oracle on CPU; "interpret" forces the Pallas kernel
# in interpret mode (tests). "auto" resolves once per process.
RANK_BACKENDS = ("auto", "numpy", "cpu", "kernel", "interpret")
_auto_rank_backend: str | None = None


def resolve_rank_backend(backend: str | None) -> str:
    global _auto_rank_backend
    b = backend or "auto"
    if b not in RANK_BACKENDS:
        raise ValueError(f"unknown merge-join rank backend {b!r}")
    if b != "auto":
        return b
    if _auto_rank_backend is None:
        _auto_rank_backend = "kernel" if _on_tpu() else "numpy"
    return _auto_rank_backend


def split_key_planes(x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 keys -> (hi, lo) int32 planes whose (signed hi, signed lo)
    lexicographic order equals the int64 order (the lo sign bit is flipped
    so signed int32 compares act as unsigned compares on the low half)."""
    x = np.asarray(x, dtype=np.int64)
    hi = (x >> np.int64(32)).astype(np.int32)
    lo = (x & np.int64(0xFFFFFFFF)).astype(np.uint32)
    return hi, (lo ^ np.uint32(1 << 31)).view(np.int32)


def merge_join_ranks(table, probes, backend: str | None = None,
                     interpret: bool | None = None, side: str = "both"):
    """Insertion ranks of `probes` in the sorted int64 `table`: with
    side="both" (the join's CSR widths) returns (lo, hi) int64 where
    lo = searchsorted-left and hi = searchsorted-right; side="left"/"right"
    returns just that bound (the semijoin membership / SIP interval tests —
    the numpy backend then runs a single searchsorted and the CPU twin a
    single binary search; the counting kernel's pass yields both for free).
    The rank pass of the relational merge
    join; see kernels/merge_join.py. Keys must be < int64-max (the kernel's
    padding sentinel)."""
    if side not in ("both", "left", "right"):
        raise ValueError(f"unknown rank side {side!r}")
    backend = resolve_rank_backend(
        "interpret" if (interpret and backend in (None, "auto")) else backend)
    table = np.asarray(table, dtype=np.int64)
    probes = np.asarray(probes, dtype=np.int64)
    m = len(probes)
    if len(table) == 0 or m == 0:
        z = np.zeros(m, dtype=np.int64)
        return (z, z.copy()) if side == "both" else z

    def numpy_ranks():
        if side != "both":
            return np.searchsorted(table, probes, side)
        return (np.searchsorted(table, probes, "left"),
                np.searchsorted(table, probes, "right"))

    with _Dispatch("merge_join_ranks") as call:
        if backend == "numpy":
            attempts = [("numpy", numpy_ranks), ("oracle", numpy_ranks)]
        else:
            def accel(backend=backend):
                # pow2 size classes bound jit recompiles; the int64-max
                # sentinel compares greater than every probe, so table
                # padding never changes a rank, and padded probe rows are
                # sliced off below
                t_hi, t_lo = split_key_planes(
                    _pad_pow2(table, (1 << 63) - 1))
                p_hi, p_lo = split_key_planes(_pad_pow2(probes, 0))
                planes = [call.up(a) for a in (t_hi, t_lo, p_hi, p_lo)]
                if backend == "cpu":
                    out = _mj.merge_join_ranks_host(*planes, side=side)
                    if side != "both":
                        return call.down(out[:m]).astype(np.int64)
                    lo, hi = out
                elif backend == "kernel" and not _on_tpu():
                    lo, hi = _ranks_ref_jit(*planes)
                else:
                    lo, hi = _mj.merge_join_ranks(
                        *planes,
                        interpret=backend == "interpret" and not _on_tpu())
                lo = call.down(lo[:m]).astype(np.int64)
                hi = call.down(hi[:m]).astype(np.int64)
                return ((lo, hi) if side == "both"
                        else (lo if side == "left" else hi))

            attempts = [(backend, accel), ("oracle", numpy_ranks)]
        return _fault.run_op(
            "merge_join_ranks", attempts,
            validate=functools.partial(_v_ranks, n=len(table), side=side))


def _v_ranks(out, n: int, side: str) -> bool:
    lo, hi = out if side == "both" else (out, out)
    lo, hi = np.asarray(lo), np.asarray(hi)
    return bool((lo >= 0).all() and (hi <= n).all() and (lo <= hi).all())


def _pad_pow2(x: np.ndarray, fill: int) -> np.ndarray:
    p = 1 << max(int(len(x) - 1).bit_length(), 3)
    if p == len(x):
        return x
    return np.concatenate([x, np.full(p - len(x), fill, dtype=np.int64)])


@jax.jit
def _ranks_ref_jit(t_hi, t_lo, p_hi, p_lo):
    return ref.merge_join_ranks_ref(t_hi, t_lo, p_hi, p_lo)


def f64_sort_keys(x: np.ndarray) -> np.ndarray:
    """IEEE-754 doubles -> order-isomorphic int64 sort keys (host, exact).

    The classic total-order flip: positives keep their bit pattern with the
    sign bit toggled, negatives are complemented; -0.0 is canonicalized to
    +0.0 first so the two zero encodings stay equal. int64 comparisons on
    the keys then agree bit-for-bit with f64 ``<=`` on the inputs, which
    lets the 32-bit kernels run the engine's f64 box tests exactly. Finite
    inputs map strictly inside (int64-min, int64-max), so both extremes
    remain free for never-matching padding sentinels.
    """
    x = np.where(x == 0.0, 0.0, np.asarray(x, dtype=np.float64))
    u = np.asarray(x, dtype=np.float64).view(np.uint64)
    sign = np.uint64(1) << np.uint64(63)
    key_u = np.where(u & sign != 0, ~u, u | sign)
    return (key_u ^ sign).view(np.int64)


# never-intersecting padding box in f64_sort_keys space: mins above every
# real max key, maxs below every real min key (rows are x0, y0, x2, y3)
DESCEND_PAD_BOX = np.array(
    [(1 << 63) - 1, (1 << 63) - 1, -(1 << 63), -(1 << 63)], dtype=np.int64)


def tree_descend(node_keys, cs_path, box_keys, backend: str = "kernel",
                 interpret: bool | None = None):
    """Fused Phase-1 candidate-node pass; see kernels/tree_descend.py.

    node_keys (4, N) int64 `f64_sort_keys` planes of the node MBRs (rows
    x0, y0, x2, y3); cs_path (N,) bool root-path Bloom verdicts; box_keys
    (B, M, 4) int64 keys of the expanded driver boxes with padding rows
    pre-set to `DESCEND_PAD_BOX`. Returns the (B, N) bool candidate masks.
    backend: "kernel" (Pallas on TPU, jitted dense oracle on CPU) or
    "interpret" (Pallas interpret mode, tests). The host frontier is the
    "numpy" backend and never reaches this dispatch (core/squadtree.py).
    """
    if backend not in ("kernel", "interpret"):
        raise ValueError(f"unknown tree-descend backend {backend!r}")
    node_keys = np.asarray(node_keys, dtype=np.int64)
    box_keys = np.asarray(box_keys, dtype=np.int64)
    n = node_keys.shape[1]
    b, m = box_keys.shape[0], box_keys.shape[1]
    if n == 0 or b == 0:
        return np.zeros((b, n), dtype=bool)
    with _Dispatch("tree_descend") as call:
        # pow2 size classes bound jit recompiles: padded blocks/boxes carry
        # the never-intersecting sentinel box and are sliced off / ignored
        bp = 1 << max(int(b - 1).bit_length(), 0)
        mp = 1 << max(int(m - 1).bit_length(), 3)
        if bp != b or mp != m:
            padded = np.empty((bp, mp, 4), dtype=np.int64)
            padded[:] = DESCEND_PAD_BOX
            padded[:b, :m] = box_keys
            box_keys = padded
        n_hi, n_lo = split_key_planes(node_keys)
        b_hi, b_lo = split_key_planes(box_keys)
        cs = np.asarray(cs_path).astype(np.int32)

        def planes():
            return [call.up(a) for a in (n_hi, n_lo, cs, b_hi, b_lo)]

        def oracle():
            return _descend_ref_jit(*planes())

        if backend == "kernel" and not _on_tpu():
            attempts = [("kernel", oracle), ("oracle", oracle)]
        else:
            attempts = [
                (backend, lambda: _td.tree_descend(
                    *planes(),
                    interpret=backend == "interpret" and not _on_tpu())),
                ("oracle", oracle),
            ]
        out = _fault.run_op("tree_descend", attempts, validate=_v_mask01)
        return call.down(out[:b]) != 0


def tree_descend_sharded(node_keys, cs_path, box_keys,
                         backend: str = "kernel"):
    """Phase-1 descent over every store shard in one dispatch.

    node_keys (S, 4, N_max) stacked per-shard `f64_sort_keys` planes (pad
    columns carry `DESCEND_PAD_BOX`); cs_path (S, N_max) bool with padded
    nodes False; box_keys (B, M, 4) shared driver boxes. Returns (S, B,
    N_max) bool masks.

    The live route lays the shard axis over a `launch/mesh.make_shard_mesh`
    mesh via shard_map — each device sweeps its resident shards with the
    SAME per-shard descent `tree_descend` launches (Pallas kernel on TPU,
    the jitted dense oracle on CPU), so device count scales shards without
    touching the kernel. Failover: a sequential host loop of per-shard
    `tree_descend` calls (each with its own internal chain). Both routes
    are exact integer-compare passes — bit-identical.
    """
    if backend not in ("kernel", "interpret"):
        raise ValueError(f"unknown tree-descend backend {backend!r}")
    node_keys = np.asarray(node_keys, dtype=np.int64)
    box_keys = np.asarray(box_keys, dtype=np.int64)
    s, _, n = node_keys.shape
    b, m = box_keys.shape[0], box_keys.shape[1]
    if s == 0 or n == 0 or b == 0:
        return np.zeros((s, b, n), dtype=bool)
    with _Dispatch("tree_descend_sharded") as call:
        bp = 1 << max(int(b - 1).bit_length(), 0)
        mp = 1 << max(int(m - 1).bit_length(), 3)
        padded = box_keys
        if bp != b or mp != m:
            padded = np.empty((bp, mp, 4), dtype=np.int64)
            padded[:] = DESCEND_PAD_BOX
            padded[:b, :m] = box_keys
        cs = np.asarray(cs_path).astype(np.int32)

        def via_shard_map():
            from ..launch import mesh as _mesh
            n_hi, n_lo = split_key_planes(node_keys)
            b_hi, b_lo = split_key_planes(padded)
            f = sharded_descend_fn(_mesh.make_shard_mesh(s),
                                   pallas=backend == "interpret" or _on_tpu(),
                                   interpret=backend == "interpret"
                                   and not _on_tpu())
            out = f(*[call.up(a) for a in (n_hi, n_lo, cs, b_hi, b_lo)])
            return call.down(out)[:, :b]

        def sequential():
            # each shard's descent is a dispatch of its own, counted there
            return np.stack([
                tree_descend(node_keys[i], cs[i], box_keys, backend=backend)
                .astype(np.int32) for i in range(s)])

        attempts = [("shard_map", via_shard_map), ("sequential", sequential)]
        out = _fault.run_op("tree_descend_sharded", attempts,
                            validate=_v_mask01)
        return out != 0


@functools.lru_cache(maxsize=None)
def sharded_descend_fn(mesh, pallas: bool, interpret: bool = False):
    """The jitted shard_map program behind `tree_descend_sharded`: the
    stacked (S, ...) node planes and cs masks split over the mesh's "shard"
    axis, the driver boxes replicated, and each device sweeping its resident
    shards with `lax.map` over the per-shard descent — the Pallas kernel
    when `pallas`, else the dense jnp oracle. Cached per (mesh, route), so a
    serve loop compiles it once per shape and not once per call."""
    spec = jax.sharding.PartitionSpec

    def body(nh, nl, c, bh, bl):
        def one(args):
            nh1, nl1, c1 = args
            if pallas:
                return _td.tree_descend(nh1, nl1, c1, bh, bl,
                                        interpret=interpret)
            return ref.tree_descend_ref(nh1, nl1, c1, bh, bl)
        return jax.lax.map(one, (nh, nl, c))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec("shard"), spec("shard"), spec("shard"), spec(), spec()),
        out_specs=spec("shard"), check_vma=False))


def _v_mask01(out) -> bool:
    a = np.asarray(out)
    return bool(a.size == 0 or (a.min() >= 0 and a.max() <= 1))


@jax.jit
def _descend_ref_jit(n_hi, n_lo, cs, b_hi, b_lo):
    return ref.tree_descend_ref(n_hi, n_lo, cs, b_hi, b_lo)


def bloom_probe(bits, keys, k: int = 3, interpret: bool | None = None):
    """bits (B, W) uint32 pre-gathered filter rows; keys (B,) int64."""
    with _Dispatch("bloom_probe") as call:
        keys = np.asarray(keys, dtype=np.int64).view(np.uint64)
        lo = call.up((keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
                     .view(np.int32))
        hi = call.up((keys >> np.uint64(32)).astype(np.uint32)
                     .view(np.int32))
        bits = call.up(bits)

        def oracle():
            # int verdict plane (not bool) so corrupt-injection has an
            # out-of-domain value for the validator to catch
            return jnp.asarray(ref.bloom_probe_ref(bits, lo, hi, k),
                               jnp.int32)

        if _on_tpu() or interpret:
            live = "interpret" if (interpret and not _on_tpu()) else "kernel"
            attempts = [
                (live, lambda: _bp.bloom_probe(
                    bits, lo, hi, k=k,
                    interpret=bool(interpret) and not _on_tpu())),
                ("oracle", oracle),
            ]
        else:
            attempts = [("jit", oracle), ("oracle", oracle)]
        out = _fault.run_op("bloom_probe", attempts, validate=_v_mask01)
        return call.down(out) == 1


def block_scan(scores, theta: float, interpret: bool | None = None):
    scores = jnp.asarray(scores, dtype=jnp.float32)
    if _on_tpu() or interpret:
        return _bs.block_scan(scores, theta,
                              interpret=bool(interpret) and not _on_tpu())
    return ref.block_scan_ref(scores, theta)


def morton_encode(cx, cy, interpret: bool | None = None):
    cx = jnp.asarray(cx, dtype=jnp.int32)
    cy = jnp.asarray(cy, dtype=jnp.int32)
    if _on_tpu() or interpret:
        return _mk.morton_encode(cx, cy,
                                 interpret=bool(interpret) and not _on_tpu())
    return ref.morton_ref(cx, cy)


def flash_attention(q, k, v, causal: bool = True,
                    interpret: bool | None = None):
    if _on_tpu() or interpret:
        return _fa.flash_attention(q, k, v, causal=causal,
                                   interpret=bool(interpret) and not _on_tpu())
    return ref.flash_attention_ref(q, k, v, causal=causal)
