"""Reduce a profiler trace (`.xplane.pb`) to device busy and idle time.

Device planes are those named ``/device:TPU:<n>``; on each, the line of
XLA operations carries one event per operation run, named by its HLO
text (``%tree_descend.1 = s32[8,1,2048]{...} custom-call(...), ...``).
Busy time is the union of those events' intervals inside the window,
averaged over the devices. Kernel time is the device time of the Pallas
kernels: the custom calls to ``tpu_custom_call``, each named after the
jitted wrapper in ``kernels/ops.py`` that launched it. Operations are
reported by that name without its ``.<n>`` suffix. The window is the host
span named ``WINDOW_SPAN``; each idle gap inside it is named by the
innermost host span of the benchmark (``bench.*``) that covers the gap's
middle.
"""
from __future__ import annotations

import collections
import dataclasses
import gzip
import re
from pathlib import Path

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
OPS_LINES = ("XLA Ops",)
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
TOP = 10


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                  # mean over devices
    kernel_s: float                # mean over devices
    n_devices: int
    device_ops: list               # [[name, seconds], ...] most time first
    idle_gaps: list                # [[host span, seconds], ...] longest first

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    @property
    def kernel_pct(self) -> float:
        return 100.0 * self.kernel_s / self.window_s


def _union(iv: list) -> list:
    out: list = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def is_kernel(name: str) -> bool:
    return KERNEL_MARK in name


def op_name(name: str) -> str:
    """``%merge_join_ranks.3 = (...) custom-call(...)`` -> ``merge_join_ranks``."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: Path):
    """The profile at `path`, an ``.xplane.pb`` or a gzip of one."""
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    return ProfileData.from_serialized_xspace(data)


def reduce(path: Path) -> Reduced:
    pd = load(path)
    host_spans: list = []
    device_lines: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [ln for ln in plane.lines if ln.name in OPS_LINES]
            device_lines.append([(e.name, e.start_ns, e.duration_ns)
                                 for ln in ops for e in ln.events])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(HOST_PREFIX):
                        host_spans.append((e.name, e.start_ns,
                                           e.start_ns + e.duration_ns))
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW_SPAN} span")
    w0, w1 = windows[0]
    if not device_lines:
        raise ValueError(f"{path}: no TPU device plane")
    busy = kernel = 0.0
    op_time: collections.Counter = collections.Counter()
    gaps: list = []
    for events in device_lines:
        iv = []
        for name, s, d in events:
            s0, e0 = max(s, w0), min(s + d, w1)
            if e0 <= s0:
                continue
            iv.append((s0, e0))
            op_time[op_name(name)] += (e0 - s0) / len(device_lines)
            if is_kernel(name):
                kernel += (e0 - s0) / len(device_lines)
        merged = _union(iv)
        busy += sum(e - s for s, e in merged) / len(device_lines)
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((s, e))
    spans = [(n, s, e) for n, s, e in host_spans if n != WINDOW_SPAN]

    def label(s: float, e: float) -> str:
        mid = (s + e) / 2
        cover = [(ee - ss, n) for n, ss, ee in spans if ss <= mid <= ee]
        return min(cover)[1] if cover else "none"

    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=busy / 1e9, kernel_s=kernel / 1e9,
        n_devices=len(device_lines),
        device_ops=[[n, t / 1e9] for n, t in op_time.most_common(TOP)],
        idle_gaps=[[label(s, e), (e - s) / 1e9] for s, e in gaps[:TOP]])


def describe(path: Path, top: int = 25) -> str:
    """The trace's planes and lines, with the events that took most time on
    each line and the stats of the first of them: what to look at before
    changing the rules above."""
    out = []
    for plane in load(path).planes:
        out.append(f"plane {plane.name}")
        for ln in plane.lines:
            time_of: collections.Counter = collections.Counter()
            count: collections.Counter = collections.Counter()
            stats = {}
            for e in ln.events:
                time_of[e.name] += e.duration_ns
                count[e.name] += 1
                stats.setdefault(e.name, list(e.stats))
            out.append(f"  line {ln.name!r}: {sum(count.values())} events")
            for name, ns in time_of.most_common(top):
                out.append(f"    {ns / 1e6:12.3f} ms {count[name]:7d}x "
                           f"{name[:100]!r} {str(stats[name])[:400]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    xplane = find_xplane(Path(sys.argv[1]))
    print(describe(xplane))
    print(reduce(xplane))
