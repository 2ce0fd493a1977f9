"""Share of the HBM roofline that the refinement kernel reached in the
traced window, %: the bytes its launches read and wrote over the kernel's
device time, against the chip's HBM bandwidth in ``peaks.json``.

The launches are the program's ``streak.kernel`` spans with op
``bucketed_min_core`` inside the window; each span's ``shapes`` stat lists
the planes handed to the device, ``dims`` planes of (B, m_pad) and then
``dims`` of (B, n_pad). A launch reads B·(m_pad + n_pad)·dims·4 bytes and
writes B·4; it computes B·m_pad·n_pad·6 operations (a subtraction, a
product and a sum per coordinate, and a minimum). ``peaks.json`` has no
float32 VPU peak for this chip, so the share is of the HBM bytes alone; a
kernel that is bound by its vector units reads low on it. The kernel's
device time is that of the device trace's ``tpu_custom_call`` operations
named ``bucketed_min_core``. A run with no such launch or no device
operation of that name reads nothing.
"""
import json
import re
from pathlib import Path

from streakbench import spans, trace_reduce

ROOT = Path(__file__).resolve().parents[2]
OP = "bucketed_min_core"
SHAPE = re.compile(r"[a-z0-9]+\[(\d+), (\d+)\]")


def launch_bytes(shapes: str) -> int:
    """Bytes one launch reads and writes, from its span's ``shapes``."""
    dims = [(int(b), int(p)) for b, p in SHAPE.findall(shapes)]
    half = len(dims) // 2
    b = dims[0][0]
    return (sum(p for _, p in dims[:half]) + sum(p for _, p in dims[half:])
            ) * b * 4 + b * 4


def measure(xspace) -> tuple:
    """(bytes, device seconds) of the refinement kernel inside the window
    of the profile `xspace`."""
    window, nbytes, device_ns, n_devices = None, 0, 0, 0
    host = []
    for plane in xspace.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == trace_reduce.WINDOW_SPAN and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name == "streak.kernel":
                        host.append((e.start_ns, dict(e.stats)))
    if window is None:
        return 0, 0.0
    w0, w1 = window
    for s, st in host:
        if w0 <= s < w1 and st.get("op") == OP and st.get("shapes"):
            nbytes += launch_bytes(str(st["shapes"]))
    for plane in xspace.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        n_devices += 1
        for ln in plane.lines:
            if ln.name not in trace_reduce.OPS_LINES:
                continue
            for e in ln.events:
                if (trace_reduce.is_kernel(e.name)
                        and trace_reduce.op_name(e.name) == OP):
                    s, t = max(e.start_ns, w0), min(e.start_ns
                                                    + e.duration_ns, w1)
                    device_ns += max(t - s, 0)
    return nbytes, device_ns / 1e9 / max(n_devices, 1)


def share(xspace, peak_bytes_per_s: float | None) -> float | None:
    """The kernel's bytes per second over `peak_bytes_per_s`, %."""
    nbytes, secs = measure(xspace)
    if not peak_bytes_per_s or not nbytes or secs <= 0:
        return None
    return 100.0 * nbytes / secs / peak_bytes_per_s


def read(rec):
    if rec.trace is None:
        return None
    import jax
    peaks = json.loads((Path(spans.__file__).parent / "peaks.json")
                       .read_text())
    peak = peaks.get(jax.devices()[0].device_kind, {}).get("hbm_bytes_per_s")
    if not peak:
        return None
    path = trace_reduce.find_xplane(ROOT / spans.TRACE_DIR)
    return share(trace_reduce.load(path), peak)
