"""Named spans on the profiler's clock.

`span(name, **meta)` is a `jax.profiler.TraceAnnotation`: while a profiler
records (``jax.profiler.start_trace``, TensorBoard's capture) it leaves a
host event named `name` with `meta` as its stats, on the same clock as the
device's operations; otherwise it costs a flag check. Metadata that takes
work to build is set inside the span, after ``sp.is_enabled()``::

    with span("streak.kernel", op="tree_descend") as sp:
        ...
        if sp.is_enabled():
            sp.set_metadata(shapes=...)

The serve path's spans, all named ``streak.*``: ``step`` (one
`SpatialServeEngine.step`), ``admit`` (a cursor built), ``scan``
(relational scans, merge joins and the APS choice), ``phase1`` (candidate
nodes: descent and Bloom probes), ``phase2`` (V* selection), ``phase3``
(the MBR join), ``refine`` (exact geometry), ``topk`` (result assembly and
the top-k merge) and ``kernel`` (one dispatch in `kernels/ops.py`, from
entry to its host result). Spans that work for one request carry its
``rid``; pooled ones carry ``rows`` and ``slots``.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """A context manager that records `name` while a profiler is on."""
    return TraceAnnotation(name, **meta)
