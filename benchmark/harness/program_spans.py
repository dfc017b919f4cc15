"""The program's own host spans, read from the run's trace file.

Since PR 24 a live span of ``deeplearning_cfn_tpu/obs/trace.py`` is also a
``jax.profiler.TraceAnnotation``: an event on a host plane of the same
``.xplane.pb`` as the device operations, on the profiler's clock. The
runner's ``Trace`` collects only the benchmark's own wrappers and refuses a
file with no device plane, and these readers also run in a CPU rehearsal, so
they open ``.bench_trace`` themselves (``run.py`` removes it after the
readers ran). A program without such spans, as the parent of PR 24 is,
yields none, and the metrics are left out.
"""

from __future__ import annotations

import collections
import os
import re
from typing import List, Optional, Tuple

from . import device, xplane
from .stats import median

TRACE_DIR = os.path.join(device.CHECKOUT, ".bench_trace")
# ``<layer>.<operation>``, the layers docs/OBSERVABILITY.md names.
PROGRAM_SPAN = re.compile(r"^(train|ckpt|serve|launch|fleet)\.[\w.]+$")

Span = Tuple[str, int, int]


def host_spans(path: str) -> List[Span]:
    """``(name, start_ns, end_ns)`` of every program span on the host
    planes of one trace file, by start."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if xplane.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if PROGRAM_SPAN.match(ev.name):
                    s = int(ev.start_ns)
                    spans.append((ev.name, s, s + int(ev.duration_ns)))
    return sorted(spans, key=lambda s: s[1])


def read(ctx) -> List[Span]:
    """The run's program spans, read once and kept in ``ctx``; none where
    the run left no trace file. The first call also prints device 0's idle
    time by the program span that covers most of each gap."""
    if "program_spans" not in ctx:
        try:
            path = xplane.find_xplane(TRACE_DIR)
        except xplane.TraceError:
            ctx["program_spans"] = []
            return []
        spans = ctx["program_spans"] = host_spans(path)
        counts = collections.Counter(name for name, _, _ in spans)
        ctx["say"](f"program spans in the trace: {dict(counts) or 'none'}")
        if ctx["trace"] is not None and spans:
            idle_by_program_span(ctx, spans)
    return ctx["program_spans"]


def idle_by_program_span(ctx, spans: List[Span]) -> List[List[object]]:
    """``Trace.idle_gaps`` with the program's spans where the runner's
    reduction has the benchmark's wrappers. Printed, not a metric. A gap
    goes whole to the span that covers most of it, however little that is,
    so the line also says how much of the idle time no span covers at all."""
    trace, window = ctx["trace"], ctx["window"]
    gaps = xplane.Trace(trace.ops, spans, trace.labels).idle_gaps(window)
    idle = sum(v for _, v in gaps)
    if not gaps:
        return gaps
    busy = trace.busy_intervals(min(trace.ops), window)
    covered = xplane._union(
        [(s, e) for _, s, e in spans if e > window[0] and s < window[1]]
        + busy)
    bare = (window[1] - window[0] - sum(e - s for s, e in covered)) / 1e9
    steps = ctx["run"]["steps"]
    ctx["say"](
        f"device 0 idle by program span, ms a step "
        f"({1e3 * idle / steps:.3f} in all): " + ", ".join(
            f"{k} {1e3 * v / steps:.3f} ({100 * v / idle:.1f} %)"
            for k, v in gaps)
        + f"; {1e3 * bare / steps:.3f} of it with no span over it at all")
    return gaps


def median_ms(ctx, name: str) -> Optional[float]:
    """Median duration in ms of the spans called ``name`` that lie inside
    the traced window (the whole trace where there is no window), or
    ``None`` where there are none."""
    w0, w1 = ctx["window"] or (-(2 ** 62), 2 ** 62)
    mine = [(e - s) / 1e6 for n, s, e in read(ctx)
            if n == name and s >= w0 and e <= w1]
    if not mine:
        return None
    ctx["say"](f"{name}: {len(mine)} spans inside the window, "
               f"{min(mine):.4f} ... {max(mine):.4f} ms")
    return median(mine)
