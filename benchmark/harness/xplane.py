"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: the device's busy time, each operation's summed time, the idle
gaps labelled by what the host was doing.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is a
plane named ``/device:TPU:<n>``; its operations are the events of its
``XLA Ops`` line. Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, found by name on the host planes. A
trace with no device plane fails loudly: it was not taken on a chip.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"


class TraceError(RuntimeError):
    pass


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def label(event_name: str) -> str:
    """The instruction's name and its result type, for a breakdown a reader
    can place: ``fusion.18 f32[16,1024,50257]``."""
    name, _, rest = event_name.partition(" = ")
    result = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name.lstrip('%')} {result}"[:96].strip()


def short_name(event_name: str) -> str:
    """The instruction's name: the trace names a device operation by its
    whole HLO text (``%fusion.18 = f32[...] fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


class Trace:
    """``ops[device]``: ``(name, start_ns, end_ns)`` of every device
    operation; ``spans``: ``(name, start_ns, end_ns)`` of the host spans
    named in ``span_names``."""

    def __init__(self, ops: Dict[int, List[Tuple[str, int, int]]],
                 spans: List[Tuple[str, int, int]],
                 labels: Optional[Dict[str, str]] = None):
        if not ops or not any(ops.values()):
            raise TraceError("the trace has no device plane with operations: "
                             "it was not taken on a chip, or nothing ran")
        self.ops = ops
        self.labels = labels or {}
        self.spans = sorted(spans, key=lambda s: s[1])
        starts = [e[1] for evs in ops.values() for e in evs]
        ends = [e[2] for evs in ops.values() for e in evs]
        self.first_ns, self.last_ns = min(starts), max(ends)

    @classmethod
    def from_file(cls, path: str, span_names: Sequence[str]) -> "Trace":
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        wanted = set(span_names)
        ops: Dict[int, List[Tuple[str, int, int]]] = {}
        spans: List[Tuple[str, int, int]] = []
        labels: Dict[str, str] = {}
        seen = []
        for plane in data.planes:
            seen.append(plane.name)
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name == OPS_LINE:
                    evs = ops.setdefault(int(m.group(1)), [])
                    for ev in line.events:
                        name = short_name(ev.name)
                        labels.setdefault(name, label(ev.name))
                        s = int(ev.start_ns)
                        evs.append((name, s, s + int(ev.duration_ns)))
                elif not m and wanted:
                    for ev in line.events:
                        if ev.name in wanted:
                            s = int(ev.start_ns)
                            spans.append((ev.name, s,
                                          s + int(ev.duration_ns)))
        try:
            return cls(ops, spans, labels)
        except TraceError as e:
            raise TraceError(f"{e}; planes in {path}: {seen}") from e

    # -- the window ---------------------------------------------------------

    def window(self, span_name: Optional[str] = None) -> Tuple[int, int]:
        """The traced window: from the first start to the last end of the
        host spans named ``span_name`` where there are any, else from the
        first device operation to the last."""
        if span_name:
            mine = [s for s in self.spans if s[0] == span_name]
            if mine:
                return min(s[1] for s in mine), max(s[2] for s in mine)
        return self.first_ns, self.last_ns

    def busy_intervals(self, device: int, window: Tuple[int, int]):
        w0, w1 = window
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in self.ops[device]
                   if e > w0 and s < w1]
        return _union(clipped)

    def busy_s(self, window: Tuple[int, int]) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        per = [sum(e - s for s, e in self.busy_intervals(d, window))
               for d in sorted(self.ops)]
        return sum(per) / len(per) / 1e9

    # -- operations ---------------------------------------------------------

    def op_seconds(self, device: int = 0,
                   window: Optional[Tuple[int, int]] = None
                   ) -> Dict[str, float]:
        """Summed seconds of each operation name on one device. A ``while``
        or ``conditional`` spans its body's operations, which are counted
        themselves, so the containers are left out."""
        w0, w1 = window or (self.first_ns, self.last_ns)
        out: Dict[str, float] = {}
        for name, s, e in self.ops[device]:
            if e <= w0 or s >= w1 or is_container(name):
                continue
            out[name] = out.get(name, 0.0) + (min(e, w1) - max(s, w0)) / 1e9
        return out

    def seconds_matching(self, pattern: str, device: int = 0,
                         window=None) -> Tuple[float, int]:
        """Summed seconds and count of names the operations matching
        ``pattern`` have on one device."""
        rx = re.compile(pattern)
        hit = {n: s for n, s in self.op_seconds(device, window).items()
               if rx.search(n)}
        return sum(hit.values()), len(hit)

    def events_named(self, names, device: int = 0, window=None
                     ) -> Tuple[float, int]:
        """Summed seconds and the number of events whose operation is one
        of ``names`` (with or without the leading ``%``)."""
        w0, w1 = window or (self.first_ns, self.last_ns)
        wanted = {short_name(n) for n in names}
        total, count = 0, 0
        for name, s, e in self.ops[device]:
            if name in wanted and e > w0 and s < w1:
                total += min(e, w1) - max(s, w0)
                count += 1
        return total / 1e9, count

    def top_ops(self, n: int = 10, window=None) -> List[List[object]]:
        ops = self.op_seconds(0, window)
        return [[self.labels.get(k, k), v] for k, v in
                sorted(ops.items(), key=lambda kv: -kv[1])[:n]]

    # -- idle gaps ----------------------------------------------------------

    def idle_gaps(self, window: Tuple[int, int], n: int = 10
                  ) -> List[List[object]]:
        """The idle time of device 0 summed by label: the host span that
        covers most of each gap (the span of the whole window aside), or
        ``(no span)``."""
        busy = self.busy_intervals(min(self.ops), window)
        edges = [window[0]] + [t for iv in busy for t in iv] + [window[1]]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        by_label: Dict[str, float] = {}
        si = 0
        spans = [s for s in self.spans
                 if not (s[1] <= window[0] and s[2] >= window[1])]
        for g0, g1 in gaps:
            while si < len(spans) and spans[si][2] <= g0:
                si += 1
            cover: Dict[str, int] = {}
            j = si
            while j < len(spans) and spans[j][1] < g1:
                ov = min(spans[j][2], g1) - max(spans[j][1], g0)
                if ov > 0:
                    cover[spans[j][0]] = cover.get(spans[j][0], 0) + ov
                j += 1
            label = max(cover, key=cover.get) if cover else "(no span)"
            by_label[label] = by_label.get(label, 0.0) + (g1 - g0) / 1e9
        return [[k, v] for k, v in
                sorted(by_label.items(), key=lambda kv: -kv[1])[:n]]


def is_container(name: str) -> bool:
    base = short_name(name).split(".")[0]
    return base in ("while", "conditional", "call")


CUSTOM_CALL = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = .*custom-call\(.*"
    r'custom_call_target="tpu_custom_call"', re.M)


def pallas_calls(hlo_text: str) -> List[Dict[str, object]]:
    """The Pallas kernels of a compiled program's text: the instruction's
    name (what the trace calls the operation), whether it belongs to the
    backward pass (its ``op_name`` went through ``transpose(jvp(...))``), and
    the shape of its first operand."""
    out = []
    for m in CUSTOM_CALL.finditer(hlo_text):
        line = hlo_text[m.start():hlo_text.find("\n", m.start())]
        op_name = re.search(r'op_name="([^"]*)"', line)
        shape = re.search(r"operand_layout_constraints=\{\w+\[([\d,]+)\]",
                          line)
        out.append({
            "name": m.group("name"),
            "backward": bool(op_name and "transpose(" in op_name.group(1)),
            "shape": [int(x) for x in shape.group(1).split(",")]
            if shape else None})
    return out


def structure(path: str, limit: int = 12) -> List[str]:
    """A few lines on what a trace file holds, for a first look by hand."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            out.append(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:limit]:
                stats = {k: (str(v)[:60]) for k, v in list(ev.stats)[:8]}
                out.append(f"    {ev.name!r} start={ev.start_ns} "
                           f"dur={ev.duration_ns} {stats}")
    return out


def idle_share(ctx):
    """Percent of the traced window in which no operation ran on the device,
    averaged over the cell's chips; ``None`` where there is no trace."""
    d = ctx["device"]
    if "busy_s" not in d:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
