"""Device time in collective operations, from the trace of a cell on more
than one chip.

A collective is a device operation that is one by its instruction's name, all
that a trace keeps of it: the partitioner's own are named after their opcode
(``all-reduce.3``, ``all-gather-start.1``, ``all-gather-done.1``), one that the
program wrote is named after jax's primitive (``all_gather.5``,
``reduce_scatter.88``, ``psum.2``), and the TPU compiler's asynchronous forms
are ``async-collective-start.N`` / ``-done.N`` (the transfer runs between the
two halves and each half is an operation of its own). Its time is exposed
where the same chip runs no other operation meanwhile. Both are a chip's, the
mean over the cell's chips, a step.
"""

from __future__ import annotations

import re
from typing import List, Optional, Tuple

from .xplane import _union

COLLECTIVE = re.compile(
    r"^(all[-_]gather|reduce[-_]scatter|all[-_]reduce|psum|all[-_]to[-_]all|"
    r"collective[-_]permute|ppermute|async-collective)"
    r"(-start|-done)?(\.\d+)?$")


def _intervals(trace, device: int, window) -> Tuple[List, List]:
    """``(collectives, others)``: the merged intervals of one device's
    collective operations and of its other operations, cut to the window."""
    w0, w1 = window
    mine, others = [], []
    for name, s, e in trace.ops[device]:
        if e <= w0 or s >= w1:
            continue
        (mine if COLLECTIVE.match(name) else others).append(
            (max(s, w0), min(e, w1)))
    return _union(mine), _union(others)


def _overlap(a: List, b: List) -> int:
    """Nanoseconds in both of two lists of merged, sorted intervals."""
    total, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        i = j
        while i < len(b) and b[i][0] < e:
            total += min(e, b[i][1]) - max(s, b[i][0])
            i += 1
    return total


def ms_per_step(ctx, exposed: bool) -> Optional[float]:
    """Milliseconds a step a chip spends in collective operations (or, with
    ``exposed``, in them and in nothing else), the mean over the chips of
    the trace; ``None`` where there is no trace or no collective in it (a
    cell on one chip)."""
    trace = ctx["trace"]
    if trace is None:
        return None
    per_device = []
    for device in sorted(trace.ops):
        mine, others = _intervals(trace, device, ctx["window"])
        took = sum(e - s for s, e in mine)
        per_device.append(took - _overlap(mine, others) if exposed else took)
    if not any(per_device):
        return None
    return sum(per_device) / len(per_device) / 1e6 / ctx["run"]["steps"]
