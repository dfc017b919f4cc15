"""Device time of the train step by any pattern over the program's scopes.

``sections.py`` sorts every operation into the sections the first cell had.
A cell whose program has scopes of its own (the expert layer's ``moe_*``, a
sliding layer's kernels) reads them here by the same join: instruction name
in the trace -> the same instruction in the compiled step's text -> its
``op_name`` -> a pattern the reader gives. The step is compiled once more
for it (``sections.compile_step``: a hit in the persistent cache) and the
result kept in ``ctx``; an operation that is not an instruction of the text
by name and result type is left out, as there.
"""

from __future__ import annotations

import re
from typing import List, Optional, Tuple

from . import sections


def scoped_ops(ctx) -> Optional[List[Tuple[str, float]]]:
    """``(op_name, seconds)`` of every operation of device 0 in the traced
    window, or ``None`` where there is no trace."""
    if ctx["trace"] is None:
        return None
    if "scoped_ops" not in ctx:
        import jax

        compiled = sections.compile_step(
            ctx["cell"], jax.devices()[:ctx["device"]["count"]])
        scopes = sections.instruction_scopes(compiled.as_text())
        ops = ctx["trace"].op_seconds(0, ctx["window"])
        strangers = set(sections.foreign(ops, ctx["trace"].labels,
                                         scopes["labels"]))
        ctx["scoped_ops"] = [(scopes["all"][name], seconds)
                             for name, seconds in ops.items()
                             if name not in strangers]
    return ctx["scoped_ops"]


def seconds_matching(ctx, pattern: str, also=lambda op_name: True
                     ) -> Optional[float]:
    """Seconds of device 0 in the window in operations whose ``op_name``
    matches ``pattern`` (and ``also``); ``None`` where nothing matches, so
    that a program without the scope leaves the metric out."""
    ops = scoped_ops(ctx)
    if ops is None:
        return None
    rx = re.compile(pattern)
    found = [seconds for op_name, seconds in ops
             if rx.search(op_name) and also(op_name)]
    return sum(found) if found else None


def ms_per_step(ctx, pattern: str) -> Optional[float]:
    seconds = seconds_matching(ctx, pattern)
    return None if seconds is None else 1e3 * seconds / ctx["run"]["steps"]
