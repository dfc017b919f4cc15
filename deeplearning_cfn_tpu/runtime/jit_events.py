"""jax's own account of tracing, lowering and compiling, in the program's
registry.

jax times every function it stages out and tells whoever listens
(``jax.monitoring``): the Python trace to a jaxpr, the jaxpr's lowering to
an MLIR module, and the backend compile, which with the persistent cache on
brackets ``compile_or_get_cached`` and so *is* the cache's retrieval on a
hit. :func:`install` registers one listener for the process that adds them
to the default tracer's registry:

- counters ``jit.trace_s``, ``jit.lower_s``, ``jit.backend_compile_s`` and
  their ``jit.*_count``, labelled ``fun`` by the name jax passes
  (``train_step`` for the trace, ``jit(train_step)`` for the other two:
  the wrapper is taken off);
- counters ``jit.cache_requests``, ``jit.cache_hits``, ``jit.cache_misses``
  (a miss is counted where the compile was written to the cache),
  ``jit.cache_retrieval_s`` and ``jit.cache_saved_s`` (jax's
  ``compile_time_saved_sec``, the stored compile time less the retrieval;
  a retrieval that took longer adds nothing). These carry no name.

An inner jit's events fall inside its caller's, so a reader takes one
label and never sums over labels. :func:`totals` is what a caller brackets
a piece of work with (``Trainer.fit`` its first step: the differences are
the attrs of ``train.first_step``, which ``obs summarize`` prints), and
:func:`watch` hands one caller at a time the events of one function as they
come (``fit``, to tell a retrace). ``DLCFN_OBS_OFF=1`` leaves the listener
registered and deaf. ``obs/`` imports without jax; this module is where the
two meet.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, Optional, Tuple

from jax import monitoring

from ..obs.trace import get_tracer, obs_enabled

PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_s",
}
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}

Watcher = Callable[[str, float], None]
Watched = Tuple[Optional[str], Optional[Watcher]]

_lock = threading.Lock()
_installed = False
_watched: Watched = (None, None)


def _bare(fun_name: str) -> str:
    """``jit(train_step)`` (a module's name) as ``train_step``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if not obs_enabled():
        return
    registry = get_tracer().registry
    phase = PHASES.get(event)
    if phase is None:
        name = _CACHE_SECONDS.get(event)
        if name is not None:
            registry.counter(f"jit.{name}").inc(max(float(duration), 0.0))
        return
    fun = _bare(str(kwargs.get("fun_name", "")))
    registry.counter(f"jit.{phase}_s").inc(float(duration), fun=fun)
    registry.counter(f"jit.{phase}_count").inc(fun=fun)
    watched, watcher = _watched
    if fun == watched:
        watcher(phase, float(duration))


def _on_event(event: str, **_kwargs) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None and obs_enabled():
        get_tracer().registry.counter(f"jit.{name}").inc()


def install() -> None:
    """Register the listener with jax, once a process however often it is
    called."""
    global _installed
    with _lock:
        if _installed:
            return
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _installed = True


def watch(fun: Optional[str], watcher: Optional[Watcher]) -> Watched:
    """From now on call ``watcher(phase, seconds)`` after each trace,
    lowering and backend compile of the function jax calls ``fun``, on the
    thread that provoked it. One pair has them at a time: the pair that had
    them is returned, and the caller gives them back when it is done
    (``watch(*former)``)."""
    global _watched
    former, _watched = _watched, (fun, watcher)
    return former


def totals(fun: str) -> Dict[str, float]:
    """What the registry holds now for ``fun`` and for the cache. A caller
    that brackets a piece of work subtracts two of these."""
    registry = get_tracer().registry
    out = {f"{phase}_s": registry.counter(f"jit.{phase}_s").value(fun=fun)
           for phase in PHASES.values()}
    for name in _CACHE_EVENTS.values():
        out[name] = int(registry.counter(f"jit.{name}").value())
    for name in _CACHE_SECONDS.values():
        out[name] = registry.counter(f"jit.{name}").value()
    return out
