"""Tracing / profiling subsystem (SURVEY.md §6).

The reference had nothing in-repo — users fell back to ``nvidia-smi`` and the
Horovod timeline Chrome trace. The rebuild makes profiling native: a
``jax.profiler`` trace server per host (point TensorBoard or xprof at it), a
bracketed trace context for capturing N hot-loop steps, and a
``block_until_ready``-synced step timer whose numbers feed the
images/sec/chip north-star metric.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, Optional

import jax

from ..obs.metrics import Histogram, MetricsRegistry, percentile

DEFAULT_PROFILER_PORT = 9012


def start_profiler_server(port: int = DEFAULT_PROFILER_PORT) -> Optional[int]:
    """Start the per-host profiler server (the Horovod-timeline replacement:
    attach a trace viewer at any time instead of re-running with an env var).
    Returns the port, or None if a server is already running."""
    try:
        jax.profiler.start_server(port)
        return port
    except (RuntimeError, ValueError):  # already started
        return None


@contextlib.contextmanager
def trace_steps(log_dir: str) -> Iterator[None]:
    """Capture a device+host trace of the enclosed steps to ``log_dir``
    (TensorBoard 'profile' plugin format).

    ``stop_trace`` runs only if ``start_trace`` succeeded, and any error
    it raises is swallowed when the body already raised — the body's
    exception is the one the operator needs, and a secondary "no trace
    in progress" must never mask it."""
    jax.profiler.start_trace(log_dir)
    body_failed = False
    try:
        yield
    except BaseException:
        body_failed = True
        raise
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            if not body_failed:
                raise


class StepTimer:
    """Wall-clock step timing with explicit device sync.

    Async dispatch makes naive timing lie (the Python loop runs ahead of the
    device); this timer syncs on a result before reading the clock.

    Timings land in an ``obs`` :class:`Histogram` (``step_time_s``) — raw
    samples retained, exponential buckets for the Prometheus export — in a
    per-timer registry by default, or pass ``registry=`` to aggregate into
    a shared one.
    """

    def __init__(self, warmup: int = 2,
                 registry: Optional[MetricsRegistry] = None):
        self.warmup = warmup
        self.registry = registry or MetricsRegistry()
        self._hist: Histogram = self.registry.histogram(
            "step_time_s", "synced per-step wall time")
        self._count = 0
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, result=None) -> Optional[float]:
        """Sync on ``result`` (pytree of jax arrays) then record elapsed.
        Warmup steps (compile + cache effects) are discarded."""
        if result is not None:
            jax.block_until_ready(result)
        elapsed = time.perf_counter() - self._t0
        self._count += 1
        if self._count > self.warmup:
            self._hist.observe(elapsed)
        return elapsed

    @property
    def steps(self) -> int:
        return self._hist.count()

    def summary(self, items_per_step: int = 0) -> Dict[str, float]:
        times = self._hist.samples()
        if not times:
            return {"steps": 0}
        mean = self._hist.mean()
        out = {
            "steps": float(len(times)),
            "mean_step_s": mean,
            "min_step_s": min(times),
            "max_step_s": max(times),
            "p50_step_s": percentile(times, 50),
            "p95_step_s": percentile(times, 95),
        }
        if items_per_step:
            out["items_per_sec"] = items_per_step / mean
            out["items_per_sec_per_device"] = (
                items_per_step / mean / jax.device_count()
            )
        return out
