"""The device a run is on: the chips it may use, their published peaks, the
compile cache inside the checkout, and jax's own compile events.

Nothing here falls back. A cell asks for ``chips`` TPU chips; a process that
finds another platform, or fewer chips, raises :class:`NoAccelerator` and
``run.py`` ends without a result line.
"""

from __future__ import annotations

import os
from typing import Dict, List

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# A fixed path inside the checkout: the directory is part of the cache's key,
# and the driver gives each side of a comparison its own checkout.
COMPILE_CACHE_DIR = os.path.join(CHECKOUT, ".jax_cache")

# Published peaks of one chip, keyed by jax's ``device_kind``. A kind that is
# not here is an error, never a default.
# Source: Google Cloud documentation, "TPU v5e" system architecture page
# (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e
# at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect per chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


class NoAccelerator(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def peaks_of(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; the table "
            f"in benchmark/harness/device.py has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def place_compile_cache() -> str:
    """Point jax's persistent compilation cache at the checkout, before jax
    is imported by anything else, and let small programs into it: without
    this, every program that compiles in under jax's 1 s threshold (the
    trainer's markers and norms, the weights) is compiled again in every run.

    Through the environment, so the program's own
    ``configure_compile_cache`` ("JAX_COMPILATION_CACHE_DIR stands where
    set") takes the directory it is given. The driver's own value, where it
    sets one, stands."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE_DIR)
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def cpu_rehearsal() -> bool:
    """True where the CPU was asked for by name *and* the caller said it is a
    rehearsal. The tests set both; the driver sets neither, so a run that
    finds no TPU there fails."""
    return os.environ.get("JAX_PLATFORMS", "").startswith("cpu") and \
        os.environ.get("BENCHMARK_REHEARSAL") == "cpu"


def require_chips(chips: int) -> List:
    """The first ``chips`` devices, all TPU chips, or :class:`NoAccelerator`.
    More visible devices than ``chips`` change nothing: the rest are left
    alone."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"jax found no backend: {e}") from e
    platform = devices[0].platform
    if platform != "tpu" and not (platform == "cpu" and cpu_rehearsal()):
        raise NoAccelerator(
            f"needs {chips} TPU chip(s), but jax's default backend is "
            f"{platform!r} with {len(devices)} device(s)")
    if len(devices) < chips:
        raise NoAccelerator(
            f"needs {chips} {platform} chip(s), but jax sees "
            f"{len(devices)}")
    return list(devices[:chips])


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest of ``devices``: the allocator's
    ``peak_bytes_in_use`` plus ``peak_bytes_reserved``. On a TPU the buffers
    jax allocates (arguments, results, state) are counted in the first, and
    the temporaries of the compiled programs in the second: a 336 MB
    temporary showed as 70 MB in use and 336 MB reserved (my chip run,
    PR 23). 0 where the backend keeps no such counts, as the CPU's does
    not."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def describe(devices) -> Dict[str, object]:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": memory_peak_bytes(devices)}


class CompileEvents:
    """What jax itself reports about compilation while this object listens:
    seconds spent in backend compiles, how many there were, and what the
    persistent cache was asked and answered. ``mark()`` starts a new count,
    so the window's own compiles (there should be none) are told apart from
    set-up's."""

    _DURATIONS = {"/jax/core/compile/backend_compile_duration": "compile_s"}
    _EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
               "cache_requests",
               "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax

        self.totals = self._zero()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    @staticmethod
    def _zero():
        return {"compile_s": 0.0, "compiles": 0, "cache_requests": 0,
                "cache_hits": 0}

    def _on_event(self, event, **_):
        key = self._EVENTS.get(event)
        if key:
            self.totals[key] += 1

    def _on_duration(self, event, duration, **_):
        key = self._DURATIONS.get(event)
        if key:
            self.totals[key] += float(duration)
            self.totals["compiles"] += 1

    def mark(self) -> Dict[str, float]:
        """The counts since the last mark, which this call resets."""
        out, self.totals = self.totals, self._zero()
        return out
