"""Backend selection and compile-cache placement, shared by every entry point.

The program runs in two ways: on the CPU, where the CPU was asked for by
name (tests, dry-run stacks, ``--accelerator cpu``), and on the TPU
otherwise. Nothing falls back from one to the other: an entry point that
was not given the CPU by name and finds no TPU stops.

A chip belongs to one process at a time, so a launcher that would start
several chip-needing processes refuses before any of them hangs at warm-up.

Importing this module does not touch jax backends; jax is imported lazily
inside the functions.
"""

from __future__ import annotations

import os
import re
from typing import Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# A fixed path: the directory is part of the cache key's lookup, so a cache
# that moves between runs (tempfile, pid, workdir) never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")


class AcceleratorError(RuntimeError):
    """No TPU where one was required, or a second process for one chip."""


def force_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Select the CPU backend, optionally with ``n_devices`` virtual devices.

    Must run before any jax call that initializes backends (``jax.devices``,
    ``device_count``, jit execution). Replaces any preexisting
    ``--xla_force_host_platform_device_count`` value — keeping a stale count
    would make device-count asserts fail for an environmental reason.
    """
    if n_devices is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        flag = f"--xla_force_host_platform_device_count={n_devices}"
        if "xla_force_host_platform_device_count" in flags:
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", flag, flags)
        else:
            flags = (flags + " " + flag).strip()
        os.environ["XLA_FLAGS"] = flags
    # The environment carries the choice to child processes; the config
    # update covers this process, where jax may already have been imported
    # (its flags read the environment once, at import).
    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    jax.config.update("jax_platforms", "cpu")


def cpu_requested(accelerator: str = "") -> bool:
    """True where the CPU was asked for by name: ``--accelerator cpu`` /
    ``stack.accelerator=cpu``, or ``JAX_PLATFORMS`` starting with ``cpu``."""
    return accelerator == "cpu" or \
        os.environ.get("JAX_PLATFORMS", "").startswith("cpu")


def require_accelerator(accelerator: str = "") -> str:
    """Select the backend for this process and return its platform.

    The CPU is used only where :func:`cpu_requested`; otherwise the default
    backend must be a TPU — jax's own silent fallback to the CPU is an
    error here, not a slower run. Also places the compile cache, since
    every caller is about to compile.
    """
    if accelerator == "cpu":
        force_cpu_platform()
    configure_compile_cache()
    if cpu_requested(accelerator):
        return "cpu"

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise AcceleratorError(
            f"no TPU: jax's default backend is {platform!r}. The CPU is "
            f"used only where it is asked for by name (--accelerator cpu, "
            f"stack.accelerator=cpu, or JAX_PLATFORMS=cpu).")
    return platform


def refuse_shared_chip(n_processes: int, accelerator: str, what: str) -> None:
    """Raise where ``what`` would have ``n_processes`` processes want one
    chip. Decided from the request alone — the caller may be a parent that
    must stay off jax so that its one child can have the chip."""
    if n_processes > 1 and not cpu_requested(accelerator):
        raise AcceleratorError(
            f"{what} needs {n_processes} processes on the accelerator, and "
            f"a chip belongs to one process at a time: every process after "
            f"the first would fail or hang at warm-up. Run the replicas in "
            f"one process (`fleet route`, `bench --fleet`), or ask for the "
            f"CPU by name. One process driving several chips is ROADMAP D4.")


def configure_compile_cache() -> Optional[str]:
    """Place jax's persistent compilation cache; call before the first jit.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax's own handling of it
    stands and no directory is set here. Otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Returns the directory in use.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir

    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
