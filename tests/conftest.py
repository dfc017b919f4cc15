"""Test harness: 8 fake CPU devices (SURVEY.md §5 strategy #2).

The reference had no multi-node test harness at all; ours simulates every
mesh/pjit/collective path single-process by forcing the CPU backend with 8
virtual devices. Must run before jax initializes its backends, hence env
setup at conftest import time.
"""

import os
import shutil
import tempfile

os.environ.setdefault("JAX_ENABLE_X64", "0")
# The child processes tests start (the benchmark's tiny cells, the CLI's
# verbs, the net replicas) run with jax's persistent compile cache off, as
# the suite always has: through the environment, which they inherit. The
# test process itself has one, below.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from deeplearning_cfn_tpu.runtime.platform import force_cpu_platform  # noqa: E402

force_cpu_platform(8)

import jax  # noqa: E402
import pytest  # noqa: E402
from jax._src import lru_cache  # noqa: E402
from jax.experimental.compilation_cache import compilation_cache  # noqa: E402

# One compile cache for one test session. Every test builds its own tiny
# engine or trainer, so its jits are new Python objects and jax's in-memory
# cache misses, though the program is the one the previous test compiled:
# with the cache off, half the suite's CPU was such compiles. The directory
# is made by the controller outside the checkout, named to its workers
# through the environment they are started with, shared by them (what one
# worker compiled the other five find), and removed when the session ends.
_CACHE_DIR_VAR = "DLCFN_TEST_SESSION_COMPILE_CACHE"


def pytest_configure(config):
    if not hasattr(config, "workerinput"):  # the controller, or a lone run
        os.environ[_CACHE_DIR_VAR] = tempfile.mkdtemp(prefix="dlcfn-tests-jit-")


def pytest_unconfigure(config):
    if not hasattr(config, "workerinput"):
        shutil.rmtree(os.environ.pop(_CACHE_DIR_VAR), ignore_errors=True)


def _put_whole(self, key, val):
    """jax writes an entry in place, and a worker that looks the key up
    meanwhile reads half of it: write beside the entry and rename."""
    path = self.path / f"{key}{lru_cache._CACHE_SUFFIX}"
    if not path.exists():
        part = path.with_name(f"{path.name}.{os.getpid()}")
        part.write_bytes(val)
        os.replace(part, path)


lru_cache.LRUCache.put = _put_whole
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _open_compile_cache():
    jax.config.update("jax_compilation_cache_dir", os.environ[_CACHE_DIR_VAR])
    jax.config.update("jax_enable_compilation_cache", True)
    # jax decides once whether and where it caches: the reset makes it ask
    # again, and the compile makes it open the directory now and keep it. A
    # test that moves ``jax_compilation_cache_dir`` afterwards (every
    # in-process ``main()`` does, ``runtime/platform.py:
    # configure_compile_cache``, to ``.jax_cache`` in the checkout) moves
    # nothing.
    compilation_cache.reset_cache()
    jax.jit(lambda: 0)()


@pytest.fixture(scope="session", autouse=True)
def _session_compile_cache():
    _open_compile_cache()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake devices, got {devs}"
    return devs


@pytest.fixture()
def tmp_workdir(tmp_path):
    return str(tmp_path)


@pytest.fixture(scope="module", autouse=True)
def _modules_that_compile_without_the_cache(request):
    """Two kinds of module run with the cache off, as the whole suite did.

    ``tests/benchmark/``: several of its files read compile events
    (``jit.backend_compile_s``, the set-up spans), and a hit is not a compile.

    ``tests/test_chip_compile*.py``: a compile for a described chip is
    written to the cache and cannot be read back without the chip. These
    also search the text of a compiled step, and that text's table of stack
    frames names whoever first traced a shared ``jnp`` function in the
    process: jax keeps a function's jaxpr, frames and all. Forget what the
    worker's earlier files traced before such a file starts, so that it
    reads its own traces whichever file ran before it
    (``tests/test_rows_kernel.py`` did, in nine schedules of ten of ``-n 6
    --dist loadfile`` with PR 37's counts of tests)."""
    chip_compile = request.path.name.startswith("test_chip_compile")
    if not (chip_compile or request.path.parent.name == "benchmark"):
        yield
        return
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    if chip_compile:
        jax.clear_caches()
    yield
    _open_compile_cache()
