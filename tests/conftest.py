"""Test harness: 8 fake CPU devices (SURVEY.md §5 strategy #2).

The reference had no multi-node test harness at all; ours simulates every
mesh/pjit/collective path single-process by forcing the CPU backend with 8
virtual devices. Must run before jax initializes its backends, hence env
setup at conftest import time.
"""

import os

os.environ.setdefault("JAX_ENABLE_X64", "0")
# The suite runs with jax's persistent compile cache off, so that tier-1
# neither grows a cache in the checkout nor changes its timing. Through the
# environment, so that the child processes tests start inherit it too.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

from deeplearning_cfn_tpu.runtime.platform import force_cpu_platform  # noqa: E402

force_cpu_platform(8)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake devices, got {devs}"
    return devs


@pytest.fixture()
def tmp_workdir(tmp_path):
    return str(tmp_path)


@pytest.fixture(scope="module", autouse=True)
def _chip_compile_reads_its_own_traces(request):
    """``tests/test_chip_compile.py`` searches the text of a compiled step,
    and that text's table of stack frames names whoever first traced a
    shared ``jnp`` function in the process: jax keeps a function's jaxpr,
    frames and all. Forget what the worker's earlier files traced before
    that file starts, so that it reads its own traces whichever file ran
    before it (``tests/test_rows_kernel.py`` did, in nine schedules of ten
    of ``-n 6 --dist loadfile`` with PR 37's counts of tests)."""
    if request.path.name == "test_chip_compile.py":
        jax.clear_caches()
    yield
