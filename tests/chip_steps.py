"""What the chip-compile tests share (``tests/test_chip_compile*.py``): a
described v5e that is not attached, the benchmark's own rehearsal, and the
readers of a compiled step's plan counters and text.

A kernel's own compile takes seconds. A cell's whole train step compiled for
the described chip takes one to three minutes of one worker, and ``--dist
loadfile`` keeps a file on one worker, so the five are spread over three
modules with no module over 300 s. xdist starts the files with the most tests
first: a module of one or two such tests starts last and sets the run's wall
(three of them did, PR 46's first run), so each module also holds ten or more
of the kernels' compiles, those of its own cells where there are any.

The rule for the next cell: its whole-step compile goes into the module where
the seconds are fewest, and asserts only what neither a kernel's own compile
test nor the driver's chip run of the cell shows (the plan counters, the
scopes in the text, "no row is scattered"). The bound on memory stays: one
line on a compile that is already paid for.

``tests/conftest.py`` runs these modules, by name, with the compile cache off
(a compile for a described chip is written and cannot be read back without
the chip) and with the worker's earlier traces forgotten."""

import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import pytest  # noqa: E402

from deeplearning_cfn_tpu.obs.trace import get_tracer  # noqa: E402


@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    return topo.devices[0]


def _row_scatters(text, width=2048):
    """The instructions of a compiled step that scatter rows of ``width``
    under an expert layer's ``moe_dispatch`` or ``moe_combine``: the rows go
    to the buffer and back by gathers (``models/moe.py:take_rows``,
    ``sum_rows``), so there are none; what is still scattered there is
    integers."""
    return [line.strip()[:200] for line in text.splitlines()
            if re.search(rf"= \w+\[\d+,{width}\]\S* scatter\(", line)
            and re.search(r"/moe_(dispatch|combine)/", line)]


def _norms_by_xla(text, rows=16384):
    """The instructions of a compiled step under ``qk_norm`` (or an
    ``RMSNorm`` called ``query_norm`` / ``key_norm``) that hold a result of
    ``rows`` positions and are no Pallas kernel's: XLA's norm over q or k,
    forward or backward. The norm is inside the rotary kernel
    (``ops/rope.py``), so there are none; what XLA still does there is the sum
    of the scale's gradient over the kernel's grid steps."""
    return [line.strip()[:200] for line in text.splitlines()
            if re.search(r'op_name="[^"]*\b(qk|query|key)_norm\b', line)
            and re.search(rf"= \(?\w+\[[\d,]*\b{rows}\b[\d,]*\]", line)
            and "tpu_custom_call" not in line
            and " get-tuple-element(" not in line]  # a kernel's second result


def _bench():
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    sys.path.insert(0, bench)
    try:
        from harness import manifest
        import rehearse_compile
    finally:
        sys.path.remove(bench)
    return manifest, rehearse_compile


def _rows_calls(since=None):
    """``moe.rows.calls`` as ``{(side, path): calls}``: the series that
    moved since ``since`` (a call's own return) by how far, or every
    series' count."""
    series = {tuple(dict(key)[label] for label in ("side", "path")): n
              for key, n in get_tracer().registry.counter(
                  "moe.rows.calls").series().items()}
    if since is None:
        return series
    return {key: n - since.get(key, 0) for key, n in series.items()
            if n > since.get(key, 0)}


def _gmm_calls(since=None):
    """``moe.gmm.calls`` as ``{(kernel, tile, divides)}``: the series that
    moved since ``since`` (a call's own return), or every series' count."""
    series = get_tracer().registry.counter("moe.gmm.calls").series()
    if since is None:
        return series
    return {tuple(dict(key)[label] for label in ("kernel", "tile", "divides"))
            for key, n in series.items() if n > since.get(key, 0)}
