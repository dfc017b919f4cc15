"""runtime/platform.py: the CPU only where it was asked for by name, one
process for each chip, and a compile cache that is placed from outside."""

import json
import os
import subprocess
import sys
import tempfile

import jax
import pytest

from deeplearning_cfn_tpu.cli.main import main
from deeplearning_cfn_tpu.runtime import platform

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def cache_config():
    """Leave jax's cache directory as the test found it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_stands(monkeypatch, cache_config, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, the code sets no directory."""
    jax.config.update("jax_compilation_cache_dir", "untouched")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert platform.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "untouched"


def test_compile_cache_defaults_to_fixed_path_in_checkout(
        monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO_ROOT, ".jax_cache")
    assert platform.configure_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    # Fixed: no tempfile name, pid or time in it, and the same every call.
    assert not want.startswith(tempfile.gettempdir() + os.sep)
    assert str(os.getpid()) not in want
    assert platform.configure_compile_cache() == want
    with open(os.path.join(REPO_ROOT, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_suite_shares_one_compile_cache_outside_the_checkout(cache_config):
    """tests/conftest.py: the test process compiles into the session's one
    directory, which is not in the checkout (``.jax_cache`` is the program's
    own, and a test that calls ``main()`` in this process points jax's
    option at it without moving the open cache); the children a test starts
    run with the cache off."""
    assert jax.config.jax_enable_compilation_cache is True
    cache = os.environ["DLCFN_TEST_SESSION_COMPILE_CACHE"]
    assert os.path.isdir(cache)
    assert os.path.commonpath([cache, REPO_ROOT]) != REPO_ROOT
    platform.configure_compile_cache()  # as an in-process ``main()`` does

    def probe(x):
        return x * float(os.getpid())

    probe.__name__ = f"probe_of_worker_{os.getpid()}"  # jax names the entry
    jax.jit(probe)(1.0)
    assert any(name.startswith(f"jit_{probe.__name__}-")
               for name in os.listdir(cache))
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"


def test_the_same_program_built_twice_is_compiled_once():
    """What the session's cache is for: a second jit of the same tiny
    function is a new Python object, so jax's in-memory cache misses, and
    the persistent one answers."""
    from deeplearning_cfn_tpu.runtime import jit_events

    jit_events.install()
    salt = float(int.from_bytes(os.urandom(3), "little"))
    build = lambda: jax.jit(lambda x: x * salt + 1.0)
    assert build()(2.0) == 2.0 * salt + 1.0
    before = jit_events.totals("")
    assert build()(2.0) == 2.0 * salt + 1.0
    after = jit_events.totals("")
    assert (after["cache_requests"] - before["cache_requests"],
            after["cache_hits"] - before["cache_hits"]) == (1, 1)


@pytest.mark.parametrize("accelerator,env,want", [
    ("cpu", None, True),
    ("cpu", "tpu", True),
    ("", "cpu", True),
    ("tpu", "cpu", True),      # the suite's own case: conftest's platform
    ("tpu", "cpu,tpu", True),
    ("", None, False),
    ("tpu", None, False),
    ("tpu", "tpu", False),
    ("", "tpu,cpu", False),
])
def test_cpu_only_where_asked_for_by_name(monkeypatch, accelerator, env,
                                          want):
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    assert platform.cpu_requested(accelerator) is want


def test_require_accelerator_refuses_a_silent_cpu(monkeypatch, cache_config):
    """Not asked for the CPU, and jax's backend is the CPU all the same (no
    chip: its own quiet fallback): the entry point stops, and says which
    platform it found."""
    assert platform.require_accelerator() == "cpu"  # conftest asked for it
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(platform.AcceleratorError, match="'cpu'"):
        platform.require_accelerator()
    with pytest.raises(platform.AcceleratorError, match="no TPU"):
        platform.require_accelerator("tpu")


@pytest.mark.parametrize("argv", [
    ["train", "--preset", "cifar10_resnet20", "train.steps=1", "workdir={}"],
    ["eval", "--preset", "cifar10_resnet20", "workdir={}"],
    ["serve", "--preset", "transformer_nmt_wmt", "--requests", "none.jsonl",
     "workdir={}"],
    ["bench", "--collectives"],
    ["fleet", "route", "--preset", "transformer_nmt_wmt",
     "--requests", "none.jsonl", "workdir={}"],
])
def test_entry_points_stop_without_a_tpu(monkeypatch, capsys, tmp_path,
                                         cache_config, argv):
    monkeypatch.delenv("JAX_PLATFORMS")
    assert main([a.format(tmp_path) for a in argv]) == 1
    err = capsys.readouterr().err
    assert "no TPU" in err and "asked for by name" in err
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("argv,what", [
    (["fleet", "up", "--replicas", "2"], "fleet up --replicas 2"),
    (["fleet", "up", "--net", "--replicas", "3"],
     "fleet up --net --replicas 3"),
    (["bench", "--fleet", "--net", "--smoke"], "bench --fleet --net"),
])
def test_launchers_refuse_to_share_a_chip(monkeypatch, capsys, tmp_path,
                                          argv, what):
    """With an accelerator backend, more than one chip-needing process is
    refused at once — before any child is started — with a message that
    says a chip belongs to one process and points to ROADMAP D4."""
    reqs = tmp_path / "reqs.jsonl"
    reqs.write_text(json.dumps({"src_ids": [5, 2]}) + "\n")
    if argv[0] == "fleet":
        argv = argv + ["--preset", "transformer_nmt_wmt",
                       "--requests", str(reqs), "--run-root",
                       str(tmp_path / "fleet"), f"workdir={tmp_path}"]
    if argv[0] == "bench":
        # `bench` itself runs jax; let it believe it has its chip.
        monkeypatch.setattr(platform, "require_accelerator",
                            lambda accelerator="": "tpu")
    monkeypatch.delenv("JAX_PLATFORMS")
    assert main(argv) == 1
    err = capsys.readouterr().err
    assert what in err
    assert "a chip belongs to one process" in err and "D4" in err
    assert not (tmp_path / "fleet").exists()


def test_one_replica_or_the_cpu_is_not_refused(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS")
    platform.refuse_shared_chip(1, "tpu", "fleet up --replicas 1")
    platform.refuse_shared_chip(4, "cpu", "fleet up --replicas 4")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    platform.refuse_shared_chip(4, "", "bench --fleet --net")


def test_replica_children_inherit_the_environment_as_it_is():
    """No default to the CPU for a replica child: the spec adds nothing to
    the environment the launcher passes on."""
    from deeplearning_cfn_tpu.net.bench import make_server_spec

    spec, _ = make_server_spec("r0", "/nonexistent/run")
    assert spec.env == {}


def test_entry_point_subprocess_stops_without_a_tpu(tmp_path):
    """As a user would hit it on a machine with no chip: a fresh process,
    no JAX_PLATFORMS, jax falls back to the CPU by itself — and `train`
    stops with rc 1 instead of carrying on there."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning_cfn_tpu.cli", "train",
         "--preset", "cifar10_resnet20", f"workdir={tmp_path}",
         "train.steps=1"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT, env=env)
    assert proc.returncode == 1, proc.stderr[-2000:]
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr
    assert os.listdir(tmp_path) == []
