"""Bench harness: the root wrapper's record contract (red is null and
non-zero, no CPU fallback) and a tiny real run of the in-package
measurement on the CPU backend."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_wrapper():
    spec = importlib.util.spec_from_file_location(
        "root_bench", os.path.join(REPO_ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_wrapper_red_record_contract():
    """The red record: the contract keys, null (never 0.0) where a number
    would go, and the error's tail."""
    w = _load_wrapper()
    rec = w._red_record("x" * 5000 + " the end")
    assert rec["metric"] == w.METRIC and rec["unit"] == w.UNIT
    assert rec["measured"] is False
    assert rec["value"] is None and rec["vs_baseline"] is None
    assert rec["mfu"] is None
    assert len(rec["error"]) == 2000 and rec["error"].endswith(" the end")


def test_stage_markers_go_to_stderr(capsys):
    """The in-package bench says on stderr which stage it is in, so the
    tail of a run cut at its time limit localizes the stall."""
    from deeplearning_cfn_tpu.bench import stage

    stage("backend_init")
    stage("build", preset="x", global_batch=8)
    out, err = capsys.readouterr()
    assert out == ""
    lines = err.strip().splitlines()
    assert lines[0].startswith("[bench-stage] t=+")
    assert lines[0].endswith("s backend_init")
    assert lines[1].endswith("s build preset=x global_batch=8")


def test_annotate_record_labels():
    """Fallback + underfill labels (r03 Weak #4/#5): seq-parallel presets on
    a seq=1 mesh are flagged as dense fallbacks; a bench batch below the
    preset's is flagged underfilled; healthy configs stay unlabeled."""
    from deeplearning_cfn_tpu.bench import annotate_record

    r = annotate_record({}, "bert_long_wikipedia", {"data": 1, "seq": 1},
                        gb=8, preset_gb=256)
    assert r["fallback"] is True
    assert "NOT a ring/Ulysses" in r["fallback_note"]
    assert r["batch_underfilled"] is True and r["preset_global_batch"] == 256

    r = annotate_record({}, "gpt_long_lm", {"data": 2, "seq": 4},
                        gb=64, preset_gb=64)
    assert r["fallback"] is False
    assert "fallback_note" not in r and "batch_underfilled" not in r

    r = annotate_record({}, "imagenet_resnet50", {"data": 8}, 512, 8192)
    assert "fallback" not in r
    assert r["batch_underfilled"] is True


def test_pipelined_mfu_uses_dense_twin_flops():
    """The GPipe preset's MFU numerator must come from the dense twin: the
    scanned trunk's own cost analysis under-counts by ~ticks x layers
    (r03 Weak #3). Compare the two counts at tiny matched shapes on CPU."""
    import jax

    from deeplearning_cfn_tpu.bench import _dense_equiv_flops, _flops_of
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.data import build_pipeline
    from deeplearning_cfn_tpu.parallel.mesh import build_mesh, \
        local_batch_size
    from deeplearning_cfn_tpu.config import MeshConfig
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = get_preset("bert_pipelined_wikipedia")
    cfg.train.global_batch = 8
    cfg.train.grad_accum_steps = 1
    cfg.data.seq_len = 32
    cfg.data.vocab_size = 128
    cfg.model.kwargs.update(hidden_size=32, num_layers=4, num_heads=2,
                            mlp_dim=64, max_len=32, n_microbatches=4)
    apply_overrides(cfg, ["data.prefetch=0", "data.synthetic=true"])
    cfg.data.num_train_examples = 8
    cfg.data.num_eval_examples = 8
    mesh = build_mesh(MeshConfig(data=-1))

    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer, build_schedule(cfg.schedule, 1000,
                                                       8, 100))
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=getattr(task, "param_rules", ()),
                               shard_opt_state=cfg.train.shard_opt_state)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    pipe = build_pipeline(cfg.data, local_batch_size(8, mesh),
                          cfg.model.num_classes, seed=0, train=True)
    dev_batch = trainer.device_batch(next(iter(pipe.one_epoch(0))))
    compiled = trainer.train_step.lower(
        state, dev_batch, jax.random.PRNGKey(1)).compile()
    scanned = _flops_of(compiled)
    dense = _dense_equiv_flops("bert_pipelined_wikipedia", cfg, mesh, 8)
    assert dense is not None and scanned is not None
    # The dense twin must count (substantially) more than the scanned
    # program whose trunk body is counted once: 4 layers x (4+S-1) ticks.
    assert dense > 1.5 * scanned, (dense, scanned)


def test_wrapper_red_record_has_null_value():
    """A red (unmeasured) contract record must carry null value/vs_baseline/
    mfu — never 0.0, which an aggregator would average in as a real zero —
    and the wrapper must exit non-zero with it. Drive the wrapper
    end-to-end with a preset the measurement rejects."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               DLCFN_BENCH_PRESET="no_such_preset")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT, env=env)
    assert proc.returncode == 1, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["measured"] is False
    assert rec["value"] is None
    assert rec["vs_baseline"] is None
    assert rec["mfu"] is None
    assert "no_such_preset" in rec["error"]
    assert "Traceback" in proc.stderr


def test_finalize_green_refuses_unrequested_cpu(monkeypatch):
    """A record taken on the CPU without the CPU having been asked for by
    name is a hard failure — there is no relabelling it, and no
    cpu_fallback_value to carry it along."""
    w = _load_wrapper()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    for rec in ({"value": 12.3, "mfu": None, "platform": "cpu",
                 "device_kind": "cpu"},
                {"value": 12.3, "device_kind": "cpu"}):
        with pytest.raises(RuntimeError, match="without the CPU having"):
            w._finalize_green(rec)

    # Explicitly-requested CPU (tests, operator smoke) stays green.
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rec = w._finalize_green({"value": 12.3, "platform": "cpu",
                             "device_kind": "cpu"})
    assert rec["measured"] is True and rec["value"] == 12.3

    # A real chip record is untouched.
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rec = w._finalize_green({"value": 2413.7, "platform": "tpu",
                             "device_kind": "TPU v5 lite"})
    assert rec["measured"] is True and rec["value"] == 2413.7


def test_finalize_green_nulls_any_unmeasured_record(monkeypatch):
    """Null-over-zero is not fallback-specific: a child that itself said
    measured=false (for any reason) must not ship numeric value/
    vs_baseline/mfu through the green path — even on a live accelerator
    with no CPU fallback in sight."""
    w = _load_wrapper()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rec = w._finalize_green(
        {"measured": False, "value": 99.9, "vs_baseline": 0.5, "mfu": 0.4,
         "device_kind": "TPU v5e", "error": "child: warmup diverged"})
    assert rec["measured"] is False
    assert rec["value"] is None
    assert rec["vs_baseline"] is None
    assert rec["mfu"] is None
    # The measurement's own error stands.
    assert rec["error"] == "child: warmup diverged"


def test_finalize_green_nulls_serving_perf_fields_when_unmeasured(
        monkeypatch):
    """The serving-scenario perf fields (speculation/quantization) follow
    the same null-over-zero rule on measured=false — and are left alone
    on records that never carried them."""
    w = _load_wrapper()
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rec = w._finalize_green(
        {"measured": False, "value": 99.9, "spec_gamma": 2,
         "spec_accept_rate": 0.9, "tokens_per_target_step": 2.5,
         "weight_bytes": 12345, "device_kind": "TPU v5e",
         "error": "child: warmup diverged"})
    for key in ("spec_gamma", "spec_accept_rate",
                "tokens_per_target_step", "weight_bytes"):
        assert rec[key] is None
    rec = w._finalize_green(
        {"measured": False, "value": 1.0, "device_kind": "TPU v5e",
         "error": "x"})
    assert "spec_gamma" not in rec  # key set untouched when absent


def test_bench_child_measures_on_cpu():
    """``python -m deeplearning_cfn_tpu.bench`` measures a tiny preset on
    the CPU it was given by name, prints the contract JSON with
    measured=true, names the device, carries mfu null (the CPU has no
    peak), and emits every stage marker through 'done' on stderr."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, "-m", "deeplearning_cfn_tpu.bench",
         "--preset", "cifar10_resnet20", "--steps", "3", "--warmup", "1",
         "--global-batch", "32"],
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["measured"] is True
    assert rec["value"] > 0
    assert rec["unit"] == "images/sec/chip"
    assert rec["global_batch"] == 32
    assert rec["platform"] == "cpu" and rec["device_kind"] == "cpu"
    assert rec["mfu"] is None
    for name in ("start", "import_jax", "backend_init", "devices_ok",
                 "build", "first_compile", "warmup", "timed", "done"):
        assert f"s {name}" in proc.stderr, (name, proc.stderr[-2000:])


def test_wrapper_goes_red_when_accelerator_dead(monkeypatch, capsys):
    """No accelerator answers: the wrapper prints a red record (measured
    false, null value) and returns non-zero. It does not measure on the
    CPU instead."""
    import deeplearning_cfn_tpu.bench as inner
    from deeplearning_cfn_tpu.runtime.platform import AcceleratorError

    def dead(**kwargs):
        raise AcceleratorError("no TPU: jax's default backend is 'cpu'")

    monkeypatch.setattr(inner, "run_bench", dead)
    w = _load_wrapper()
    assert w.main() == 1
    out, err = capsys.readouterr()
    rec = json.loads(out.strip().splitlines()[-1])
    assert rec["measured"] is False
    assert rec["value"] is None and rec["mfu"] is None
    assert "forced_platform" not in rec
    assert "AcceleratorError" in rec["error"] and "no TPU" in rec["error"]
    assert "AcceleratorError" in err  # the traceback


def test_wrapper_exits_nonzero_without_accelerator():
    """End-to-end on a host with no accelerator and no request for the
    CPU: jax quietly falls back to the CPU, and the wrapper must refuse —
    rc 1, measured false, null value — where it used to ship a forced-CPU
    number under the chip metric's name with rc 0."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               DLCFN_BENCH_PRESET="cifar10_resnet20",
               DLCFN_BENCH_STEPS="3", DLCFN_BENCH_WARMUP="1",
               DLCFN_BENCH_GLOBAL_BATCH="32")
    env.pop("JAX_PLATFORMS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "bench.py")],
        capture_output=True, text=True, timeout=300, cwd=REPO_ROOT, env=env)
    assert proc.returncode == 1, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["measured"] is False and rec["value"] is None
    assert "forced_platform" not in rec
    assert "no TPU" in rec["error"] and "'cpu'" in rec["error"]


def test_unknown_device_kind_is_an_error_cpu_has_no_peak():
    from types import SimpleNamespace

    from deeplearning_cfn_tpu.bench import peak_flops_per_chip

    dev = lambda platform, kind: SimpleNamespace(platform=platform,
                                                 device_kind=kind)
    assert peak_flops_per_chip(dev("cpu", "cpu")) is None
    assert peak_flops_per_chip(dev("tpu", "TPU v5 lite")) == 197e12
    assert peak_flops_per_chip(dev("tpu", "TPU v5p")) == 459e12
    with pytest.raises(ValueError, match="unknown accelerator kind"):
        peak_flops_per_chip(dev("tpu", "TPU v9 ultra"))
    with pytest.raises(ValueError, match="unknown accelerator kind"):
        peak_flops_per_chip(dev("gpu", "NVIDIA H100"))
