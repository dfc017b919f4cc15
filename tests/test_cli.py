"""Tests for L5: the `stack create → train` CLI flow — the reference's
user-facing contract (SURVEY.md §4.1/§4.4), exercised end-to-end against the
dry-run control plane."""

import json
import os
import sys

import pytest

from deeplearning_cfn_tpu.cli import main


def test_presets_lists_all_five(capsys):
    assert main(["presets"]) == 0
    out = capsys.readouterr().out
    for name in ["cifar10_resnet20", "imagenet_resnet50",
                 "bert_base_wikipedia", "maskrcnn_coco",
                 "transformer_nmt_wmt"]:
        assert name in out


def test_config_shows_resolved_preset_with_overrides(capsys):
    assert main(["config", "--preset", "cifar10_resnet20",
                 "train.global_batch=64"]) == 0
    cfg = json.loads(capsys.readouterr().out)
    assert cfg["model"]["name"] == "resnet20"
    assert cfg["train"]["global_batch"] == 64


def test_doctor_passes_on_cpu(capsys, devices):
    assert main(["doctor"]) == 0
    out = capsys.readouterr().out
    for check in ["presets: ok", "native-loader: ok", "backend-init: ok",
                  "device-exec: ok", "mesh: ok", "all checks passed"]:
        assert check in out, out


def test_doctor_says_which_loader_and_which_backend(capsys, monkeypatch):
    """The loader's degradation is visible, and a backend that is not the
    one asked for fails the preflight instead of being skipped."""
    assert main(["doctor"]) == 0
    out = capsys.readouterr().out
    assert "native-loader: ok — native (_dataio." in out \
        or "native-loader: ok — python loader (" in out
    assert "cpu device(s)" in out
    monkeypatch.delenv("JAX_PLATFORMS")
    assert main(["doctor"]) == 1
    out = capsys.readouterr().out
    assert "backend: FAIL" in out and "no TPU" in out
    assert "device-exec" not in out and "CHECKS FAILED" in out


def test_config_rejects_unknown_override():
    with pytest.raises(KeyError):
        main(["config", "--preset", "cifar10_resnet20", "train.nope=1"])


def test_bench_collectives_verb(capsys, devices):
    """`bench --collectives` is the nccl-tests role: one JSON record per
    collective with a positive bus bandwidth over the 8-device mesh."""
    assert main(["bench", "--collectives", "--size-mb", "2"]) == 0
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert {r["op"] for r in lines} == \
        {"psum", "all_gather", "psum_scatter", "ppermute"}
    for r in lines:
        assert r["ranks"] == 8
        assert r["busbw_gbps"] > 0


def test_bench_without_a_mode_points_to_the_benchmark(capsys):
    """Training is timed by one program; `bench` alone says which."""
    assert main(["bench"]) == 2
    captured = capsys.readouterr()
    assert "benchmark/run.py --workload" in captured.err
    assert captured.out == ""


def test_stack_lifecycle(tmp_path, capsys):
    state_dir = str(tmp_path)
    assert main(["stack", "create", "--name", "clitest",
                 "--slice-type", "v5p-8", "--provisioner", "dryrun",
                 "--state-dir", state_dir]) == 0
    out = capsys.readouterr().out
    assert "CREATE_COMPLETE" in out

    assert main(["stack", "status", "clitest",
                 "--state-dir", state_dir]) == 0
    status = json.loads(capsys.readouterr().out)
    assert status["status"] == "CREATE_COMPLETE"
    assert len(status["hosts"]) == 2

    assert main(["stack", "list", "--state-dir", state_dir]) == 0
    assert "clitest" in capsys.readouterr().out

    assert main(["stack", "delete", "clitest",
                 "--state-dir", state_dir]) == 0
    assert main(["stack", "status", "clitest",
                 "--state-dir", state_dir]) == 1


def test_stack_resize(tmp_path, capsys):
    """`stack resize` is the reference's change-the-ASG-worker-count flow:
    delete + recreate under the same name with the new topology (SURVEY
    §4.5), training state carried by checkpoints."""
    state_dir = str(tmp_path)
    assert main(["stack", "create", "--name", "rz",
                 "--slice-type", "v5p-8", "--provisioner", "dryrun",
                 "--state-dir", state_dir]) == 0
    capsys.readouterr()
    assert main(["stack", "resize", "rz", "--slice", "v5p-16",
                 "--state-dir", state_dir]) == 0
    out = capsys.readouterr().out
    assert "resized to v5p-16" in out

    assert main(["stack", "status", "rz", "--state-dir", state_dir]) == 0
    status = json.loads(capsys.readouterr().out)
    assert status["status"] == "CREATE_COMPLETE"
    assert status["slice_type"] == "v5p-16"
    assert len(status["hosts"]) == 4  # v5p-16 = 4 hosts (vs 2 for v5p-8)
    # Every create-time knob except the slice type carried over into the
    # recreated stack's recorded config.
    cc = status["create_config"]
    assert cc["slice_type"] == "v5p-16"
    assert cc["provisioner"] == "dryrun"
    assert cc["runtime_version"] == "tpu-ubuntu2204-base"

    # No-op resize is an error, and the stack survives untouched.
    assert main(["stack", "resize", "rz", "--slice", "v5p-16",
                 "--state-dir", state_dir]) == 1
    assert main(["stack", "resize", "ghost", "--slice", "v5p-16",
                 "--state-dir", state_dir]) == 1
    assert main(["stack", "delete", "rz", "--state-dir", state_dir]) == 0


def test_eval_verb_standalone(tmp_path, capsys):
    """`eval` re-judges a finished run from its checkpoint: same weighted
    metrics machinery, no training step."""
    common = [
        "--preset", "cifar10_resnet20", "--accelerator", "cpu",
        f"workdir={tmp_path}", "train.global_batch=32",
        "data.num_train_examples=64", "data.num_eval_examples=32",
        "train.eval_batch=32", "schedule.warmup_epochs=0",
        "checkpoint.async_write=false", "data.prefetch=0",
    ]
    assert main(["train", *common, "train.steps=4",
                 "train.log_every_steps=2"]) == 0
    capsys.readouterr()
    assert main(["eval", *common]) == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert {"loss", "accuracy", "accuracy_top5",
            "checkpoint_step"} <= set(rec)
    assert rec["checkpoint_step"] == 4

    # Evaluating a workdir with no checkpoints errors loudly.
    assert main(["eval", "--preset", "cifar10_resnet20",
                 "--accelerator", "cpu", f"workdir={tmp_path}/empty"]) == 1


def test_metrics_summary_verb(tmp_path, capsys):
    """`metrics` summarizes a run's JSONL: last train step, best eval,
    throughput, and the final acceptance metrics."""
    common = [
        "--preset", "cifar10_resnet20", "--accelerator", "cpu",
        f"workdir={tmp_path}", "train.global_batch=32", "train.steps=8",
        "train.log_every_steps=2", "train.eval_every_steps=4",
        "data.num_train_examples=64", "data.num_eval_examples=32",
        "train.eval_batch=32", "schedule.warmup_epochs=0",
        "checkpoint.async_write=false", "data.prefetch=0",
    ]
    assert main(["train", *common]) == 0
    capsys.readouterr()
    rundir = os.path.join(str(tmp_path), "cifar10_resnet20")
    assert main(["metrics", rundir]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["last_step"] == 8
    assert rec["mean_examples_per_sec"] > 0
    assert "final_eval_accuracy" in rec["final"]
    assert "best_eval_accuracy" in rec

    assert main(["metrics", str(tmp_path / "nope")]) == 1


def test_ckpt_list_and_rollback_verbs(tmp_path, capsys):
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ckpt import save_checkpoint

    d = str(tmp_path)
    for step in [2, 4, 6]:
        save_checkpoint(d, step, {"w": jnp.zeros((2,))})

    assert main(["ckpt", "list", d]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert rec["committed_steps"] == [2, 4, 6]

    assert main(["ckpt", "rollback", d, "--step", "4"]) == 0
    assert "deleted 1 later checkpoint(s): [6]" in capsys.readouterr().out
    assert main(["ckpt", "list", d]) == 0
    assert json.loads(capsys.readouterr().out)["committed_steps"] == [2, 4]

    assert main(["ckpt", "rollback", d, "--step", "5"]) == 1
    # A mistyped directory is an error, not an empty-but-successful list.
    assert main(["ckpt", "list", d + "-typo"]) == 1


def test_stack_status_missing(tmp_path):
    assert main(["stack", "status", "nope",
                 "--state-dir", str(tmp_path)]) == 1


def test_train_requires_existing_ready_stack(tmp_path):
    assert main(["train", "--preset", "cifar10_resnet20",
                 "--stack", "ghost", "--state-dir", str(tmp_path)]) == 1


def test_train_local_inprocess(tmp_path, capsys):
    """`train` without a stack runs single-host in-process — the 'run the
    example script directly' path."""
    rc = main([
        "train", "--preset", "cifar10_resnet20",
        "--max-steps", "2",
        "--state-dir", str(tmp_path),
        f"workdir={tmp_path}/work",
        "train.global_batch=32",
        "data.num_train_examples=64",
        "data.num_eval_examples=32",
        "data.prefetch=0",
        "checkpoint.async_write=false",
        "train.log_every_steps=1",
    ])
    assert rc == 0
    assert "final metrics" in capsys.readouterr().out


def test_train_on_dryrun_stack_fans_out_worker(tmp_path, capsys):
    """Full `stack create → train` flow: a 1-host dry-run stack, the worker
    module fanned out as a real subprocess via LocalTransport."""
    state_dir = str(tmp_path / "stacks")
    assert main(["stack", "create", "--name", "trainstack",
                 "--slice-type", "v5p-4", "--provisioner", "dryrun",
                 "--state-dir", state_dir]) == 0
    capsys.readouterr()
    rc = main([
        "train", "--preset", "cifar10_resnet20",
        "--stack", "trainstack",
        "--state-dir", state_dir,
        "--max-steps", "2",
        f"workdir={tmp_path}/work",
        "train.global_batch=32",
        "data.num_train_examples=64",
        "data.num_eval_examples=32",
        "data.prefetch=0",
        "checkpoint.async_write=false",
        "train.log_every_steps=1",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "job finished" in out
    logs = list((tmp_path / "work" / "cifar10_resnet20" / "logs").iterdir())
    assert any("attempt0-host0.log" == p.name for p in logs)


def test_train_on_multihost_dryrun_stack(tmp_path, capsys):
    """The keystone cluster simulation: a 2-host dry-run stack (v5p-8),
    `train --stack` fans TWO worker processes that rendezvous over loopback
    via jax.distributed and run real data-parallel steps across 16 fake
    devices — the whole L0→L4 stack with zero real TPUs."""
    state_dir = str(tmp_path / "stacks")
    assert main(["stack", "create", "--name", "mh",
                 "--slice-type", "v5p-8", "--provisioner", "dryrun",
                 "--state-dir", state_dir]) == 0
    capsys.readouterr()
    rc = main([
        "train", "--preset", "cifar10_resnet20",
        "--stack", "mh",
        "--state-dir", state_dir,
        "--max-steps", "2",
        f"workdir={tmp_path}/work",
        "train.global_batch=32",
        "data.num_train_examples=64",
        "data.num_eval_examples=32",
        "train.eval_batch=32",
        "data.prefetch=0",
        "checkpoint.async_write=false",
        "train.log_every_steps=1",
    ])
    out = capsys.readouterr().out
    assert rc == 0, out
    log_dir = tmp_path / "work" / "cifar10_resnet20" / "logs"
    host0 = (log_dir / "attempt0-host0.log").read_text()
    assert "2 processes" in host0, host0  # both ranks joined the mesh
    assert (log_dir / "attempt0-host1.log").exists()


def test_entry_point_matches_pyproject():
    # pyproject [project.scripts] points at cli.main:main — keep them wired.
    from deeplearning_cfn_tpu.cli.main import main as m
    assert callable(m)
