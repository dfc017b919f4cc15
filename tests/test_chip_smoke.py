"""chip_smoke.py, rehearsed on the CPU: its phases at tiny overrides with the
platform assertion patched, and the contract of the script as shipped (no
TPU -> non-zero before any phase; the last line's shape; a failing phase
stops the run non-zero)."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_NMT = (
    "data.seq_len=16", "data.vocab_size=64", "model.kwargs.vocab_size=64",
    "model.kwargs.hidden_size=32", "model.kwargs.num_layers=1",
    "model.kwargs.num_heads=2", "model.kwargs.mlp_dim=64",
    "model.kwargs.max_len=32",
)
TINY_BERT = (
    "data.synthetic=true", "data.prefetch=0", "data.vocab_size=64",
    "data.num_train_examples=32", "data.num_eval_examples=8",
    "train.global_batch=8", "train.dtype=float32",
    "train.shard_opt_state=false", "model.kwargs.vocab_size=64",
    "model.kwargs.hidden_size=32", "model.kwargs.num_layers=1",
    "model.kwargs.num_heads=2", "model.kwargs.mlp_dim=64",
    "model.kwargs.dropout_rate=0.0",
)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO_ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_checkpoint_serve_phases_tiny(smoke, tmp_path):
    """Phase 2 end to end at hidden 32: train, checkpoint, resume, then the
    paged + fused-window serve against --decode-window 1."""
    out = str(tmp_path)
    overrides = tuple(o for o in smoke.NMT_OVERRIDES
                      if not o.startswith(("train.g", "data.num_"))
                      ) + TINY_NMT + (
        "train.global_batch=16", "data.num_train_examples=64",
        "data.num_eval_examples=16", "eval.enabled=false")
    info = smoke.train_and_resume(out, "transformer_nmt_wmt", overrides,
                                  2, 4)
    assert info["resumed_from"] == 2 and info["steps"] == 4
    assert os.path.exists(os.path.join(out, "logs",
                                       "transformer_nmt_wmt.log"))
    served = smoke.nmt_serve(
        out, (f"workdir={info['workdir']}",) + TINY_NMT, seed=0, n_greedy=4,
        max_new_tokens=12)
    assert served["requests"] == served["done"] == 5
    assert served["steps_per_window"] >= 2.0
    assert served["window4_vs_window1_identical"] == "4/4"
    # The margin diagnostic runs only on a disagreement on the chip; keep
    # it runnable.
    (margin, scale, top2), _ = smoke.divergence_margins(
        (f"workdir={info['workdir']}",) + TINY_NMT,
        [([5, 6, 2], [7, 8]), ([9, 2], [])])
    assert margin >= 0 and scale > 0 and len(top2) == 2


def test_flash_attention_phase_tiny(smoke):
    info = smoke.flash_attention(
        shapes=(("tiny", (2, 2, 256, 64), True),
                ("tiny", (1, 2, 256, 64), False)),
        kernel="interpret", expect_auto_kernel=False)
    assert [r["causal"] for r in info["shapes"]] == [True, False]
    assert all(r["fwd_rel_err"] <= smoke.FLASH_TOL[0]
               for r in info["shapes"])


def test_mesh_phases_tiny(smoke, devices):
    """The --chips 4 phases on four virtual CPU devices."""
    four = devices[:4]
    info = smoke.mesh_dp_tp(
        four, overrides=TINY_BERT + ("data.seq_len=16",
                                     "model.kwargs.max_len=16"), n_steps=2)
    assert info["model_sharded_leaves"] >= 6
    assert info["max_rel_diff"] <= smoke.MESH_LOSS_RTOL
    info = smoke.mesh_ring(
        four, overrides=TINY_BERT + ("data.seq_len=32",
                                     "model.kwargs.max_len=32"), n_steps=2)
    assert info["max_rel_diff"] <= smoke.MESH_LOSS_RTOL


@pytest.mark.parametrize("argv,phases", [
    ((), ["start", "resnet50_train", "nmt_train", "nmt_serve",
          "flash_attention", "compile_cache"]),
    (("--chips", "4"), ["start", "mesh_dp_tp", "mesh_ring",
                        "compile_cache"]),
])
def test_main_prints_phase_lines_then_the_contract_line(
        smoke, monkeypatch, capsys, tmp_path, argv, phases):
    for name in ("train_and_resume", "nmt_serve", "flash_attention",
                 "mesh_dp_tp", "mesh_ring"):
        monkeypatch.setattr(smoke, name,
                            lambda *a, **k: {"workdir": str(tmp_path)})
    monkeypatch.setattr(smoke, "require_tpu", jax.devices)
    assert smoke.main(["--out", str(tmp_path), *argv]) == 0
    records = [json.loads(ln) for ln in
               capsys.readouterr().out.strip().splitlines()]
    assert [r["phase"] for r in records[:-1]] == phases
    assert all(r["ok"] is True and "seconds" in r for r in records[:-1])
    d = jax.devices()[0]
    assert records[-1] == {"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}


def test_failing_phase_stops_the_run(smoke, monkeypatch, capsys, tmp_path):
    def boom(*a, **k):
        raise RuntimeError("loss was nan")

    monkeypatch.setattr(smoke, "train_and_resume", boom)
    monkeypatch.setattr(smoke, "require_tpu", jax.devices)
    with pytest.raises(RuntimeError, match="loss was nan"):
        smoke.main(["--out", str(tmp_path)])
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["phase"] == "resnet50_train" and last["ok"] is False
    assert "loss was nan" in last["error"]
    assert not any('"device"' in ln for ln in lines)


def test_shipped_script_refuses_without_a_tpu(tmp_path):
    """As the driver runs it in the sandbox: non-zero, no result line, and
    a message that names the platform it found."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py"),
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
    assert os.listdir(tmp_path) == []
