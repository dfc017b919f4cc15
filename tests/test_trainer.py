"""End-to-end trainer over the 8-fake-device mesh — the keystone test
(SURVEY.md §8 Phase 1): sharded pjit-DP step runs, loss decreases on learnable
synthetic data, metrics stream out, checkpoint-resume continues the run.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.config import ExperimentConfig, apply_overrides
from deeplearning_cfn_tpu.metrics import read_metrics
from deeplearning_cfn_tpu.parallel import build_mesh
from deeplearning_cfn_tpu.presets import get_preset
from deeplearning_cfn_tpu.train import create_train_state
from deeplearning_cfn_tpu.train.optim import build_optimizer, build_schedule
from deeplearning_cfn_tpu.train.run import run_experiment
from deeplearning_cfn_tpu.train.task import build_task
from deeplearning_cfn_tpu.train.trainer import Trainer


def _tiny_cfg(tmp_workdir, steps=12) -> ExperimentConfig:
    cfg = get_preset("cifar10_resnet20")
    apply_overrides(cfg, [
        f"workdir={tmp_workdir}",
        "train.global_batch=32",
        f"train.steps={steps}",
        "train.log_every_steps=4",
        "train.eval_every_steps=1000000",
        "data.num_train_examples=256",
        "data.num_eval_examples=64",
        "train.eval_batch=32",
        "data.prefetch=0",
        "schedule.name=constant",
        "schedule.base_lr=0.1",
        "schedule.warmup_epochs=0",
        "checkpoint.async_write=false",
    ])
    return cfg


def test_sharded_train_step_runs_and_learns(tmp_workdir, devices):
    cfg = _tiny_cfg(tmp_workdir, steps=32)
    mesh = build_mesh(cfg.mesh)
    assert mesh.shape["data"] == 8
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 32, cfg.train.global_batch, 8)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)

    from deeplearning_cfn_tpu.data import build_pipeline

    pipe = build_pipeline(cfg.data, cfg.train.global_batch, 10, train=True)
    it = pipe.epochs()
    rng = jax.random.PRNGKey(1)

    losses = []
    for _ in range(32):
        batch = trainer.device_batch(next(it))
        # Batch must actually be sharded over the data axis.
        assert batch["image"].addressable_shards[0].data.shape[0] == 4
        state, metrics = trainer.train_step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    assert int(state.step) == 32
    assert np.isfinite(losses).all()
    # Learnable synthetic data: loss should drop clearly.
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) * 0.9, losses


def test_run_experiment_end_to_end(tmp_workdir, devices):
    cfg = _tiny_cfg(tmp_workdir, steps=10)
    final = run_experiment(cfg)
    assert "accuracy" in final and np.isfinite(final["loss"])

    mpath = os.path.join(tmp_workdir, "cifar10_resnet20", "metrics.jsonl")
    records = read_metrics(mpath)
    steps_logged = [r["step"] for r in records if "examples_per_sec" in r]
    assert steps_logged, records
    assert any("final_eval_accuracy" in r for r in records)

    ckpts = glob.glob(os.path.join(tmp_workdir, "cifar10_resnet20", "ckpt",
                                   "step_*", "COMMIT"))
    assert ckpts


def test_resume_continues_from_checkpoint(tmp_workdir, devices):
    cfg = _tiny_cfg(tmp_workdir, steps=6)
    run_experiment(cfg)
    # Second run with more steps must resume (not restart): metrics log shows
    # resumed step numbers > 6.
    cfg2 = _tiny_cfg(tmp_workdir, steps=12)
    run_experiment(cfg2)
    mpath = os.path.join(tmp_workdir, "cifar10_resnet20", "metrics.jsonl")
    steps = [r["step"] for r in read_metrics(mpath) if "loss" in r]
    assert max(steps) >= 12
    # No step was trained twice from scratch: the second run's first logged
    # step is past the first run's last checkpoint.
    assert min(s for s in steps if s > 6) > 6


def test_eval_uses_global_batch(tmp_workdir, devices):
    cfg = _tiny_cfg(tmp_workdir)
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, cfg.train.global_batch, 8)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    from deeplearning_cfn_tpu.data import build_pipeline

    eval_pipe = build_pipeline(cfg.data, cfg.train.global_batch, 10,
                               train=False)
    metrics = trainer.evaluate(state, eval_pipe.one_epoch(), max_steps=2)
    assert set(metrics) >= {"loss", "accuracy", "accuracy_top5"}
    # Top-5 can never be beaten by top-1 and both are proportions.
    assert 0.0 <= metrics["accuracy"] <= metrics["accuracy_top5"] <= 1.0


def test_gradients_identical_across_mesh_layouts(tmp_workdir, devices):
    """DP sharding is numerically transparent: one step on a 8-way data mesh
    equals one step on a 1-way mesh (the correctness claim that replaces
    Horovod's allreduce-equivalence)."""
    cfg = _tiny_cfg(tmp_workdir)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, cfg.train.global_batch, 8)
    tx = build_optimizer(cfg.optimizer, sched)

    from deeplearning_cfn_tpu.config import MeshConfig
    from deeplearning_cfn_tpu.data import build_pipeline

    pipe = build_pipeline(cfg.data, cfg.train.global_batch, 10, train=True)
    batch = next(iter(pipe.one_epoch(0)))

    results = []
    for mesh_cfg in [MeshConfig(data=-1), MeshConfig(data=1, model=1)]:
        devs = jax.devices() if mesh_cfg.data == -1 else jax.devices()[:1]
        mesh = build_mesh(mesh_cfg, devices=devs)
        state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
        trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
        dev_batch = trainer.device_batch(batch)
        state, metrics = trainer.train_step(state, dev_batch,
                                            jax.random.PRNGKey(1))
        results.append((float(metrics["loss"]),
                        np.asarray(jax.tree_util.tree_leaves(state.params)[0])))
    loss_a, w_a = results[0]
    loss_b, w_b = results[1]
    assert loss_a == pytest.approx(loss_b, rel=1e-5)
    np.testing.assert_allclose(w_a, w_b, rtol=1e-5, atol=1e-6)


def test_multi_slice_mesh_matches_single_slice(tmp_workdir, devices):
    """DCN scale-out is numerically transparent: a train step on a 2-slice
    hybrid mesh (dcn_data=2 × data=4) equals the same step on a single-slice
    data=8 mesh — the hierarchical ICI+DCN gradient reduction must sum to
    exactly the flat allreduce."""
    cfg = _tiny_cfg(tmp_workdir)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, cfg.train.global_batch, 8)
    tx = build_optimizer(cfg.optimizer, sched)

    from deeplearning_cfn_tpu.config import MeshConfig
    from deeplearning_cfn_tpu.data import build_pipeline

    pipe = build_pipeline(cfg.data, cfg.train.global_batch, 10, train=True)
    batch = next(iter(pipe.one_epoch(0)))

    results = []
    for mesh_cfg in [MeshConfig(data=-1, num_slices=2), MeshConfig(data=-1)]:
        mesh = build_mesh(mesh_cfg)
        state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
        trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
        dev_batch = trainer.device_batch(batch)
        # The batch must really shard over both data axes on the hybrid mesh.
        assert dev_batch["image"].addressable_shards[0].data.shape[0] == 4
        for _ in range(3):
            state, metrics = trainer.train_step(state, dev_batch,
                                                jax.random.PRNGKey(1))
        results.append((float(metrics["loss"]),
                        np.asarray(jax.tree_util.tree_leaves(state.params)[0])))
    (loss_a, w_a), (loss_b, w_b) = results
    assert loss_a == pytest.approx(loss_b, rel=1e-5)
    np.testing.assert_allclose(w_a, w_b, rtol=1e-5, atol=1e-6)


def test_checkpoint_cadence_decoupled_from_log_cadence(tmp_workdir, devices):
    """Regression: periodic saves must fire even when every_steps is not a
    multiple of log_every_steps (found by driving the surface: only the final
    force-save landed)."""
    cfg = _tiny_cfg(tmp_workdir, steps=10)
    apply_overrides(cfg, ["train.log_every_steps=3",
                          "checkpoint.every_steps=4"])
    run_experiment(cfg)
    ckpts = sorted(
        os.path.basename(os.path.dirname(p)) for p in
        glob.glob(os.path.join(tmp_workdir, "cifar10_resnet20", "ckpt",
                               "step_*", "COMMIT"))
    )
    assert "step_00000004" in ckpts and "step_00000008" in ckpts, ckpts


def test_zero1_opt_state_sharding_matches_replicated(tmp_workdir, devices):
    """ZeRO-1 (train.shard_opt_state): optimizer slots shard over 'data',
    params/grads stay replicated — training must be numerically identical
    to the replicated layout, and the slots must actually be sharded."""
    cfg = _tiny_cfg(tmp_workdir)
    apply_overrides(cfg, ["optimizer.name=adamw"])  # mu/nu mirror slots
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, cfg.train.global_batch, 8)
    tx = build_optimizer(cfg.optimizer, sched)

    from deeplearning_cfn_tpu.data import build_pipeline

    mesh = build_mesh(cfg.mesh)
    pipe = build_pipeline(cfg.data, cfg.train.global_batch, 10, train=True)
    batch = next(iter(pipe.one_epoch(0)))

    def count_partitioned(tree):
        return sum(
            1 for leaf in jax.tree_util.tree_leaves(tree)
            if hasattr(leaf, "addressable_shards") and leaf.ndim > 0
            and leaf.addressable_shards[0].data.shape != leaf.shape)

    results = []
    for zero1 in (True, False):
        state = create_train_state(jax.random.PRNGKey(0), task.init, tx,
                                   mesh, shard_opt_state=zero1)
        if zero1:
            # At least one mirror slot must really be partitioned: its
            # addressable shard is smaller than the global array.
            assert count_partitioned(state.opt_state) >= 10
        trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
        dev_batch = trainer.device_batch(batch)
        for _ in range(3):
            state, metrics = trainer.train_step(state, dev_batch,
                                                jax.random.PRNGKey(1))
        # Layout stability across steps: params must STAY replicated (no
        # GSPMD leak of the slot sharding through apply_updates) and the
        # slots must STAY sharded.
        assert count_partitioned(state.params) == 0, \
            "params became partitioned after training steps"
        if zero1:
            assert count_partitioned(state.opt_state) >= 10
        results.append((float(metrics["loss"]),
                        np.asarray(jax.tree_util.tree_leaves(state.params)[0])))
    (loss_a, w_a), (loss_b, w_b) = results
    assert loss_a == pytest.approx(loss_b, rel=1e-6)
    np.testing.assert_allclose(w_a, w_b, rtol=1e-6, atol=1e-7)


def test_training_run_deterministic(tmp_workdir, devices):
    """SURVEY §5.3's step-numerics golden test in self-consistent form: two
    fresh runs with the same seed produce bit-identical loss trajectories
    (data order, augmentation, init, and the compiled step are all
    deterministic — the reproducibility the reference never had)."""
    trajectories = []
    for run in ("a", "b"):
        cfg = _tiny_cfg(os.path.join(tmp_workdir, run), steps=8)
        apply_overrides(cfg, ["train.log_every_steps=1"])
        run_experiment(cfg)
        path = os.path.join(tmp_workdir, run, "cifar10_resnet20",
                            "metrics.jsonl")
        trajectories.append([r["loss"] for r in read_metrics(path)
                             if "loss" in r])
    assert len(trajectories[0]) == 8
    assert trajectories[0] == trajectories[1], trajectories


def test_profile_steps_captures_trace(tmp_workdir, devices):
    """train.profile_steps captures a TensorBoard-format profiler trace of
    hot-loop steps into <workdir>/<preset>/profile (SURVEY §6 tracing row
    — the Horovod-timeline role, reachable from config)."""
    cfg = _tiny_cfg(tmp_workdir, steps=4)
    apply_overrides(cfg, ["train.profile_steps=2"])
    run_experiment(cfg)
    trace_root = os.path.join(tmp_workdir, "cifar10_resnet20", "profile")
    files = [os.path.join(dp, f) for dp, _, fs in os.walk(trace_root)
             for f in fs]
    assert files, f"no trace files under {trace_root}"


def test_exact_eval_counts_every_example(tmp_workdir, devices):
    """The eval set does not divide the eval batch (70 % 32 != 0): with the
    padded-tail pipeline the trainer must still count ALL 70 examples, and
    the weighted accuracy must equal the directly-computed full-set value
    — not a mean of unequal batch means."""
    cfg = _tiny_cfg(tmp_workdir)
    apply_overrides(cfg, ["data.num_eval_examples=70"])
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, cfg.train.global_batch, 8)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    from deeplearning_cfn_tpu.data import build_pipeline

    eval_pipe = build_pipeline(cfg.data, cfg.train.global_batch, 10,
                               train=False, drop_remainder=False)
    metrics = trainer.evaluate(state, eval_pipe.one_epoch())
    assert metrics["examples"] == 70.0

    # Oracle: accuracy over the full set computed directly, one example at
    # a time — batch-size independent.
    correct = 0
    variables = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    for batch in eval_pipe.one_epoch():
        logits = task.model.apply(variables, jnp.asarray(batch["image"]),
                                  train=False)
        pred = np.argmax(np.asarray(logits), -1)
        m = batch["eval_mask"] > 0
        correct += int((pred[m] == batch["label"][m]).sum())
    np.testing.assert_allclose(metrics["accuracy"], correct / 70.0,
                               atol=1e-6)


@pytest.mark.parametrize("unroll_mode", ["scan", "unroll"])
def test_grad_accum_matches_full_batch(devices, unroll_mode):
    """grad_accum_steps=k must give exactly the full-batch update for an
    unweighted mean loss with no BN: mean of k equal-size microbatch
    gradients == the global-batch gradient, and the optimizer runs once.

    Parametrized over BOTH lowerings: 'auto' unrolls on the CPU test
    backend, so without the explicit 'scan' leg the rolled (unroll=1)
    path production TPU runs use would have zero coverage."""
    from deeplearning_cfn_tpu.config import MeshConfig
    import optax

    cfg = _tiny_cfg("/tmp/unused")
    cfg.train.global_batch = 32

    def init_fn(rng):
        return {"params": {"w": jnp.zeros((8,), jnp.float32)}}

    def loss_fn(params, batch_stats, batch, rng, train):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    mesh = build_mesh(MeshConfig(data=-1))
    tx = optax.sgd(0.1)
    x = np.random.RandomState(0).randn(32, 8).astype(np.float32)
    y = np.random.RandomState(1).randn(32).astype(np.float32)
    rng = jax.random.PRNGKey(0)

    results = {}
    for accum in (1, 4):
        cfg.train.grad_accum_steps = accum
        cfg.train.grad_accum_unroll = unroll_mode
        state = create_train_state(jax.random.PRNGKey(0), init_fn, tx, mesh)
        trainer = Trainer(cfg, loss_fn, tx, mesh=mesh)
        batch = trainer.device_batch({"x": x, "y": y})
        new_state, metrics = trainer.train_step(state, batch, rng)
        results[accum] = (np.asarray(new_state.params["w"]),
                          float(metrics["loss"]),
                          float(metrics["grad_norm"]))

    w1, l1, g1 = results[1]
    w4, l4, g4 = results[4]
    # f32 summation order differs (mean-of-4-means vs one mean): allow
    # a few ulps, nothing more.
    np.testing.assert_allclose(w4, w1, rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(l4, l1, rtol=1e-5)
    np.testing.assert_allclose(g4, g1, rtol=1e-5)


def test_grad_accum_trains_bn_model(tmp_workdir, devices):
    """The accumulation path must also run the full preset machinery
    (BN stats threaded through the scan carry, metrics averaged)."""
    cfg = _tiny_cfg(tmp_workdir, steps=4)
    apply_overrides(cfg, ["train.grad_accum_steps=2"])
    metrics = run_experiment(cfg)
    assert np.isfinite(metrics["loss"])


def test_grad_accum_divisibility_validated(devices):
    cfg = _tiny_cfg("/tmp/unused")
    cfg.train.global_batch = 32  # divisible by the 8 data ways, not by 3
    cfg.train.grad_accum_steps = 3
    mesh = build_mesh(cfg.mesh)

    with pytest.raises(ValueError, match="grad_accum_steps"):
        Trainer(cfg, lambda *a: None, None, mesh=mesh)


def test_fit_preserves_cadences(tmp_workdir, devices):
    """``fit`` keeps every cadence contract: periodic checkpoints COMMIT on
    their exact steps (log every 4, eval and checkpoint every 8), eval
    fires on eval_every multiples, the watchdog stays beaten (run
    survives), and the metrics log carries compile_s once plus honest
    post-compile examples_per_sec."""
    cfg = _tiny_cfg(tmp_workdir, steps=16)
    apply_overrides(cfg, [
        "train.log_every_steps=4",
        "checkpoint.every_steps=8", "train.eval_every_steps=8",
        "train.hang_timeout_s=600",
    ])
    final = run_experiment(cfg)
    assert np.isfinite(final["loss"])

    ckpts = sorted(
        os.path.basename(os.path.dirname(p)) for p in
        glob.glob(os.path.join(tmp_workdir, "cifar10_resnet20", "ckpt",
                               "step_*", "COMMIT")))
    assert ckpts == ["step_00000008", "step_00000016"], ckpts

    records = read_metrics(
        os.path.join(tmp_workdir, "cifar10_resnet20", "metrics.jsonl"))
    eval_steps = [r["step"] for r in records
                  if any(k.startswith("eval_") for k in r)]
    assert eval_steps == [8, 16], records
    train_recs = [r for r in records if "loss" in r]
    assert [r["step"] for r in train_recs] == [4, 8, 12, 16]
    assert sum(1 for r in records if "compile_s" in r) == 1
    assert "compile_s" in train_recs[0]
    eps = [r["examples_per_sec"] for r in train_recs]
    assert all(v > 0 for v in eps)


# -- program scopes and spans (PR 24) ----------------------------------------

# Scopes the program owns (docs/OBSERVABILITY.md), and the model's own
# module name, which flax puts on everything inside the model.
_PROGRAM_SCOPE = r"\b(lm_head|lm_loss|optimizer|step_rng)\b"
_MODULE_SCOPE = r"TransformerCausalLm"


def _tiny_gpt(devices, extra=()):
    cfg = get_preset("gpt_small_lm")
    apply_overrides(cfg, [
        "model.name=gpt_tiny", "data.vocab_size=512", "data.seq_len=32",
        "model.kwargs.max_len=32", "train.dtype=float32",
        "train.global_batch=4", "mesh.data=1", "train.ema_decay=0.99",
        *extra])
    mesh = build_mesh(cfg.mesh, devices=devices[:1])
    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer, build_schedule(
        cfg.schedule, 100, cfg.train.global_batch, None))
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules, ema=True)
    return cfg, Trainer(cfg, task.loss_fn, tx, mesh=mesh), state


def _lm_batches(n, batch=4, seq=32):
    rs = np.random.RandomState(0)
    return [{"tokens": rs.randint(0, 512, (batch, seq + 1)).astype(np.int32),
             "loss_mask": np.ones((batch, seq), np.float32)}
            for _ in range(n)]


def _op_names(hlo_text, entry_only=False):
    """``op_name`` of every instruction of a compiled program's text that
    carries one (the compiler's own copies carry none)."""
    import re

    out, in_entry = [], False
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            in_entry = line.startswith("ENTRY")
        m = re.search(r'op_name="([^"]*)"', line)
        if m and " = " in line and (in_entry or not entry_only) \
                and not re.search(r" (parameter|constant)\(", line):
            out.append(m.group(1))
    return out


def test_every_operation_of_the_step_belongs_to_a_scope(devices):
    import re

    _, trainer, state = _tiny_gpt(devices)
    batch = trainer.device_batch(_lm_batches(1)[0])
    text = trainer.train_step.lower(
        state, batch, jax.random.PRNGKey(7)).compile().as_text()
    names = _op_names(text)
    # What the parent left bare: the clip, the schedule, Adam's moments and
    # the EMA now say ``optimizer``; the cross-entropy's gather and its
    # scatter-add backward say ``lm_loss``; the head is told from the
    # embedding lookup that shares the module ``token``.
    marks = {r"jit\(clip\)|/cos$|/sqrt$": r"/optimizer/",
             r"take_along_axis": r"\blm_loss\b",
             r"token\.attend": r"/lm_head/",
             r"_train_step_fn": r"/step_rng/"}
    for mark, scope in marks.items():
        marked = [n for n in names if re.search(mark, n)]
        assert marked, f"no instruction matches {mark}"
        bare = [n for n in marked if not re.search(scope, n)]
        assert not bare, f"{mark} outside {scope}: {bare[:5]}"
    assert any("/optimizer/ema/" in n for n in names)
    # The sections are whole: of the entry computation's instructions that
    # carry a name at all, fewer than 5 % have neither a program nor a
    # module scope.
    named = _op_names(text, entry_only=True)
    loose = [n for n in named if not re.search(
        _PROGRAM_SCOPE + "|" + _MODULE_SCOPE, n)]
    assert len(named) > 300
    assert len(loose) < 0.05 * len(named), (len(loose), len(named),
                                            sorted(set(loose))[:20])


def test_scopes_change_no_numerics(devices, monkeypatch):
    """``jax.named_scope`` writes ``op_name`` metadata and nothing else:
    the step with every scope of the program taken out gives the same bits."""
    import contextlib

    def losses():
        _, trainer, state = _tiny_gpt(devices)
        rng, out = jax.random.PRNGKey(7), []
        for batch in _lm_batches(3):
            state, m = trainer.train_step(state, trainer.device_batch(batch),
                                          rng)
            out.append((float(m["loss"]).hex(), float(m["grad_norm"]).hex()))
        w = sum(float(jnp.sum(jnp.abs(x)))
                for x in jax.tree_util.tree_leaves(state.ema_params))
        return out, w.hex()

    scoped = losses()
    seen = []
    monkeypatch.setattr(jax, "named_scope", lambda name: (
        seen.append(name), contextlib.nullcontext())[1])
    bare = losses()
    assert {"lm_head", "lm_loss", "optimizer", "step_rng", "ema"} <= set(seen)
    assert scoped == bare


@pytest.mark.parametrize("prefetch", [0, 2])
def test_fit_spans_are_siblings(devices, prefetch):
    """``train.next_batch``, ``train.dispatch`` and ``train.hooks`` are
    siblings: the wait for input is no longer inside the dispatch span."""
    from deeplearning_cfn_tpu.obs import MemorySink, Tracer, configured

    _, trainer, state = _tiny_gpt(devices, [
        f"train.device_prefetch={prefetch}"])
    tracer, sink = Tracer(), MemorySink()
    tracer.add_sink(sink)
    configured(tracer)
    calls = []
    try:
        trainer.fit(state, iter(_lm_batches(4)), num_steps=4,
                    rng=jax.random.PRNGKey(7), log_every=2,
                    hooks=(lambda step, st, last: calls.append(step),))
    finally:
        configured(None)
    assert calls == [1, 2, 3, 4]
    names = [r["span"] for r in sink.records
             if r["span"] not in ("train.realize", "train.first_step")]
    assert names == ["train.next_batch", "train.dispatch",
                     "train.hooks"] * 4
    assert all(r["parent_id"] is None for r in sink.records)
    assert all(r["ok"] for r in sink.records)
    assert [r["step"] for r in sink.by_span("train.dispatch")] == [0, 1, 2, 3]
    assert [r["step"] for r in sink.by_span("train.realize")] == [2, 4]


# -- the step loop's record contract (PR 28) ----------------------------------

class _Recorder:
    def __init__(self):
        self.records = []

    def write(self, record):
        self.records.append(dict(record))


class _ClosableBatches:
    def __init__(self, batches):
        self._it = iter(batches)
        self.closed = False

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def close(self):
        self.closed = True


_THROUGHPUT_KEYS = {"examples_per_sec", "examples_per_sec_per_device",
                    "step_time_s"}


@pytest.mark.parametrize("prefetch", [0, 2])
@pytest.mark.parametrize("num_steps,log_every",
                         [(6, 1), (6, 4), (7, 3), (5, 0)])
def test_fit_record_contract(devices, num_steps, log_every, prefetch):
    """What ``fit`` writes and when: a record at every multiple of
    ``max(log_every, 1)`` and at the last step and nowhere else,
    ``compile_s`` on the first record only, no throughput key on a boundary
    that no step after the compile precedes, every hook once a step with the
    last realized record, and the iterator closed at the end."""
    _, trainer, state = _tiny_gpt(
        devices, [f"train.device_prefetch={prefetch}"])
    writer, calls = _Recorder(), []
    feed = _ClosableBatches(_lm_batches(num_steps))
    out = trainer.fit(
        state, feed, num_steps=num_steps, rng=jax.random.PRNGKey(7),
        log_every=log_every, metrics_writer=writer,
        hooks=(lambda step, st, last: calls.append(
            (step, int(st.step), None if last is None else last["step"])),))
    assert int(out.step) == num_steps
    assert feed.closed

    every = max(log_every, 1)
    boundaries = [s for s in range(1, num_steps + 1)
                  if s % every == 0 or s == num_steps]
    assert [r["step"] for r in writer.records] == boundaries
    assert ["compile_s" in r for r in writer.records] == \
        [True] + [False] * (len(boundaries) - 1)
    assert writer.records[0]["compile_s"] > 0
    for r in writer.records:
        assert np.isfinite(r["loss"]) and np.isfinite(r["grad_norm"])
        # The throughput window restarts after the first step's sync, so
        # only a boundary at step 1 has nothing to report.
        if r["step"] == 1:
            assert not _THROUGHPUT_KEYS & set(r)
        else:
            assert _THROUGHPUT_KEYS <= set(r)
            assert r["examples_per_sec"] > 0 and r["step_time_s"] > 0

    assert [c[0] for c in calls] == list(range(1, num_steps + 1))
    assert all(step == at for step, at, _ in calls)
    realized = [max([b for b in boundaries if b <= s], default=None)
                for s in range(1, num_steps + 1)]
    assert [c[2] for c in calls] == realized


def test_fit_in_two_calls_equals_one(devices):
    """The step's key folds in ``state.step``: 3 + 3 steps, the second call
    from the state the first returned, end bit-equal in losses and
    parameters to one call of 6, dropout on."""
    def run(splits):
        cfg, trainer, state = _tiny_gpt(devices)
        assert cfg.model.kwargs["dropout_rate"] > 0
        writer, batches = _Recorder(), _lm_batches(6)
        for lo, hi in splits:
            state = trainer.fit(state, iter(batches[lo:hi]), num_steps=hi,
                                rng=jax.random.PRNGKey(7), log_every=1,
                                metrics_writer=writer)
        losses = [float(r["loss"]).hex() for r in writer.records]
        leaves = [np.asarray(x) for x in
                  jax.tree_util.tree_leaves(state.params)]
        return losses, leaves

    one_losses, one_leaves = run([(0, 6)])
    two_losses, two_leaves = run([(0, 3), (3, 6)])
    assert len(one_losses) == 6 and one_losses == two_losses
    for a, b in zip(one_leaves, two_leaves):
        np.testing.assert_array_equal(a, b)


# -- the start of a trainer's life: first step, state's build, retraces (PR 37)


@pytest.fixture(scope="module")
def start_of_life(devices):
    """One tiny trainer through three ``fit`` calls under a tracer of its
    own: 3 steps, 2 more of the same shape, then 2 of another sequence
    length. Returns the span records, the records ``fit`` wrote in each
    call, and the registry."""
    from deeplearning_cfn_tpu.obs import MemorySink, Tracer, configured

    tracer, sink = Tracer(), MemorySink()
    tracer.add_sink(sink)
    configured(tracer)
    try:
        _, trainer, state = _tiny_gpt(devices)
        calls = []
        for upto, batches in ((3, _lm_batches(3)), (5, _lm_batches(2)),
                              (7, _lm_batches(2, seq=16))):
            writer = _Recorder()
            state = trainer.fit(state, iter(batches), num_steps=upto,
                                rng=jax.random.PRNGKey(7), log_every=1,
                                metrics_writer=writer)
            calls.append(writer.records)
        n_params = sum(x.size for x in
                       jax.tree_util.tree_leaves(state.params))
    finally:
        configured(None)
    return {"sink": sink, "calls": calls, "registry": tracer.registry,
            "n_params": n_params}


def test_first_step_span_is_the_records_compile_s(start_of_life):
    """One ``train.first_step`` a ``fit`` call, each the duration its
    call's first record holds as ``compile_s``, from the same two clock
    reads (the span record is rounded to the microsecond)."""
    firsts = start_of_life["sink"].by_span("train.first_step")
    assert [r["step"] for r in firsts] == [0, 3, 5]
    for span_rec, records in zip(firsts, start_of_life["calls"]):
        assert ["compile_s" in r for r in records] == \
            [True] + [False] * (len(records) - 1)
        assert span_rec["dur_s"] == pytest.approx(
            records[0]["compile_s"], abs=1e-6)
        assert span_rec["ok"] and span_rec["parent_id"] is None
    durations = start_of_life["registry"].histogram("span_dur_s")
    assert durations.samples(name="train.first_step")[0] == \
        pytest.approx(start_of_life["calls"][0][0]["compile_s"], abs=1e-9)


def test_first_step_holds_jaxs_seconds_for_the_step(start_of_life):
    """The first ``fit``'s span carries what jax reported for
    ``train_step`` inside it, part by part and together no more than the
    span or its dispatch; the second call, which traced nothing, carries
    zeros; the gauge ``train.first_step_s`` keeps the first call's and no
    later one's."""
    first, second, third = start_of_life["sink"].by_span("train.first_step")
    parts = ("trace_s", "lower_s", "backend_compile_s")
    assert all(first[k] > 0 for k in parts)
    assert sum(first[k] for k in parts) <= first["dispatch_s"]
    assert first["next_batch_s"] + first["dispatch_s"] <= first["dur_s"]
    assert [second[k] for k in parts] == [0.0, 0.0, 0.0]
    assert all(third[k] > 0 for k in parts)
    registry = start_of_life["registry"]
    kept = {dict(key)["part"]: v for key, v in
            registry.gauge("train.first_step_s").series().items()}
    assert kept.pop("whole") == pytest.approx(first["dur_s"], abs=1e-6)
    assert kept == {k[:-2]: first[k] for k in first if k.endswith("_s")
                    and k not in ("dur_s", "t0_s")}
    assert set(kept) == {"trace", "lower", "backend_compile", "next_batch",
                         "dispatch", "cache_retrieval", "cache_saved"}
    # The counters are the process's totals under the step's own label.
    for k in parts:
        total = registry.counter(f"jit.{k}").value(fun="train_step")
        assert total == pytest.approx(first[k] + third[k], abs=1e-5)
        assert registry.counter(f"jit.{k[:-2]}_count").value(
            fun="train_step") == 2


def test_first_step_holds_what_the_cache_was_asked_and_answered(
        start_of_life):
    """The persistent cache is off under test, so jax asks it nothing: the
    five are on the record all the same, as numbers, and a hit or a store
    is never more than a request."""
    for first in start_of_life["sink"].by_span("train.first_step"):
        assert first["cache_requests"] >= \
            first["cache_hits"] + first["cache_misses"] >= 0
        assert first["cache_retrieval_s"] >= 0
        assert first["cache_saved_s"] >= 0


def test_a_second_fit_adds_no_retrace_and_another_shape_exactly_one(
        start_of_life):
    sink, calls = start_of_life["sink"], start_of_life["calls"]
    assert not any("retraces" in r for r in calls[0] + calls[1])
    assert [r.get("retraces") for r in calls[2]] == [1, None]
    assert start_of_life["registry"].counter(
        "train.step_retraces").value() == 1
    (retrace,) = sink.by_span("train.retrace")
    assert retrace["step"] == 5 and retrace["jit_s"] > 0
    assert retrace["dur_s"] >= retrace["jit_s"] - 1e-3
    (parent,) = [r for r in sink.records
                 if r["span_id"] == retrace["parent_id"]]
    assert parent["span"] == "train.dispatch" and parent["step"] == 5


def test_a_retrace_that_stops_at_the_trace_is_closed_with_its_dispatch(
        devices):
    """jax traces the step again and finds the jaxpr compiled already: no
    lowering and no backend compile follows. The loop closes what the
    watcher opened, behind the dispatch, as one counted record. And ``fit``
    hands the watch back to whoever had it."""
    from deeplearning_cfn_tpu.obs import MemorySink, Tracer, configured
    from deeplearning_cfn_tpu.runtime import jit_events

    def elder(phase, seconds):
        pass

    tracer, sink = Tracer(), MemorySink()
    tracer.add_sink(sink)
    configured(tracer)
    outer = jit_events.watch("train_step", elder)
    try:
        _, trainer, state = _tiny_gpt(devices)
        trainer.fit(state, iter(_lm_batches(1)), num_steps=1,
                    rng=jax.random.PRNGKey(7), log_every=1)
        assert jit_events.watch("train_step", elder) == ("train_step", elder)
        assert sink.by_span("train.retrace") == []
        trainer._on_step_jit("trace", 0.25)
        trainer._close_retrace(41, 9)
    finally:
        jit_events.watch(*outer)
        configured(None)
    (retrace,) = sink.by_span("train.retrace")
    assert (retrace["step"], retrace["parent_id"]) == (9, 41)
    assert retrace["jit_s"] == 0.25
    assert retrace["dur_s"] == pytest.approx(0.25, abs=1e-3)
    assert tracer.registry.counter("train.step_retraces").value() == 1
    assert trainer._retrace is None and trainer._retraces_unreported == 1


def test_create_train_state_emits_init_state_with_params_and_bytes(
        start_of_life):
    (build,) = start_of_life["sink"].by_span("train.init_state")
    assert build["params"] == start_of_life["n_params"]
    # float32 parameters, their EMA, Adam's two moments, and the counters.
    assert 16 * build["params"] < build["bytes"] < 16 * build["params"] + 64
    assert build["dur_s"] > 0 and build["ok"]
    assert start_of_life["registry"].counter(
        "jit.backend_compile_count").value(fun="make_state") == 1


def test_with_obs_off_no_span_is_made_and_fit_still_writes_compile_s(
        devices, monkeypatch):
    from deeplearning_cfn_tpu.obs import MemorySink, Tracer, configured

    monkeypatch.setenv("DLCFN_OBS_OFF", "1")
    tracer, sink = Tracer(), MemorySink()
    tracer.add_sink(sink)
    configured(tracer)
    writer = _Recorder()
    try:
        _, trainer, state = _tiny_gpt(devices)
        trainer.fit(state, iter(_lm_batches(2)), num_steps=2,
                    rng=jax.random.PRNGKey(7), log_every=1,
                    metrics_writer=writer)
    finally:
        configured(None)
    assert sink.records == []
    assert writer.records[0]["compile_s"] > 0
    assert "compile_s" not in writer.records[1]
    assert tracer.registry.counter("jit.trace_count").series() == {}
    assert tracer.registry.histogram("span_dur_s").series() == {}


def test_installing_the_listener_twice_counts_once(devices):
    from deeplearning_cfn_tpu.obs import Tracer, configured
    from deeplearning_cfn_tpu.runtime import jit_events

    def counted_once_pr37(x):
        return x * 2 + 1

    tracer = Tracer()
    configured(tracer)
    try:
        jit_events.install()
        jit_events.install()
        jax.jit(counted_once_pr37)(jnp.ones(3)).block_until_ready()
    finally:
        configured(None)
    for phase in ("trace", "lower", "backend_compile"):
        assert tracer.registry.counter(f"jit.{phase}_count").value(
            fun="counted_once_pr37") == 1
        assert tracer.registry.counter(f"jit.{phase}_s").value(
            fun="counted_once_pr37") > 0
