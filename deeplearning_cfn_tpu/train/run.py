"""Experiment runner: config in → trained state out.

This is the engine behind the ``train`` CLI verb (SURVEY.md §4.4): it builds
the mesh, task, data pipeline, optimizer, sharded state, wires metrics +
checkpointing (with auto-resume), and runs the Trainer loop. The reference
spread this across per-framework example scripts + launch wrappers; here it is
one code path for all five workloads.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

import jax

from ..ckpt import CheckpointManager, retry_policy_from_config
from ..config import ExperimentConfig
from ..obs import JsonlSink, get_tracer, obs_enabled, write_prometheus
from ..runtime.faults import chaos_kill_hook_from_env
from ..data import build_pipeline
from ..metrics import MetricsWriter
from ..parallel.mesh import build_mesh, describe, local_batch_size
from .optim import build_optimizer, build_schedule
from .state import create_train_state
from .task import build_task
from .trainer import Trainer


def _workdir_and_ckpt_dir(cfg: ExperimentConfig):
    """The one definition of the experiment's on-disk layout."""
    workdir = os.path.join(cfg.workdir, cfg.preset or cfg.model.name)
    ckpt_dir = cfg.checkpoint.directory or os.path.join(workdir, "ckpt")
    return workdir, ckpt_dir


def _build_eval_pipe(cfg: ExperimentConfig, task, mesh):
    """Eval pipeline honoring the task's exact-eval contract: tasks that
    weight metrics by eval_mask get the exact full eval set (padded
    tail); others keep the drop-remainder contract."""
    eval_batch = cfg.train.eval_batch or cfg.train.global_batch
    exact_eval = getattr(task, "exact_eval", False)
    return build_pipeline(cfg.data, local_batch_size(eval_batch, mesh),
                          cfg.model.num_classes, seed=cfg.train.seed,
                          train=False, drop_remainder=not exact_eval)


def _build_trainer(cfg: ExperimentConfig, task, tx, mesh) -> Trainer:
    return Trainer(cfg, task.loss_fn, tx, mesh=mesh,
                   spatial_dim=getattr(task, "spatial_dim", None),
                   spatial_keys=getattr(task, "spatial_keys", None),
                   eval_derived=getattr(task, "eval_derived", None))


def _final_eval(cfg, task, trainer, state, eval_pipe) -> Dict[str, float]:
    """Weighted full-set eval + the workload's own acceptance metric
    (tasks that define final_eval run the reference's yardstick: BLEU
    for NMT, COCO mAP for detection)."""
    final = trainer.evaluate(state, eval_pipe.one_epoch())
    task_final_eval = getattr(task, "final_eval", None)
    if task_final_eval is not None and cfg.eval.enabled:
        final.update(task_final_eval(
            state, lambda: eval_pipe.one_epoch(), trainer))
    return final


def run_eval(
    cfg: ExperimentConfig,
    step: int = 0,
    mesh=None,
) -> Dict[str, float]:
    """Evaluate a trained checkpoint — no training step is taken.

    Restores the latest committed checkpoint under the experiment's
    checkpoint dir (or the exact ``step``), runs the weighted full-set
    eval plus the task's own acceptance metric (``final_eval``: BLEU,
    COCO mAP), and returns the metrics. The standalone judging flow the
    reference's example scripts offered via their ``--eval-only``-style
    entry points.
    """
    from ..ckpt import latest_checkpoint

    _, ckpt_dir = _workdir_and_ckpt_dir(cfg)
    # Fail on the common error (wrong workdir/preset) in milliseconds,
    # before any model or data-pipeline construction.
    if latest_checkpoint(ckpt_dir) is None:
        raise FileNotFoundError(
            f"no committed checkpoint to evaluate in {ckpt_dir}")
    mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
    task = build_task(cfg, mesh=mesh)
    eval_pipe = _build_eval_pipe(cfg, task, mesh)
    # The optimizer is never stepped; a schedule-free SGD keeps the state
    # tree minimal (restore targets only the keys the template carries,
    # so the checkpoint's real optimizer slots are simply not read).
    import optax

    tx = optax.sgd(0.0)
    state = create_train_state(
        jax.random.PRNGKey(cfg.train.seed), task.init, tx, mesh,
        param_rules=getattr(task, "param_rules", ()),
        ema=cfg.train.ema_decay > 0,
        shard_opt_state=False,
    )
    manager = CheckpointManager(ckpt_dir,
                                retry=retry_policy_from_config(cfg.checkpoint))
    restored, at_step = manager.restore_or_none(state, step=step)
    state = restored
    trainer = _build_trainer(cfg, task, tx, mesh)
    if jax.process_index() == 0:
        print(f"[dlcfn-tpu] evaluating checkpoint step {at_step} "
              f"({describe(mesh)})")
    metrics = _final_eval(cfg, task, trainer, state, eval_pipe)
    metrics["checkpoint_step"] = int(at_step)
    return metrics


def run_experiment(
    cfg: ExperimentConfig,
    max_steps: Optional[int] = None,
    mesh=None,
) -> Dict[str, float]:
    """Run (or resume) the experiment; returns final eval metrics."""
    mesh = mesh if mesh is not None else build_mesh(cfg.mesh)
    task = build_task(cfg, mesh=mesh)

    local_batch = local_batch_size(cfg.train.global_batch, mesh)
    train_pipe = build_pipeline(cfg.data, local_batch,
                                cfg.model.num_classes, seed=cfg.train.seed,
                                train=True)
    eval_pipe = _build_eval_pipe(cfg, task, mesh)

    steps_per_epoch = max(train_pipe.steps_per_epoch, 1)
    total_steps = (cfg.train.steps if cfg.train.steps > 0
                   else int(cfg.train.epochs * steps_per_epoch))
    if max_steps is not None:
        total_steps = min(total_steps, max_steps)

    schedule = build_schedule(cfg.schedule, total_steps,
                              cfg.train.global_batch, steps_per_epoch)
    tx = build_optimizer(cfg.optimizer, schedule)

    rng = jax.random.PRNGKey(cfg.train.seed)
    init_rng, data_rng, train_rng = jax.random.split(rng, 3)
    workdir, ckpt_dir = _workdir_and_ckpt_dir(cfg)
    metrics_path = os.path.join(workdir, "metrics.jsonl")
    writer = MetricsWriter(metrics_path)
    # Span records (train.init_state/first_step/dispatch/realize/eval,
    # ckpt.save/restore/retry) flow into the SAME metrics.jsonl, from the
    # state's build on — additive lines with a "span" key,
    # not on stdout (spans are high-rate; stdout stays the human stream).
    # Existing keys keep their bytes.
    span_sink = None
    if obs_enabled():
        span_sink = JsonlSink(MetricsWriter(metrics_path,
                                            also_stdout=False))
        get_tracer().add_sink(span_sink)
    try:
        state = create_train_state(
            init_rng, task.init, tx, mesh,
            param_rules=getattr(task, "param_rules", ()),
            ema=cfg.train.ema_decay > 0,
            shard_opt_state=cfg.train.shard_opt_state,
        )

        ckpt_every = cfg.checkpoint.every_steps or steps_per_epoch
        manager = CheckpointManager(
            ckpt_dir, every_steps=ckpt_every, keep=cfg.checkpoint.keep,
            async_write=cfg.checkpoint.async_write,
            retry=retry_policy_from_config(cfg.checkpoint))
        if cfg.checkpoint.resume:
            # Sweep torn step dirs left by a crashed attempt BEFORE anything
            # else touches the store: no save is in flight yet, and a later
            # re-save of a swept step must start from an empty directory.
            if jax.process_index() == 0:
                orphans = manager.sweep_orphans()
                if orphans:
                    print(f"[dlcfn-tpu] swept {len(orphans)} uncommitted "
                          f"checkpoint dir(s): steps {orphans}")
            restored, at_step = manager.restore_or_none(state)
            if restored is not None:
                state = restored
                if jax.process_index() == 0:
                    print(f"[dlcfn-tpu] resumed from step {at_step}")

        trainer = _build_trainer(cfg, task, tx, mesh)
        if jax.process_index() == 0:
            print(f"[dlcfn-tpu] {describe(mesh)}")
            print(f"[dlcfn-tpu] total_steps={total_steps} "
                  f"steps_per_epoch={steps_per_epoch} "
                  f"global_batch={cfg.train.global_batch}")

        def ckpt_hook(step, st, _metrics):
            manager.save(step, st)

        # ckpt_hook first, chaos kill (test harness, env-gated) after it: the
        # SIGKILL then lands between a dispatched save and the next one — the
        # torn-commit window the recovery contract must survive.
        hooks = [ckpt_hook]
        chaos_hook = chaos_kill_hook_from_env()
        if chaos_hook is not None:
            hooks.append(chaos_hook)

        eval_every = cfg.train.eval_every_steps or steps_per_epoch
        state = trainer.fit(
            state,
            train_pipe.epochs(start_epoch=int(state.step) // steps_per_epoch,
                              skip_batches=int(state.step) % steps_per_epoch),
            num_steps=total_steps,
            rng=train_rng,
            eval_iter_fn=lambda: eval_pipe.one_epoch(),
            eval_every=eval_every,
            hooks=tuple(hooks),
            log_every=cfg.train.log_every_steps,
            metrics_writer=writer,
            trace_dir=os.path.join(workdir, "profile")
            if cfg.train.profile_steps > 0 else None,
            trace_steps=cfg.train.profile_steps,
        )
        manager.save(int(state.step), state, force=True)
        manager.wait()

        final = _final_eval(cfg, task, trainer, state, eval_pipe)
        writer.write({"step": int(state.step),
                      "ckpt_store_retries": manager.store_retries(),
                      **{f"final_eval_{k}": v for k, v in final.items()}})
    finally:
        writer.close()
        if span_sink is not None:
            get_tracer().remove_sink(span_sink)
            span_sink.close()
        if obs_enabled() and jax.process_index() == 0:
            # One end-of-run Prometheus text snapshot of every instrument
            # the tracer's registry accumulated (span_dur_s histograms
            # included) — scrape-by-file, no server.
            write_prometheus(get_tracer().registry,
                             os.path.join(workdir, "metrics.prom"))
    del data_rng
    return final
