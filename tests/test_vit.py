"""Vision Transformer: shapes, train-mode dropout, convergence through
ClassificationTask, and TP kernel sharding."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.config import (
    DataConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    OptimizerConfig,
    ScheduleConfig,
    TrainConfig,
)
from deeplearning_cfn_tpu.metrics import read_metrics
from deeplearning_cfn_tpu.models import build_model
from deeplearning_cfn_tpu.train.run import run_experiment


def test_vit_shapes_and_params():
    model = build_model("vit_s16", num_classes=1000, dtype=jnp.bfloat16)
    x = jnp.zeros((2, 224, 224, 3))
    # Shapes and dtypes are all this asks, so nothing is computed.
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False))
    n = sum(int(np.prod(p.shape)) for p in
            jax.tree_util.tree_leaves(variables["params"]))
    assert 20e6 < n < 24e6, n  # ViT-S/16 ≈ 22M
    logits = jax.eval_shape(lambda v: model.apply(v, x, train=False),
                            variables)
    assert logits.shape == (2, 1000)
    assert logits.dtype == jnp.float32

    with pytest.raises(ValueError, match="divisible"):
        jax.eval_shape(lambda v: model.apply(
            v, jnp.zeros((1, 100, 100, 3)), train=False), variables)


def test_vit_dropout_active_in_train_mode():
    """The stats-free train path must run a REAL train-mode forward:
    dropout noise varies with the rng (the silent train=False fallback
    this change removed would make these identical)."""
    from deeplearning_cfn_tpu.train.task import ClassificationTask

    cfg = ExperimentConfig(
        model=ModelConfig(name="vit_tiny", num_classes=10,
                          kwargs=dict(dropout_rate=0.5)),
        data=DataConfig(name="cifar10", image_size=32),
        train=TrainConfig(dtype="float32"),
    )
    task = ClassificationTask(cfg)
    variables = jax.jit(task.init)(jax.random.PRNGKey(0))
    # The head kernel is zero-init (logits would be constant and hide the
    # dropout noise) — randomize it for this test.
    params = jax.tree_util.tree_map(lambda x: x, variables["params"])
    params["head"]["kernel"] = jax.random.normal(
        jax.random.PRNGKey(3), params["head"]["kernel"].shape) * 0.1
    variables = {"params": params}
    batch = {"image": jnp.ones((4, 32, 32, 3)),
             "label": jnp.zeros((4,), jnp.int32)}
    loss_fn = jax.jit(lambda params, key: task.loss_fn(
        params, {}, batch, key, True))
    l1, _ = loss_fn(variables["params"], jax.random.PRNGKey(1))
    l2, _ = loss_fn(variables["params"], jax.random.PRNGKey(2))
    eval_fn = jax.jit(lambda params: task.loss_fn(
        params, {}, batch, None, False))
    l_eval1, _ = eval_fn(variables["params"])
    l_eval2, _ = eval_fn(variables["params"])
    assert float(l1) != float(l2)  # dropout noise differs by rng
    assert float(l_eval1) == float(l_eval2)  # eval is deterministic


def test_vit_trains_end_to_end(tmp_workdir, devices):
    cfg = ExperimentConfig(
        model=ModelConfig(name="vit_tiny", num_classes=10,
                          kwargs=dict(dropout_rate=0.0)),
        data=DataConfig(name="cifar10", image_size=32,
                        num_train_examples=256, num_eval_examples=64,
                        prefetch=0),
        train=TrainConfig(global_batch=32, dtype="float32", eval_batch=32,
                          steps=40, log_every_steps=5),
        optimizer=OptimizerConfig(name="adamw", weight_decay=0.01,
                                  grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="constant", base_lr=1e-3,
                                warmup_steps=5),
        mesh=MeshConfig(data=-1),
    )
    cfg.workdir = os.path.join(tmp_workdir, "work")
    cfg.checkpoint.async_write = False
    final = run_experiment(cfg)
    records = [r for r in read_metrics(
        os.path.join(cfg.workdir, "vit_tiny", "metrics.jsonl"))
        if "loss" in r]
    assert records[-1]["loss"] < records[0]["loss"] - 0.3, \
        (records[0], records[-1])
    assert {"accuracy", "accuracy_top5"} <= set(final)


def test_vit_tensor_parallel_shards_kernels(devices):
    from deeplearning_cfn_tpu.parallel import build_mesh
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, build_schedule
    from deeplearning_cfn_tpu.train.task import build_task

    cfg = ExperimentConfig(
        model=ModelConfig(name="vit_tiny", num_classes=10),
        data=DataConfig(name="cifar10", image_size=32),
        train=TrainConfig(global_batch=16, dtype="float32"),
        mesh=MeshConfig(data=4, model=2),
    )
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, 16, 4)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules)
    n_sharded = sum(
        1 for leaf in jax.tree_util.tree_leaves(state.params)
        if (spec := getattr(leaf.sharding, "spec", None))
        and any(ax == "model" for ax in spec if ax))
    assert n_sharded >= 6, n_sharded
