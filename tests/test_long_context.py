"""Long-context model integration (models/bert_long.py, 'seq' axis).

ops/ring_attention.py and ops/ulysses.py are op-level proven in
tests/test_ops.py (vs a single-device oracle, forward + backward); these
tests prove the MODEL-level integration: bert_long trained on a
(data, seq) mesh reproduces pure-DP numerics through the full trainer for
both strategies, while the sequence dim of the activations is actually
sharded.
"""

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
from deeplearning_cfn_tpu.parallel.mesh import build_mesh


def _run_long(mesh_cfg, impl, steps=6, num_heads=4):
    from deeplearning_cfn_tpu.data import build_pipeline
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(name="bert_long", num_classes=2,
                          kwargs=dict(vocab_size=64, hidden_size=32,
                                      num_layers=2, num_heads=num_heads,
                                      mlp_dim=64, max_len=32,
                                      seq_impl=impl)),
        data=DataConfig(name="wikipedia_mlm", seq_len=32, vocab_size=64,
                        num_train_examples=128, prefetch=0),
        train=TrainConfig(global_batch=16, dtype="float32"),
        optimizer=OptimizerConfig(name="adamw", weight_decay=0.01),
        schedule=ScheduleConfig(name="constant", base_lr=3e-3,
                                warmup_steps=0),
        mesh=mesh_cfg,
    )
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg, mesh=mesh)
    sched = build_schedule(cfg.schedule, 100, 16, 8)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh, donate=False)
    pipe = build_pipeline(cfg.data, 16, 2, seed=0, train=True)
    it = pipe.epochs()
    losses = []
    for _ in range(steps):
        batch = trainer.device_batch(next(it))
        state, m = trainer.train_step(state, batch, jax.random.PRNGKey(1))
        losses.append(float(m["loss"]))
    return state, losses


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_seq_parallel_matches_data_parallel(impl, devices):
    """bert_long trained 8 steps on a (data=2, seq=4) mesh reproduces the
    pure-DP (data=8) run for both sequence-parallel strategies."""
    state_sp, loss_sp = _run_long(MeshConfig(data=2, seq=4), impl)
    state_dp, loss_dp = _run_long(MeshConfig(data=8), impl)
    np.testing.assert_allclose(loss_sp, loss_dp, rtol=3e-4)
    # Params: atol 5e-3 — the blockwise online softmax reduces in a very
    # different order from the monolithic one, and optimizer steps amplify
    # that float32 noise; the op itself is oracle-tested bit-tight in
    # test_ops.py, and the loss check above pins the trajectory.
    for a, b in zip(jax.tree_util.tree_leaves(state_sp.params),
                    jax.tree_util.tree_leaves(state_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


@pytest.mark.parametrize("impl,collective", [
    ("ring", "ppermute"),
    ("ulysses", "all_to_all"),
])
def test_seq_attention_actually_parallel(impl, collective, devices):
    """The forward on a (data=2, seq=4) mesh really runs the
    sequence-parallel op — its collective primitive must appear in the
    jaxpr. Guards the silent-fallback path in SeqParallelAttention (mesh
    unthreaded → plain dense attention, correct numerics, zero
    parallelism)."""
    from deeplearning_cfn_tpu.models import build_model

    mesh = build_mesh(MeshConfig(data=2, seq=4))
    model = build_model("bert_long", 2, jnp.float32, vocab_size=64,
                        hidden_size=32, num_layers=1, num_heads=4,
                        mlp_dim=64, max_len=32, seq_impl=impl, mesh=mesh,
                        batch_axes="data")
    ids = jnp.zeros((8, 32), jnp.int32)
    pos = jnp.zeros((8, 4), jnp.int32)
    variables = jax.jit(lambda key: model.init(
        key, ids, jnp.ones_like(ids), ids, pos, train=False))(
            jax.random.PRNGKey(0))

    fwd = lambda v: model.apply(v, ids, jnp.ones_like(ids), ids, pos,
                                train=False)
    jaxpr_text = str(jax.make_jaxpr(fwd)(variables))
    assert collective in jaxpr_text, \
        f"{impl} attention fell back to dense: no {collective} in jaxpr"
    out = jax.jit(fwd)(variables)
    assert bool(jnp.all(jnp.isfinite(out["mlm_logits"])))


def test_ulysses_head_divisibility_error(devices):
    """num_heads not divisible by the seq ways must fail loudly (the
    ulysses op's own contract), not silently mis-shard."""
    with pytest.raises(ValueError, match="divisible"):
        _run_long(MeshConfig(data=2, seq=4), "ulysses", steps=1,
                  num_heads=2)


def test_seq_impl_unknown_raises(devices):
    with pytest.raises(KeyError):
        _run_long(MeshConfig(data=2, seq=4), "nope", steps=1)


def _run_gpt_long(mesh_cfg, impl, steps=6):
    from deeplearning_cfn_tpu.data import build_pipeline
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(name="gpt_long",
                          kwargs=dict(vocab_size=64, hidden_size=32,
                                      num_layers=2, num_heads=4,
                                      mlp_dim=64, max_len=32,
                                      seq_impl=impl)),
        data=DataConfig(name="lm_text", seq_len=32, vocab_size=64,
                        num_train_examples=128, prefetch=0),
        train=TrainConfig(global_batch=16, dtype="float32"),
        optimizer=OptimizerConfig(name="adamw", weight_decay=0.01),
        schedule=ScheduleConfig(name="constant", base_lr=3e-3,
                                warmup_steps=0),
        mesh=mesh_cfg,
    )
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg, mesh=mesh)
    sched = build_schedule(cfg.schedule, 100, 16, 8)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh, donate=False)
    pipe = build_pipeline(cfg.data, 16, 0, seed=0, train=True)
    it = pipe.epochs()
    losses = []
    for _ in range(steps):
        batch = trainer.device_batch(next(it))
        state, m = trainer.train_step(state, batch, jax.random.PRNGKey(1))
        losses.append(float(m["loss"]))
    return state, losses


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_gpt_long_seq_parallel_matches_data_parallel(impl, devices):
    """The CAUSAL long-context trunk: gpt_long on (data=2, seq=4)
    reproduces pure-DP numerics — proving the sequence-parallel ops'
    causal masking composes correctly with global block offsets."""
    state_sp, loss_sp = _run_gpt_long(MeshConfig(data=2, seq=4), impl)
    state_dp, loss_dp = _run_gpt_long(MeshConfig(data=8), impl)
    np.testing.assert_allclose(loss_sp, loss_dp, rtol=3e-4)
    for a, b in zip(jax.tree_util.tree_leaves(state_sp.params),
                    jax.tree_util.tree_leaves(state_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-3)


@pytest.mark.parametrize("impl,collective", [("ring", "ppermute"),
                                             ("ulysses", "all_to_all")])
def test_gpt_long_attention_actually_parallel(impl, collective, devices):
    from deeplearning_cfn_tpu.models import build_model

    mesh = build_mesh(MeshConfig(data=2, seq=4))
    model = build_model("gpt_long", 0, jnp.float32, vocab_size=64,
                        hidden_size=32, num_layers=1, num_heads=4,
                        mlp_dim=64, max_len=32, seq_impl=impl, mesh=mesh,
                        batch_axes="data")
    ids = jnp.zeros((8, 32), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), ids, train=False)
    fwd = lambda v: model.apply(v, ids, train=False)
    jaxpr_text = str(jax.make_jaxpr(fwd)(variables))
    assert collective in jaxpr_text, \
        f"{impl} attention fell back to dense: no {collective} in jaxpr"
    out = jax.jit(fwd)(variables)
    assert bool(jnp.all(jnp.isfinite(out)))


@pytest.mark.parametrize("impl", ["ring"])
def test_three_axis_composition_matches_data_parallel(impl, devices):
    """DP × TP × SP composed on one mesh: bert_long on
    (data=2, seq=2, model=2) — batch sharded, block kernels sharded over
    'model' (PARAM_RULES), sequence sharded with ring attention — must
    reproduce the pure-DP (data=8) trajectory. The strongest composition
    claim a fake-device mesh can prove."""
    state_3ax, loss_3ax = _run_long(MeshConfig(data=2, seq=2, model=2),
                                    impl, num_heads=4)
    state_dp, loss_dp = _run_long(MeshConfig(data=8), impl, num_heads=4)
    np.testing.assert_allclose(loss_3ax, loss_dp, rtol=3e-4)
    # Param atol 1e-2 (vs 5e-3 for the single-axis tests): THREE distinct
    # reduction orders (TP psum, ring online-softmax, DP grad psum) each
    # contribute f32 noise the optimizer amplifies over the steps; the
    # rtol-tight loss trajectory above is the equivalence pin.
    for a, b in zip(jax.tree_util.tree_leaves(state_3ax.params),
                    jax.tree_util.tree_leaves(state_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-2)
    # The 3-axis run really sharded kernels over 'model'.
    n_tp = sum(
        1 for leaf in jax.tree_util.tree_leaves(state_3ax.params)
        if (spec := getattr(leaf.sharding, "spec", None))
        and any(ax == "model" for ax in spec if ax))
    assert n_tp >= 6, n_tp
