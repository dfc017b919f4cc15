"""Mixture-of-Experts / expert parallelism (models/moe.py, 'expert' axis).

The reference has no MoE (SURVEY.md §3.2 lists EP as absent); these tests
hold the rebuild's extension to the same bar as the other parallelism
strategies: routing math proven against a per-token dense recomputation,
and the expert-parallel mesh proven numerically invisible vs pure DP while
the expert weights are asserted actually sharded.
"""

import re

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
from deeplearning_cfn_tpu.models.moe import MoeMlp, router_assignment


def test_router_assignment_places_and_drops():
    """Top-1, E=2, C=1: first token claiming each expert keeps its slot,
    later tokens overflowing capacity are dropped."""
    probs = jnp.asarray([[[0.9, 0.1],   # -> expert 0, slot 0
                          [0.8, 0.2],   # -> expert 0, over capacity: drop
                          [0.3, 0.7]]])  # -> expert 1, slot 0
    dispatch, combine = router_assignment(probs, capacity=1, top_k=1)
    assert dispatch.shape == (1, 3, 2, 1)
    np.testing.assert_allclose(dispatch[0, 0, 0, 0], 1.0)
    np.testing.assert_allclose(jnp.sum(dispatch[0, 1]), 0.0)  # dropped
    np.testing.assert_allclose(dispatch[0, 2, 1, 0], 1.0)
    # Top-1 gates renormalize to 1.0 for kept tokens.
    np.testing.assert_allclose(combine[0, 0, 0, 0], 1.0)
    np.testing.assert_allclose(combine[0, 2, 1, 0], 1.0)


def test_router_assignment_top2_priority():
    """First choices claim capacity before any second choice: with E=2, C=2
    and three tokens all preferring expert 0, the third token's FIRST
    choice loses to capacity but its second choice (expert 1) fits."""
    probs = jnp.asarray([[[0.6, 0.4],
                          [0.7, 0.3],
                          [0.8, 0.2]]])
    dispatch, _ = router_assignment(probs, capacity=2, top_k=2)
    per_expert = jnp.sum(dispatch, axis=(1, 3))  # [B, E] kept counts
    assert per_expert[0, 0] == 2  # tokens 0, 1 first-choice slots
    assert per_expert[0, 1] == 2  # capacity 2: tokens 0, 1 second choices
    # Token 2 got nothing: expert 0 full from first choices, expert 1 full
    # from higher-priority second choices of tokens 0 and 1.
    assert jnp.sum(dispatch[0, 2]) == 0


@pytest.mark.parametrize("top_k", [1, 2])
def test_moe_matches_dense_per_token(top_k):
    """With capacity ample enough that nothing drops, MoE output equals the
    dense per-token mixture: y[t] = sum_k gate_k * expert_k_mlp(x[t])."""
    b, s, f, m, e = 2, 8, 16, 32, 4
    moe = MoeMlp(num_experts=e, mlp_dim=m, capacity_factor=float(e),
                 top_k=top_k, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (b, s, f), jnp.float32)
    variables = jax.jit(moe.init)(jax.random.PRNGKey(1), x)
    y, aux = jax.jit(moe.apply)(variables, x)
    p = variables["params"]

    logits = x @ np.asarray(p["router"]["kernel"])
    probs = jax.nn.softmax(logits, axis=-1)
    w_in = np.asarray(p["w_in"])
    b_in = np.asarray(p["b_in"])
    w_out = np.asarray(p["w_out"])
    b_out = np.asarray(p["b_out"])

    expected = np.zeros((b, s, f), np.float32)
    for bi in range(b):
        for si in range(s):
            pr = np.asarray(probs[bi, si])
            order = np.argsort(-pr)[:top_k]
            gates = pr[order] / pr[order].sum()
            for gate, ei in zip(gates, order):
                h = np.asarray(jax.nn.gelu(
                    x[bi, si] @ w_in[ei] + b_in[ei]))
                expected[bi, si] += gate * (h @ w_out[ei] + b_out[ei])
    np.testing.assert_allclose(np.asarray(y), expected, atol=1e-4)
    assert float(aux["load_balance"]) >= 1.0 - 1e-5  # E*sum(f*p) >= 1
    assert np.isfinite(float(aux["router_z"]))


def _run_bert_moe(mesh_cfg, steps=10):
    from deeplearning_cfn_tpu.data import build_pipeline
    from deeplearning_cfn_tpu.parallel.mesh import build_mesh
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(name="bert_tiny", num_classes=2,
                          kwargs=dict(vocab_size=64, hidden_size=32,
                                      num_layers=2, num_heads=2,
                                      mlp_dim=64, max_len=32,
                                      num_experts=4, moe_every=2)),
        data=DataConfig(name="wikipedia_mlm", seq_len=32, vocab_size=64,
                        num_train_examples=256, prefetch=0),
        train=TrainConfig(global_batch=32, dtype="float32"),
        optimizer=OptimizerConfig(name="adamw", weight_decay=0.01),
        schedule=ScheduleConfig(name="constant", base_lr=3e-3,
                                warmup_steps=0),
        mesh=mesh_cfg,
    )
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 100, 32, 8)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh, donate=False)
    pipe = build_pipeline(cfg.data, 32, 2, seed=0, train=True)
    it = pipe.epochs()
    losses, metrics = [], {}
    for _ in range(steps):
        batch = trainer.device_batch(next(it))
        state, m = trainer.train_step(state, batch, jax.random.PRNGKey(1))
        losses.append(float(m["loss"]))
        metrics = m
    return state, losses, metrics


def test_expert_parallel_matches_data_parallel(devices):
    """bert_tiny with 4 experts trained 10 steps on a (data=4, expert=2)
    mesh reproduces the pure-DP (data=8) run — same loss trajectory, same
    final params — while the stacked expert weights are actually sharded
    over 'expert'."""
    state_ep, loss_ep, metrics = _run_bert_moe(MeshConfig(data=4, expert=2))
    state_dp, loss_dp, _ = _run_bert_moe(MeshConfig(data=8))

    # Expert weights actually partitioned: local shard dim0 < global E.
    n_sharded = 0
    for leaf in jax.tree_util.tree_leaves(state_ep.params):
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if spec is None or not len(spec):
            continue
        flat = []
        for s in spec:
            flat.extend(s if isinstance(s, tuple) else [s])
        if "expert" in flat:
            n_sharded += 1
            assert leaf.addressable_shards[0].data.shape[0] \
                == leaf.shape[0] // 2
    assert n_sharded >= 4, f"expected >=4 expert-sharded leaves, {n_sharded}"

    np.testing.assert_allclose(loss_ep, loss_dp, rtol=2e-4)
    # Params: atol 1e-3 — the expert einsums reduce in a different order
    # on the (data, expert) mesh, and 10 optimizer steps accumulate that
    # float32 noise; anything semantic (mis-routed tokens, wrong psum)
    # shows up orders of magnitude larger AND in the loss check above.
    for a, b in zip(jax.tree_util.tree_leaves(state_ep.params),
                    jax.tree_util.tree_leaves(state_dp.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3)

    # The MoE aux metrics surface through the trainer.
    assert "moe_load_balance" in metrics and "moe_router_z" in metrics
    # 10 adamw steps on the tiny task must move the loss.
    assert loss_ep[-1] < loss_ep[0]


# -- the routers of the expert layer that is told which experts it holds ----


def _mlp_router_case(with_state=True):
    from deeplearning_cfn_tpu.models.moe import MlpStateRouter

    router = MlpStateRouter(num_experts=8, hidden=16)
    m = jax.random.normal(jax.random.PRNGKey(0), (24, 32))
    state = jax.random.normal(jax.random.PRNGKey(1), (24, 16)) \
        if with_state else None
    params = router.init(jax.random.PRNGKey(2), m, state)
    return router, params, m, state


def test_mlp_router_weighs_its_one_choice_by_its_probability():
    """One expert a token; the weight is the chosen expert's softmax
    probability itself, so it is under 1 and the router feels the loss."""
    router, params, m, state = _mlp_router_case()
    chosen, weight, z = router.apply(params, m, state)
    assert chosen.shape == weight.shape == (24, 1) and z.shape == (24, 16)
    assert np.all(np.asarray(weight) < 1.0) \
        and np.all(np.asarray(weight) >= 1.0 / 8)
    grads = jax.grad(lambda p: jnp.sum(router.apply(p, m, state)[1]))(params)
    flat = {"/".join(str(k.key) for k in path): g for path, g in
            jax.tree_util.tree_flatten_with_path(grads["params"])[0]}
    assert set(flat) == {
        "down/kernel", "down/bias", "scale", "norm/scale",
        "hidden_0/kernel", "hidden_0/bias", "hidden_1/kernel",
        "hidden_1/bias", "out/kernel", "bias"}
    # The balancing bias enters the choice and nothing else.
    assert not np.any(np.asarray(flat.pop("bias")))
    assert all(np.any(np.asarray(g)) for g in flat.values())


def test_mlp_router_state_and_balancing_bias():
    router, params, m, state = _mlp_router_case()
    chosen, weight, z = router.apply(params, m, state)
    # Handed none, it has no gamma and hands on its own projection.
    bare, bare_params, _, _ = _mlp_router_case(with_state=False)
    assert "scale" not in bare_params["params"]
    _, _, z0 = bare.apply(bare_params, m)
    np.testing.assert_allclose(np.asarray(z - z0), np.asarray(state),
                               atol=1e-5)
    # A bias on expert 5 above every probability moves every choice there,
    # and the weight stays that expert's probability, bias not added.
    beta = jnp.zeros(8).at[5].set(2.0)
    tilted = {"params": dict(params["params"], bias=beta)}
    chosen_b, weight_b, _ = router.apply(tilted, m, state)
    assert np.all(np.asarray(chosen_b) == 5)
    assert np.all(np.asarray(weight_b) < 1.0)
    assert np.any(np.asarray(chosen) != 5)


def test_mlp_router_sows_its_balancing_biases_step():
    """``beta_e <- beta_e - BALANCE_RATE * min(n_e / mean(n) - 1, 1)``: sown
    as ``nudges/bias`` where that collection is asked for, and nowhere
    else."""
    from deeplearning_cfn_tpu.models.moe import BALANCE_RATE

    router, params, m, state = _mlp_router_case()
    assert set(params) == {"params"}
    (chosen, _, _), sown = router.apply(params, m, state, mutable=["nudges"])
    load = np.bincount(np.asarray(chosen)[:, 0], minlength=8)
    assert load.sum() == 24 and load.max() > 6
    np.testing.assert_allclose(
        np.asarray(sown["nudges"]["bias"]),
        -BALANCE_RATE * np.minimum(load / 3.0 - 1.0, 1.0), atol=1e-7)
    assert len(router.apply(params, m, state)) == 3


def test_held_experts_layer_is_given_a_router_or_top_k():
    from deeplearning_cfn_tpu.models.moe import HeldExpertsMlp, \
        MlpStateRouter

    x = jnp.zeros((1, 8, 32))
    for kwargs in (dict(), dict(top_k=2, router=MlpStateRouter(8, 16)),
                   dict(top_k=9)):
        with pytest.raises(ValueError):
            HeldExpertsMlp(num_experts=8, mlp_dim=16, **kwargs).init(
                jax.random.PRNGKey(0), x)


def test_held_experts_layer_keeps_its_first_routers_names():
    """Told no router it has the sigmoid top-k one, its matrix where it was
    (``router/kernel``); told an MLP router, that one's tree under the same
    name, and its state beside what the layer counted."""
    from deeplearning_cfn_tpu.models.moe import HeldExpertsMlp, \
        MlpStateRouter

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    first = HeldExpertsMlp(num_experts=8, mlp_dim=16, top_k=2, held=(0, 4),
                           routed_scale=2.5, shared_dim=8, dtype=jnp.float32)
    params = jax.jit(first.init)(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"router", "experts_in", "experts_out", "shared"}
    assert params["router"]["kernel"].shape == (32, 8)
    out, aux = jax.jit(first.apply)({"params": params}, x)
    assert set(aux) == {"rows_held", "load_max_over_mean"}

    second = HeldExpertsMlp(num_experts=8, mlp_dim=16, held=(0, 4),
                            dtype=jnp.float32,
                            router=MlpStateRouter(8, 16))
    state = jnp.ones((2, 16, 16))
    params = jax.jit(second.init)(jax.random.PRNGKey(1), x, state)["params"]
    assert set(params) == {"router", "experts_in", "experts_out"}
    assert set(params["router"]) == {"down", "scale", "norm", "hidden_0",
                                     "hidden_1", "out", "bias"}
    out, aux = jax.jit(second.apply)({"params": params}, x, state)
    assert aux["router_state"].shape == (2, 16, 16)
    assert 0 <= float(aux["rows_held"]) <= 32
    # One choice a token and half of the experts held: twice a uniform
    # router's rows are every pair, so there is one buffer and no branch.
    text = str(jax.make_jaxpr(lambda p: second.apply({"params": p}, x,
                                                     state))(params))
    assert " cond[" not in text


# -- rows to the buffer and back: take_rows and sum_rows ----------------------

_TOKENS, _EXPERTS, _WIDTH = 24, 8, 16
# name -> (experts held, what the buffer holds beside the rows held; None is
# the buffer of every pair)
_BUFFERS = {
    "live_rows_under_the_buffer": (4, 5),
    "live_rows_exactly_at_the_buffer": (4, 0),
    "the_buffer_of_every_pair": (4, None),
    "every_pair_held": (_EXPERTS, None),
    # Two experts are held, and the routing draws from the others alone.
    "no_row_held": (0, 8),
    # More pairs held than the buffer has rows: its rows are all live.
    "more_held_than_the_buffer": (4, -3),
}
# (choices a token, the rows' width, how a row is fetched): XLA's gathers at
# any width, and the row kernel interpreted, which wants whole lane tiles: 256,
# and 384 = 3 x 128, no power of two, as Mellum2's 2304 = 18 x 128 is none.
_GATHERS, _INTERPRETED = ("gather", "gather"), ("interpret", "interpret")
_FETCHES = [(1, _WIDTH, _GATHERS), (4, _WIDTH, _GATHERS),
            (1, 256, _INTERPRETED), (8, 384, _INTERPRETED)]


def _sorted_routing(seed, top_k, held, experts=_EXPERTS):
    """A token's ``top_k`` choices, distinct experts, and what the layer
    makes of them: ``(chosen, group, order, inv, n_held)``. ``held`` 0 holds
    two experts that no token chooses."""
    from deeplearning_cfn_tpu.models.moe import inverse_permutation

    rng = np.random.RandomState(seed)
    count = held or 2
    drawn_from = experts if held else experts - count
    chosen = np.stack([rng.permutation(drawn_from)[:top_k]
                       for _ in range(_TOKENS)])
    if not held:
        chosen = chosen + count
    group = jnp.minimum(jnp.asarray(chosen.reshape(-1), jnp.int32), count)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    return chosen, group, order, inverse_permutation(order), \
        int(jnp.sum(group < count))


@pytest.mark.parametrize("top_k,width,path", _FETCHES)
@pytest.mark.parametrize("buffer", sorted(_BUFFERS))
def test_rows_move_as_the_plain_forms_move_them(buffer, top_k, width, path):
    """``take_rows`` and ``sum_rows`` against what they replace, the masked
    gather ``where(valid, m[token], 0)`` and ``segment_sum`` of the masked,
    weighted rows, in value and in every gradient (``m``; ``y`` and the
    weights), in float32, by XLA's gathers and by the row kernel."""
    from deeplearning_cfn_tpu.models.moe import sum_rows, take_rows

    held, spare = _BUFFERS[buffer]
    # Eight distinct choices want more than eight experts to draw from.
    experts = max(_EXPERTS, 2 * top_k)
    held = experts if held == _EXPERTS else held
    chosen, group, order, inv, n_held = _sorted_routing(7, top_k, held,
                                                        experts)
    pairs = _TOKENS * top_k
    rows = pairs if spare is None else max(n_held + spare, 1)
    assert 0 < rows <= pairs and 0 <= n_held <= pairs
    if held in (0, experts):
        assert n_held == (pairs if held else 0)
    if spare is not None and spare < 0:
        assert n_held > rows
    # A token's choices are distinct experts, so no group holds a token
    # twice: a token's live rows are as many as the held experts it chose.
    assert all(len(set(row)) == top_k for row in chosen.tolist())
    pair = order[:rows]
    token = pair // top_k
    n_live = jnp.minimum(n_held, rows)
    valid = (jnp.arange(rows) < n_live)[:, None]
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    m = jax.random.normal(keys[0], (_TOKENS, width))
    y = jax.random.normal(keys[1], (rows, width))
    weight = jax.random.uniform(keys[2], (pairs,))
    d_xs = jax.random.normal(keys[3], (rows, width))
    d_out = jax.random.normal(keys[4], (_TOKENS, width))

    def plain_take(m):
        return jnp.where(valid, m[token], 0)

    def plain_sum(y, weight):
        return jax.ops.segment_sum(
            jnp.where(valid, y, 0) * weight[pair][:, None], token,
            num_segments=_TOKENS)

    want, back = jax.vjp(plain_take, m)
    got, back_got = jax.vjp(
        lambda m: take_rows(m, token, inv, n_live, top_k, path), m)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_allclose(back_got(d_xs)[0], back(d_xs)[0], atol=1e-5)

    want, back = jax.vjp(plain_sum, y, weight)
    got, back_got = jax.vjp(
        lambda y, w: sum_rows(y, w, order, inv, n_live, top_k, None, path),
        y, weight)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for a, b in zip(back_got(d_out), back(d_out)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-4 if width > 128 else 1e-5)
    if not n_held:
        assert not np.any(got) and not np.any(back_got(d_out)[0])


@pytest.mark.parametrize("path", [("gather", "interpret"),
                                  ("interpret", "gather")])
@pytest.mark.parametrize("buffer", sorted(_BUFFERS))
def test_each_side_of_the_movement_takes_its_own_path(buffer, path):
    """``take_rows`` and ``sum_rows`` where one side of the movement goes by
    XLA's gathers and the other by the row kernel (SDAR's and Keye's layers:
    the buffer is a source of 2 ** 27 bytes and the tokens are not), and the
    mirror, against both sides by XLA's gathers: values and every gradient
    (``m``; ``y`` and the weights), over dead slots, tokens with no live
    pair and a buffer that is not full."""
    from deeplearning_cfn_tpu.models.moe import sum_rows, take_rows

    top_k, width = 2, 128
    held, spare = _BUFFERS[buffer]
    _, group, order, inv, n_held = _sorted_routing(11, top_k, held)
    pairs = _TOKENS * top_k
    rows = pairs if spare is None else max(n_held + spare, 1)
    n_live = jnp.minimum(n_held, rows)
    token = order[:rows] // top_k
    if buffer == "live_rows_under_the_buffer":
        live = (np.asarray(inv) < int(n_live)).reshape(-1, top_k)
        assert int(n_live) < rows < pairs           # not full, dead slots
        assert (~live.any(axis=1)).any() and live.all(axis=1).any()
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    m = jax.random.normal(keys[0], (_TOKENS, width))
    y = jax.random.normal(keys[1], (rows, width))
    weight = jax.random.uniform(keys[2], (pairs,))
    d_xs = jax.random.normal(keys[3], (rows, width))
    d_out = jax.random.normal(keys[4], (_TOKENS, width))

    def both(path):
        xs, take_back = jax.vjp(
            lambda m: take_rows(m, token, inv, n_live, top_k, path), m)
        out, sum_back = jax.vjp(
            lambda y, w: sum_rows(y, w, order, inv, n_live, top_k, None,
                                  path), y, weight)
        return xs, take_back(d_xs)[0], out, *sum_back(d_out)

    for a, b in zip(both(path), both(_GATHERS)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_a_tokens_live_pairs_come_first_in_the_choices_order():
    """``_live_first``: what the kernel walks on the tokens' side."""
    from deeplearning_cfn_tpu.models.moe import _live_first

    inv = jnp.asarray([5, 90, 2, 70, 80, 60, 50, 40, 1, 0, 3, 4], jnp.int32)
    weight = jnp.arange(12, dtype=jnp.float32) + 1
    at, count, first = _live_first(inv, 6, 4, weight)
    np.testing.assert_array_equal(count, [2, 0, 4])
    np.testing.assert_array_equal(at[0, :2], [5, 2])
    np.testing.assert_array_equal(first[0, :2], [1.0, 3.0])
    np.testing.assert_array_equal(at[2], [1, 0, 3, 4])
    np.testing.assert_array_equal(first[2], [9.0, 10.0, 11.0, 12.0])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("top_k,held", [(1, 4), (4, 4), (2, 0), (8, 8)])
def test_inverse_of_the_sort_and_where_dead_pairs_lie(top_k, held, seed):
    _, group, order, inv, n_held = _sorted_routing(seed, top_k, held)
    pairs = _TOKENS * top_k
    np.testing.assert_array_equal(inv[order], np.arange(pairs))
    np.testing.assert_array_equal(order[inv], np.arange(pairs))
    dead = np.asarray(group) == (held or 2)
    assert dead.sum() == pairs - n_held
    assert np.all(np.asarray(inv)[dead] >= n_held)
    assert np.all(np.asarray(inv)[~dead] < n_held)


@pytest.mark.parametrize("width,impl,path", [(48, "auto", "gather"),
                                             (128, "interpret", "kernel")])
@pytest.mark.parametrize("top_k,held,branches", [(1, 4, 0), (2, 2, 1)])
def test_held_experts_layer_counts_a_call_once(top_k, held, branches, width,
                                               impl, path):
    """``moe.rows.calls`` counts a layer call when it is traced: once, not
    once a branch of the ``lax.cond`` over the two buffers nor again where
    ``jax.checkpoint`` traces the rows' part for the backward pass; for
    each side of the movement (``side=buffer|tokens``) under ``path=kernel``
    where the row kernel fetches the rows (rows of whole lane tiles, the
    kernels named or a TPU), else ``path=gather``."""
    from deeplearning_cfn_tpu.models.moe import HeldExpertsMlp
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, width))
    layer = HeldExpertsMlp(num_experts=8, mlp_dim=16, top_k=top_k,
                           held=(0, held), dtype=jnp.float32,
                           implementation=impl)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    calls = get_tracer().registry.counter("moe.rows.calls")
    before = calls.series()
    loss = lambda p: jnp.sum(layer.apply({"params": p}, x)[0] ** 2)
    text = str(jax.make_jaxpr(jax.grad(loss))(params))
    # Once a layer call and side of the movement.
    assert {key: n - before.get(key, 0) for key, n in calls.series().items()
            if n > before.get(key, 0)} \
        == {(("path", path), ("side", side)): 1
            for side in ("buffer", "tokens")}
    # (The kernel's own text has its ``pl.when``s.)
    assert path == "kernel" or (" cond[" in text) == bool(branches)
    assert ("live_rows" in text) == (path == "kernel")
    # No row-wide scatter-add either way: what is scattered is integers
    # (the sort's inverse) or a pair's scalar (the router's top-k).
    assert "scatter" in text
    assert not re.findall(rf",{width}\] = scatter", text)


# (choices a token, experts held, experts) of a rank of Mellum2's four: the
# usual buffer is four rows a token.
_MELLUM2 = (8, 16, 64)


@pytest.mark.parametrize("tokens,width,dtype,impl,routing,path", [
    # A rank of Mellum2's four: 32,768 tokens of 2304 in bfloat16, 151 MB,
    # and a buffer of 131,072 rows, 604 MB.
    (32768, 2304, jnp.bfloat16, "megablox", _MELLUM2, ("kernel", "kernel")),
    (32768, 2048, jnp.bfloat16, "megablox", _MELLUM2,       # 2 ** 27 bytes
     ("kernel", "kernel")),
    (16384, 2048, jnp.float32, "megablox", _MELLUM2, ("kernel", "kernel")),
    (24, 2304, jnp.bfloat16, "interpret", _MELLUM2,
     ("interpret", "interpret")),
    # Under 2 ** 27 bytes of tokens XLA's gather is the cheaper to the
    # buffer: 8,192 tokens of 2048, and 24,576 of them; their buffers of
    # four rows a token are 2 ** 27 bytes and more.
    (8192, 2048, jnp.bfloat16, "megablox", _MELLUM2, ("gather", "kernel")),
    (24576, 2048, jnp.bfloat16, "megablox", _MELLUM2, ("gather", "kernel")),
    # Off the TPU, as ``grouped_matmul`` falls back to ``ragged_dot``.
    (32768, 2304, jnp.bfloat16, "auto", _MELLUM2, ("gather", "gather")),
    (32768, 2304, jnp.bfloat16, "ragged_dot", _MELLUM2,
     ("gather", "gather")),
    # Rows the kernel cannot move: not whole lane tiles; 16-bit floats.
    (32768, 2304 + 64, jnp.bfloat16, "megablox", _MELLUM2,
     ("gather", "gather")),
    (32768, 2304, jnp.float16, "interpret", _MELLUM2, ("gather", "gather")),
    # The five expert cells. SDAR's and Keye's: 16,384 positions of 2048
    # (2 ** 26 bytes) and 16 of 128 experts held, a buffer of 32,768 rows
    # (2 ** 27): to the buffer by XLA's gather, to the tokens by the kernel.
    (16384, 2048, jnp.bfloat16, "megablox", (8, 16, 128),
     ("gather", "kernel")),
    # Laguna's: 8,192 tokens (2 ** 25), a buffer of 16,384 rows (2 ** 26).
    (8192, 2048, jnp.bfloat16, "megablox", (8, 32, 256),
     ("gather", "gather")),
    # ZAYA1's: one choice a token, the one buffer of every pair (2 ** 25).
    (8192, 2048, jnp.bfloat16, "megablox", (1, 8, 16), ("gather", "gather")),
    # Off the TPU at SDAR's shape, and with the kernel interpreted.
    (16384, 2048, jnp.bfloat16, "auto", (8, 16, 128), ("gather", "gather")),
    (16384, 2048, jnp.bfloat16, "interpret", (8, 16, 128),
     ("interpret", "interpret")),
    # A buffer one tile under the size: 2 ** 27 bytes less 512 rows.
    (16128, 2048, jnp.bfloat16, "megablox", (8, 16, 128),
     ("gather", "gather")),
])
def test_how_a_row_is_fetched_follows_what_the_layer_sees(
        tokens, width, dtype, impl, routing, path):
    """Each side of the rows' movement takes its path from its own source:
    to the buffer from the tokens, to the tokens from the usual buffer."""
    from deeplearning_cfn_tpu.models.moe import rows_path

    assert jax.default_backend() == "cpu"
    assert rows_path(impl, tokens, width, dtype, *routing) == path
