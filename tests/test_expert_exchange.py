"""A step on a mesh of more than one device: the ``shard_map`` round the
Pallas kernels (``parallel/kernels.py``) and the expert layer's exchange
between expert-parallel ranks (``models/moe.py``), on virtual CPU devices.

The shares add up to the whole through the program's own exchange: a layer, and
a whole train step, on ``expert=4`` give what the same parameters give on one
device and what the plain form gives; no routing drops a row; on one device
nothing is wrapped."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


def _mesh(devices, **axes):
    from deeplearning_cfn_tpu.config import MeshConfig
    from deeplearning_cfn_tpu.parallel.mesh import build_mesh

    cfg = MeshConfig(**{"data": 1, **axes})
    n = int(np.prod(list({"data": 1, **axes}.values())))
    return build_mesh(cfg, devices=devices[:n])


# -- the helper ---------------------------------------------------------------


def test_shard_rows_is_the_function_itself_on_one_device(devices):
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.parallel.kernels import batch_axes_of, \
        rows_spec, shard_rows
    from jax.sharding import PartitionSpec as P

    fn = lambda x: x + 1
    calls = get_tracer().registry.counter("parallel.shard_map.calls")
    before = calls.value(kernel="flash")
    one = _mesh(devices)
    assert batch_axes_of(None) == () and batch_axes_of(one) == ()
    assert shard_rows(fn, None, "flash", (P(),), P()) is fn
    assert shard_rows(fn, one, "flash", (P(),), P()) is fn
    assert calls.value(kernel="flash") == before
    # Tensor parallelism alone shards no batch: nothing to run a device on.
    assert batch_axes_of(_mesh(devices, model=4)) == ()
    four = _mesh(devices, data=2, expert=2)
    assert batch_axes_of(four) == ("data", "expert")
    assert rows_spec(("data", "expert"), 3) == P(("data", "expert"), None,
                                                 None)
    assert rows_spec(("expert",), 2, dim=1) == P(None, "expert")
    assert rows_spec((), 2) == P(None, None)
    wrapped = shard_rows(fn, four, "flash",
                         (rows_spec(("data", "expert"), 2),),
                         rows_spec(("data", "expert"), 2))
    assert wrapped is not fn
    assert calls.value(kernel="flash") == before + 1
    np.testing.assert_array_equal(wrapped(jnp.zeros((8, 3))), 1.0)


def _tiny_step_jaxpr(mesh, impl):
    """The jaxpr of ``gpt_tiny``'s train step as the benchmark's tiny
    ``gpt2_small_train`` builds it."""
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.state import TrainState
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = get_preset("gpt_small_lm")
    apply_overrides(cfg, [
        "model.name=gpt_tiny", "data.vocab_size=512", "data.seq_len=64",
        "model.kwargs.max_len=64", f"model.kwargs.attention_impl={impl}",
        "train.dtype=float32", "train.global_batch=8", "mesh.data=1",
        "train.shard_opt_state=false"])
    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer, build_schedule(
        cfg.schedule, cfg.train.steps, cfg.train.global_batch, None))
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(task.init, key)["params"]
    state = jax.eval_shape(lambda p: TrainState(
        step=jnp.zeros((), jnp.int32), params=p, batch_stats={},
        opt_state=tx.init(p)), params)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 65), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((8, 64), jnp.float32)}
    step = Trainer(cfg, task.loss_fn, tx, mesh=_mesh(jax.devices()))\
        ._train_step_fn()
    return str(jax.make_jaxpr(step)(state, batch, key))


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_one_device_mesh_leaves_the_dense_step_as_it_was(devices, impl):
    """``gpt2_small_train``'s step at the tiny size, told the benchmark's
    mesh of one device, is the step told no mesh, equation for equation: the
    wrapper is not entered, with the flash kernels or without."""
    bare = _tiny_step_jaxpr(None, impl)
    assert _tiny_step_jaxpr(_mesh(devices), impl) == bare
    assert "shard_map" not in bare
    assert ("pallas_call" in bare) == (impl == "interpret")


def test_flash_kernels_run_a_device_each_under_the_wrapper(devices):
    """Four sequences over a data=2 x expert=2 mesh through the flash
    kernels (interpret mode), forward and backward, against the plain
    attention; the wrapper was entered once for the call."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.ops.attention import attention_reference, \
        fused_attention

    mesh = _mesh(devices, data=2, expert=2)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.normal(0, 1, (4, 4, 128, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(0, 1, (4, 2, 128, 16)), jnp.float32)
            for _ in range(2))
    calls = get_tracer().registry.counter("parallel.shard_map.calls")
    before = calls.value(kernel="flash")

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.square(fn(q, k, v)))

    got = jax.jit(jax.value_and_grad(loss(lambda q, k, v: fused_attention(
        q, k, v, causal=True, implementation="interpret", window=32,
        mesh=mesh)), argnums=(0, 1, 2)))(q, k, v)
    assert calls.value(kernel="flash") == before + 1
    want = jax.value_and_grad(loss(lambda q, k, v: attention_reference(
        q, k, v, None, True, 0.25, 32)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


def test_scan_kernels_run_a_device_each_under_the_wrapper(devices):
    """Four sequences over a data=2 x expert=2 mesh through the state-space
    scan's kernels (interpret mode), forward and backward, against the
    einsums on no mesh; the wrapper was entered once for the call."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.ops.ssd import ssd_scan

    mesh = _mesh(devices, data=2, expert=2)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (4, 256, 2, 64)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.1, (4, 256, 2)), jnp.float32)
    a = jnp.asarray([-0.5, -4.0])
    b, c = (jnp.asarray(rng.normal(0, 1, (4, 256, 1, 128)), jnp.float32)
            for _ in range(2))
    calls = get_tracer().registry.counter("parallel.shard_map.calls")
    before = calls.value(kernel="ssd")

    def loss(**how):
        return lambda *t: jnp.sum(jnp.square(ssd_scan(*t, chunk=128, **how)))

    got = jax.jit(jax.value_and_grad(
        loss(implementation="interpret", mesh=mesh),
        argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    assert calls.value(kernel="ssd") == before + 1
    want = jax.jit(jax.value_and_grad(
        loss(implementation="reference"),
        argnums=(0, 1, 2, 3, 4)))(x, dt, a, b, c)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-5 * scale)


def test_convolution_kernels_run_a_device_each_under_the_wrapper(devices):
    """Four sequences over a data=2 x expert=2 mesh through the causal
    convolution's kernels (interpret mode), forward and backward, against
    the shifted multiply-adds on no mesh; the taps' and the bias's
    gradients are summed over the devices; the wrapper was entered once."""
    from deeplearning_cfn_tpu.models.ssm import CausalConv
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    mesh = _mesh(devices, data=2, expert=2)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.normal(0, 1, (4, 64, 256)), jnp.float32)
    params = {"params": {
        "kernel": jnp.asarray(rng.uniform(-0.1, 0.1, (4, 256)), jnp.float32),
        "bias": jnp.asarray(rng.normal(0, 0.3, (256,)), jnp.float32)}}
    calls = get_tracer().registry.counter("parallel.shard_map.calls")
    before = calls.value(kernel="conv")

    def loss(*how):
        return lambda p, x: sum(jnp.sum(jnp.square(part)) for part in
                                CausalConv(4).apply(p, x, (128,), *how))

    got = jax.jit(jax.value_and_grad(loss("interpret", mesh),
                                     argnums=(0, 1)))(params, x)
    assert calls.value(kernel="conv") == before + 1
    want = jax.value_and_grad(loss("reference"), argnums=(0, 1))(params, x)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-5 * scale)


def test_rotary_kernel_with_the_norm_runs_a_device_each_under_the_wrapper(
        devices):
    """Four sequences over a data=2 x expert=2 mesh through the rotary kernel
    with a block's q/k norm inside (interpret mode), forward and backward,
    against the same call on no mesh: the scale is whole on every device and
    its gradient is summed over them; the wrapper was entered once."""
    from deeplearning_cfn_tpu.models.transformer import Rope, rope_to_heads
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    mesh = _mesh(devices, data=2, expert=2)
    rope = Rope(theta=1_000_000.0)
    kx, ks, kg = jax.random.split(jax.random.PRNGKey(0), 3)
    x = 3.0 * jax.random.normal(kx, (4, 512, 2, 128), jnp.bfloat16)
    scale = 1.0 + 0.25 * jax.random.normal(ks, (128,), jnp.float32)
    weight = jax.random.normal(kg, (4, 2, 512, 128), jnp.float32)
    calls = get_tracer().registry.counter("parallel.shard_map.calls")
    before = calls.value(kernel="rope")

    def loss(mesh):
        return lambda x, scale: jnp.sum(weight * rope_to_heads(
            x, rope, "interpret", mesh, norm=(scale, 1e-6)))

    got = jax.jit(jax.value_and_grad(loss(mesh), argnums=(0, 1)))(x, scale)
    assert calls.value(kernel="rope") == before + 1
    want = jax.jit(jax.value_and_grad(loss(None), argnums=(0, 1)))(x, scale)
    assert calls.value(kernel="rope") == before + 1
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2e-5 * float(np.max(np.abs(w))))


# -- the softmax router --------------------------------------------------------


def test_softmax_router_normalises_the_chosen():
    from deeplearning_cfn_tpu.models.moe import SoftmaxTopKRouter

    rng = np.random.RandomState(1)
    m = rng.normal(0, 1, (32, 24)).astype(np.float32)
    router = SoftmaxTopKRouter(16, 4)
    params = router.init(jax.random.PRNGKey(0), m)
    kernel = np.asarray(params["params"]["kernel"], np.float64)
    chosen, weight, state = router.apply(params, m)
    assert state is None and chosen.shape == weight.shape == (32, 4)
    logits = m.astype(np.float64) @ kernel
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    best = np.argsort(-probs, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.asarray(chosen), best)
    top = np.take_along_axis(probs, best, axis=-1)
    np.testing.assert_allclose(np.asarray(weight),
                               top / top.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 1.0, rtol=1e-6)


# -- the exchange --------------------------------------------------------------

E, K, F, W = 16, 4, 24, 16


def _layer(mesh, implementation="ragged_dot"):
    from deeplearning_cfn_tpu.models.moe import HeldExpertsMlp, \
        SoftmaxTopKRouter

    return HeldExpertsMlp(num_experts=E, mlp_dim=W, dtype=jnp.float32,
                          router=SoftmaxTopKRouter(E, K), mesh=mesh,
                          implementation=implementation)


def _case(routing, width=F):
    rng = np.random.RandomState(3)
    x = rng.normal(0, 1, (4, 16, width)).astype(np.float32)
    params = jax.tree_util.tree_map(
        np.asarray, _layer(None).init(jax.random.PRNGKey(2), x)["params"])
    if routing == "skewed":
        # Every token's four choices are experts 8-11: the third of four
        # ranks takes every pair, twice over what the usual buffer holds.
        x = np.abs(x) + 0.1
        kernel = 1e-3 * params["router"]["kernel"]
        kernel[:, 8:12] += 1.0
        params["router"]["kernel"] = kernel
    return x, params


def _plain(params, x):
    """Every expert over every token, weighted by the router's choice."""
    width = x.shape[-1]
    m = x.reshape(-1, width).astype(np.float64)
    logits = m @ params["router"]["kernel"].astype(np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    best = np.argsort(-probs, axis=-1, kind="stable")[:, :K]
    top = np.take_along_axis(probs, best, axis=-1)
    top /= top.sum(-1, keepdims=True)
    w_in = params["experts_in"]["kernel"].reshape(E, width, 2 * W)
    w_out = params["experts_out"]["kernel"].reshape(E, W, width)
    out = np.zeros_like(m)
    for e in range(E):
        weight = np.where(best == e, top, 0.0).sum(-1)
        h = m @ w_in[e]
        gate, up = h[:, :W], h[:, W:]
        out += weight[:, None] * ((gate / (1 + np.exp(-gate)) * up)
                                  @ w_out[e])
    return out.reshape(x.shape), best


@pytest.mark.parametrize("axes,width,impl", [
    (dict(expert=4), F, "ragged_dot"),
    (dict(data=2, expert=2), F, "ragged_dot"),
    # The rows fetched by the row kernel (interpreted), a rank each.
    (dict(expert=4), 128, "interpret")],
    ids=["expert4", "data2_expert2", "expert4_row_kernel"])
@pytest.mark.parametrize("routing", ["seeded", "skewed"])
def test_exchange_gives_the_whole_layer_and_its_gradients(devices, routing,
                                                          axes, width, impl):
    """The layer over a mesh with an ``expert`` axis against the same
    parameters on one device and against every expert over every token:
    the result, and through the exchange's backward pass the gradient of
    every parameter and of the input. ``skewed`` sends every pair to one
    rank's experts: twice the usual buffer, so the second one takes the step
    window by window, and nothing is dropped."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    mesh = _mesh(devices, **axes)
    ranks = axes["expert"]
    x, params = _case(routing, width)
    registry = get_tracer().registry
    exchanges = registry.counter("moe.exchange.calls")
    wrapped = registry.counter("parallel.shard_map.calls")
    label = dict(path="all_gather", ranks=str(ranks))
    before = exchanges.value(**label), wrapped.value(kernel="gmm")

    def run(layer):
        def loss(p, x):
            y, aux = layer.apply({"params": p}, x)
            return jnp.sum(jnp.square(y)), (y, aux)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(params, x)

    (_, (y4, aux4)), grads4 = run(_layer(mesh, impl))
    assert exchanges.value(**label) == before[0] + 1
    assert wrapped.value(kernel="gmm") == before[1] + 1
    # A rank sends its 64 / ways tokens' rows in float32 and takes the
    # other ranks' float32 parts, forward, and the transposes backward.
    ways = int(np.prod(list(axes.values())))
    assert registry.gauge("moe.exchange.bytes").value() \
        == 2 * (ranks - 1) * (64 // ways) * width * (4 + 4)
    (_, (y1, aux1)), grads1 = run(_layer(None))
    want, best = _plain(params, x)
    np.testing.assert_allclose(y4, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y4, y1, rtol=2e-5, atol=2e-5)
    for got, one in zip(jax.tree_util.tree_leaves(grads4),
                        jax.tree_util.tree_leaves(grads1)):
        scale = max(float(jnp.max(jnp.abs(one))), 1e-6)
        np.testing.assert_allclose(got, one, rtol=0, atol=2e-5 * scale)
    # Every pair lands on a rank of the mesh: nothing is left out.
    pairs = 64 * K
    groups = ways // ranks          # data-parallel groups, each 64 / groups
    assert float(aux1["rows_held"]) == pairs
    assert float(aux4["rows_held"]) * ranks == pairs / groups
    assert set(aux4) == set(aux1) | {"rank_load_max_over_mean"}
    if routing == "skewed":
        assert set(np.unique(best)) == {8, 9, 10, 11}
        assert float(aux4["rank_load_max_over_mean"]) == pytest.approx(ranks)
    else:
        assert 1.0 <= float(aux4["rank_load_max_over_mean"]) < ranks


@pytest.mark.parametrize("width,path", [(F, ("gather", "gather")),
                                        (128, ("interpret", "interpret"))])
def test_windows_of_the_second_buffer_add_up(width, path):
    """``_in_passes`` alone: three windows of 40 rows over 100 sorted pairs
    (the last one padded) give what one buffer of every pair gives, by XLA's
    gathers and by the row kernel, in value and in every gradient (a
    window's cotangent of the weights goes back through the window's own
    places)."""
    import functools

    from deeplearning_cfn_tpu.models.moe import _held_rows, _in_passes, \
        inverse_permutation

    rng = np.random.RandomState(5)
    tokens, top_k, count, e = 25, 4, 3, 5
    m = jnp.asarray(rng.normal(0, 1, (tokens, width)), jnp.float32)
    chosen = jnp.asarray(rng.randint(0, e, (tokens, top_k)))
    weight = jnp.asarray(rng.uniform(0.1, 1, (tokens * top_k,)), jnp.float32)
    w_in = jnp.asarray(rng.normal(0, 0.3, (count, width, 2 * W)), jnp.float32)
    w_out = jnp.asarray(rng.normal(0, 0.3, (count, W, width)), jnp.float32)
    group = jnp.minimum(chosen.reshape(-1), count)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    sizes = jnp.sum(group[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    part = lambda rows, path: functools.partial(
        _held_rows, rows=rows, top_k=top_k, implementation="ragged_dot",
        path=path, out_dtype=jnp.float32)
    fixed = (order, inverse_permutation(order), sizes, jnp.sum(sizes))
    assert int(jnp.sum(sizes)) > 40

    def loss(fn):
        return lambda m, weight, w_in, w_out: jnp.sum(jnp.square(
            fn(m, weight, *fixed, w_in, w_out)))

    moved = (m, weight, w_in, w_out)
    want = jax.value_and_grad(
        loss(part(tokens * top_k, ("gather", "gather"))),
        argnums=(0, 1, 2, 3))(*moved)
    got = jax.value_and_grad(loss(functools.partial(
        _in_passes, part(40, path), 40)), argnums=(0, 1, 2, 3))(*moved)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-6)
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5 * scale)


# -- the whole step ------------------------------------------------------------


def _mellum_tiny_steps(devices, expert, impl):
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.state import create_train_state
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = get_preset("mellum2_12b_lm")
    apply_overrides(cfg, [
        "model.name=gpt_mellum2_tiny", "model.kwargs.layers_held=[0,1,2,3]",
        f"model.kwargs.attention_impl={impl}", "train.dtype=float32",
        "train.global_batch=4", "data.seq_len=32", "data.vocab_size=96",
        "mesh.data=1", f"mesh.expert={expert}",
        "schedule.warmup_steps=2"])
    mesh = _mesh(devices, expert=expert)
    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer, build_schedule(
        cfg.schedule, cfg.train.steps, cfg.train.global_batch, None))
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    rng = np.random.RandomState(0)
    batch = {"tokens": rng.randint(4, 96, (4, 33)).astype(np.int32),
             "loss_mask": np.ones((4, 32), np.float32)}
    records = []
    for _ in range(3):
        state, metrics = trainer.train_step(
            state, trainer.device_batch(batch), jax.random.PRNGKey(1))
        records.append({k: float(v) for k, v in metrics.items()})
    return state, records


@pytest.mark.parametrize("impl", ["reference", "interpret"])
def test_step_on_four_ranks_is_the_step_on_one_device(devices, impl):
    """Mellum2's block at a tiny size, three AdamW steps through the
    trainer's own compiled step: on ``expert=4`` (the stacks sharded 4 of 16
    experts a rank, the exchange run, with ``interpret`` the flash kernels
    under their ``shard_map``) and on one device holding everything. The
    losses, the gradient's norm and every parameter afterwards agree to
    float32's rounding of sums taken in another order."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from jax.sharding import PartitionSpec as P

    registry = get_tracer().registry
    wrapped = registry.counter("parallel.shard_map.calls")
    before = {k: wrapped.value(kernel=k) for k in ("flash", "rope", "gmm")}
    four, records4 = _mellum_tiny_steps(devices, 4, impl)
    made = {k: wrapped.value(kernel=k) - n for k, n in before.items()}
    # Four layers, one trace of the step; a head of 16 is no lane tile, so
    # the rotary kernel is not taken here (tests/test_chip_compile_laguna_zaya1.py
    # has it at the real size).
    assert made == {"flash": 4 if impl == "interpret" else 0, "rope": 0,
                    "gmm": 4}
    one, records1 = _mellum_tiny_steps(devices, 1, impl)
    stack = four.params["layer_2"]["mlp"]["experts_in"]["kernel"]
    assert stack.sharding.spec == P("expert", None)
    assert stack.addressable_shards[0].data.shape == (4 * 64, 64)
    assert four.params["layer_2"]["mlp"]["router"]["kernel"].sharding \
        .is_fully_replicated
    for r4, r1 in zip(records4, records1):
        assert r4["loss"] == pytest.approx(r1["loss"], rel=2e-6)
        assert r4["grad_norm"] == pytest.approx(r1["grad_norm"], rel=2e-5)
        # 4 x 32 tokens, 4 choices, 4 layers: every pair on some rank.
        assert r1["moe_rows_held"] == 4 * 32 * 4 * 4
        assert r4["moe_rows_held"] * 4 == r1["moe_rows_held"]
        assert "moe_rank_load_max_over_mean" in r4 \
            and "moe_rank_load_max_over_mean" not in r1
    assert records1[2]["loss"] < records1[0]["loss"]
    worst = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), one.params,
        jax.device_get(four.params))))
    assert worst < 1e-5, worst
