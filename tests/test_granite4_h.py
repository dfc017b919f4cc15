"""granite-4.0-h-micro on the normal path, at a size a test run can hold:
the tiny twin through ``build_task`` and ``Trainer.fit``; the program against
the benchmark's plain reference (``benchmark/references/granite4_h_micro.py``:
the recurrence a token at a time) on weights seeded as the benchmark seeds
them; blocks recomputed against blocks kept; the published widths."""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import build_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

# What ``gpt_granite4_h_tiny`` (models/lm.py) is, in the source's keys: hidden
# 64, four layers (Mamba, attention, Mamba, Mamba), 4 mixer heads of 32 with a
# state of 16 in chunks of 8, 4 query heads over 2 K/V heads of 16.
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.0625, "shared_intermediate_size": 128,
    "intermediate_size": 128, "mamba_n_heads": 4, "mamba_d_head": 32,
    "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "num_hidden_layers": 4, "vocab_size": 96,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "layers_held": [0, 1, 2, 3],
}
SEED = 2 ** 31 + 41


@pytest.fixture(scope="module")
def bench():
    """The benchmark's harness and the configuration's reference, imported
    as ``run.py`` imports them."""
    sys.path.insert(0, BENCH)
    try:
        from harness import compare, manifest, train_steps, weights
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "granite4_h_micro.json")) as fh:
        sizes = dict(json.load(fh), **TINY)
    reference = manifest.load_module(
        "benchmark/references/granite4_h_micro.py", "ref_granite4_h_micro")
    return types.SimpleNamespace(
        compare=compare, train_steps=train_steps, weights=weights,
        sizes=sizes, reference=reference)


def _tiny_cfg(**train):
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset

    cfg = get_preset("granite4_h_micro_lm")
    apply_overrides(cfg, [
        "model.name=gpt_granite4_h_tiny", "model.kwargs.layers_held=[0,1,2,3]",
        "train.dtype=float32", "train.global_batch=4", "data.seq_len=32",
        "data.vocab_size=96", "mesh.data=1", "data.synthetic=true",
        "data.use_native_loader=false", "checkpoint.every_steps=0",
        "eval.enabled=false", "train.log_every_steps=1"]
        + [f"train.{k}={v}" for k, v in train.items()])
    return cfg


@pytest.fixture(scope="module")
def program(bench):
    """The task as the preset builds it (blocks recomputed), seeded weights
    as the benchmark makes them, three batches."""
    from deeplearning_cfn_tpu.train.task import build_task

    task = build_task(_tiny_cfg())
    w = bench.weights
    shapes = jax.eval_shape(task.init, w.seed_key(SEED))["params"]
    params = jax.jit(lambda key: w.make(shapes, key))(w.seed_key(SEED))
    tokens = bench.train_steps.make_tokens(
        SEED, {"num_examples": 12, "repeat_min": 0.0, "repeat_max": 0.9},
        32, 96)
    return task, params, [tokens[i * 4:(i + 1) * 4] for i in range(3)]


# -- the program against the reference --------------------------------------

# Both sides are float32 on the CPU; they differ in the order of their sums
# (the chunked scan's exponential of a running sum against a running product
# of decays; one einsum against a head at a time). 1e-5 of a tensor's largest
# entry is a few float32 roundings of sums this long.
TOL = 1e-5


def _close(got, want, what, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0), \
        (what, np.max(np.abs(got - want)), np.max(np.abs(want)))


def test_the_seeded_mixer_leaves_are_kinds_the_harness_knows(program):
    """``harness/weights.py`` seeds by a leaf's last name and knows ``bias``,
    ``scale``, ``embedding`` and a 2-D ``kernel``: every leaf is one, and
    what the seed gives the mixer leaves its scan something to do."""
    _, params, _ = program
    mixer = params["layer_0"]["self_attn"]
    assert {k: sorted(v) for k, v in mixer.items()} == {
        "in_proj": ["kernel"], "conv": ["bias", "kernel"],
        "a_log": ["bias"], "dt_bias": ["bias"], "d_skip": ["scale"],
        "gate_norm": ["scale"], "out_proj": ["kernel"]}
    assert not np.any(np.asarray(mixer["a_log"]["bias"]))
    assert np.all(np.asarray(mixer["d_skip"]["scale"]) == 1)
    assert "query" in params["layer_1"]["self_attn"]


def test_constants_a_head_are_the_references(bench):
    """The program's grid over the heads and its taps' gain, and the
    reference's, computed apart from the configuration's file."""
    from deeplearning_cfn_tpu.models.ssm import conv_gain, head_constants

    with open(os.path.join(BENCH, "configs", "granite4_h_micro.json")) as fh:
        published = json.load(fh)
    for sizes in (bench.sizes, published):
        a, c, gain = bench.reference.seeded_constants(sizes)
        want_a, want_c = head_constants(sizes["mamba_n_heads"])
        np.testing.assert_allclose(np.asarray(a), want_a, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(c), want_c, rtol=1e-6)
        channels = sizes["mamba_n_heads"] * sizes["mamba_d_head"] \
            + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
        assert gain == pytest.approx(conv_gain(sizes["mamba_d_conv"],
                                               channels))


def test_program_matches_reference(bench, program):
    task, params, batches = program
    ref, sizes, tokens = bench.reference, bench.sizes, batches[0]
    logits = jax.jit(lambda p, ids: task.model.apply({"params": p}, ids))(
        params, tokens[:, :-1])
    _close(logits, jax.jit(lambda p, ids: ref.logits_fn(p, ids, sizes))(
        params, tokens[:, :-1]), "logits")
    batch = {"tokens": jnp.asarray(tokens),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: task.loss_fn(p, {}, batch, None, True), has_aux=True))(
            params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss_fn(p, t, sizes)))(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= TOL * float(want_loss)
    got, want = bench.weights.flat(grads), bench.weights.flat(want)
    # 3 Mamba layers of 12 leaves, the attention layer's 8, the embedding,
    # the final norm.
    assert set(got) == set(want) and len(got) == 3 * 12 + 8 + 2
    for name in want:
        assert np.any(np.asarray(want[name])), name
        _close(got[name], want[name], name)


def test_three_adamw_steps_match_the_reference(bench, program):
    """The program's optimizer (optax, as ``Trainer`` composes it) and the
    reference's, three steps from the same weights over the same batches:
    every loss, the first gradient's norms, every leaf's change."""
    import optax

    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule

    task, params, batches = program
    cfg, compare = task.cfg, bench.compare
    tx = build_optimizer(cfg.optimizer, build_schedule(
        cfg.schedule, cfg.train.steps, cfg.train.global_batch, None))

    @jax.jit
    def step(p, opt, toks):
        batch = {"tokens": toks, "loss_mask": jnp.ones((4, 32), jnp.float32)}
        (loss, _), grads = jax.value_and_grad(
            lambda q: task.loss_fn(q, {}, batch, None, True),
            has_aux=True)(p)
        updates, opt = tx.update(grads, opt, p)
        return optax.apply_updates(p, updates), opt, loss

    p, opt, losses = params, tx.init(params), []
    for toks in batches:
        p, opt, loss = step(p, opt, jnp.asarray(toks))
        losses.append(float(loss))
    moved = bench.weights.flat(jax.tree_util.tree_map(
        lambda new, old: float(jnp.sqrt(jnp.sum(jnp.square(new - old)))),
        p, params))
    with open(os.path.join(BENCH, "configs", "granite4_h_micro.json")) as fh:
        hp = json.load(fh)["optimizer"]
    want = bench.reference.train_steps(
        jax.tree_util.tree_map(lambda a: a + 0, params), batches,
        bench.sizes, hp)
    for got_loss, want_loss in zip(losses, want["loss"]):
        assert abs(got_loss - want_loss) <= TOL * want_loss
    # Adam's first steps are +-lr whatever the gradient's size, so a leaf's
    # change is its size times the rate: 1e-3 of it is float32's sign noise
    # on the all-but-zero gradients, as in ``compare.driven_leaves``.
    # (At this size a head's decay offsets have all but no gradient.)
    driven = compare.driven_leaves(want)
    assert len(want["grad_norms"]) - 3 <= len(driven)
    gap, leaf = compare.norm_gap({k: moved[k] for k in driven},
                                 {k: want["change_norms"][k] for k in driven})
    assert gap <= 1e-3, (gap, leaf)


@pytest.mark.parametrize("fault,least", [
    (dict(carry_state=False), 1e-4), (dict(decay=False), 1e-3),
    (dict(drop_tap=0), 1e-2), (dict(residual_multiplier=1.0), 1e-1),
    (dict(attention_multiplier=0.125), 1e-4),
    (dict(gate_after_norm=True), 1e-2)])
def test_a_fault_of_the_reference_moves_its_logits(bench, program, fault,
                                                   least):
    """Each control of ``benchmark/calibrate_granite4_h_micro.py`` is another
    function: it moves the logits by far more than the float32 noise the
    tests above allow. (What each reads at the cell's size against the
    cell's limits is the chip's to say: PERF.md section 2.)"""
    _, params, batches = program
    ref, sizes, ids = bench.reference, bench.sizes, batches[0][:, :-1]
    logits = lambda **kw: np.asarray(jax.jit(
        lambda p: ref.logits_fn(p, ids, sizes, **kw))(params))
    sound, faulty = logits(), logits(**fault)
    moved = np.max(np.abs(faulty - sound)) / np.max(np.abs(sound))
    assert moved > least, moved


# -- the normal path ---------------------------------------------------------


def test_tiny_preset_trains_through_trainer_fit(devices):
    """``get_preset`` -> ``build_task`` -> ``create_train_state`` ->
    ``Trainer.fit``, nothing of the model's in ``train/``: three steps on one
    repeated batch, finite, and the loss falls."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.parallel import build_mesh
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    registry = get_tracer().registry
    scans = registry.counter("ssm.scan.calls")
    recomputed = registry.counter("model.blocks.recomputed")
    before = (scans.value(path="xla", chunk="8"), recomputed.value())
    cfg = _tiny_cfg(steps=3)
    cfg.schedule.warmup_steps, cfg.schedule.base_lr = 0, 1e-2
    mesh = build_mesh(cfg.mesh, devices=devices[:1])
    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer, build_schedule(cfg.schedule, 3, 4, 4))
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
    tokens = np.random.default_rng(0).integers(0, 96, (4, 33), np.int32)
    batch = {"tokens": tokens, "loss_mask": np.ones((4, 32), np.float32)}
    rows = []
    writer = types.SimpleNamespace(write=lambda r: rows.append(dict(r)))
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    state = trainer.fit(state, iter([batch] * 3), num_steps=3,
                        rng=jax.random.PRNGKey(1), log_every=1,
                        metrics_writer=writer)
    losses = [r["loss"] for r in rows if "loss" in r]
    assert len(losses) == 3 and np.all(np.isfinite(losses))
    assert losses[2] < losses[0]
    # Three traces (the parameters' shapes, their values, the step), three
    # mixers and four recomputed blocks each; the backward pass traces
    # nothing again.
    assert scans.value(path="xla", chunk="8") - before[0] == 3 * 3
    assert recomputed.value() - before[1] == 3 * 4


def test_recomputed_blocks_give_the_gradients_of_kept_blocks_bit_for_bit():
    """``BlockStyle.remat`` changes what is kept, not what is computed.
    Operation by operation (no ``jit``: under it XLA fuses the two programs
    differently and a sum's order moves by an ulp) the float32 gradients are
    the same bits."""
    ids = jnp.arange(16).reshape(1, 16) % 96
    grads = {}
    for remat in (False, True):
        model = build_model("gpt_granite4_h_tiny", 0, jnp.float32,
                            layers_held=(0, 1), remat_blocks=remat)
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        grads[remat] = jax.grad(lambda p: jnp.mean(jnp.square(
            model.apply({"params": p}, ids))))(params)
    kept, again = (jax.tree_util.tree_leaves(grads[r]) for r in (False, True))
    assert len(kept) == len(again) == 12 + 8 + 2
    assert all(np.any(np.asarray(a)) and np.array_equal(a, b)
               for a, b in zip(kept, again))


def _kernel_calls(jaxpr, name):
    """How many equations of ``jaxpr``, nested ones counted where they stand,
    call the Pallas kernel ``name``."""
    calls = 0
    for eqn in jaxpr.eqns:
        calls += eqn.primitive.name == "pallas_call" \
            and eqn.params["name"] == name
        calls += sum(_kernel_calls(inner, name)
                     for inner in jax.core.jaxprs_in_params(eqn.params))
    return calls


@pytest.mark.parametrize("mask", ["causal", "window", "block_diffusion"])
def test_a_recomputed_block_keeps_its_flash_forward(mask, monkeypatch):
    """Two attention blocks on the kernel path (interpret mode), under each
    of the kernels' three static masks. The gradient's program runs the
    forward kernel once a block where the blocks are kept, once where they
    are recomputed (``models/lm.py``'s policy keeps the kernel's output and
    row statistics by their names) and twice with the policy taken away; the
    backward kernels once each whichever way. What is kept changes no bit of
    a gradient, and the registry says how many blocks kept how much."""
    from deeplearning_cfn_tpu.models.lm import TransformerCausalLm
    from deeplearning_cfn_tpu.models.transformer import BlockStyle
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.ops.attention import BlockDiffusion

    length, heads, head_dim = 16, 4, 8
    layout = BlockDiffusion(length, 4) if mask == "block_diffusion" else None
    positions = 2 * length if layout else length
    ids = (7 * jnp.arange(positions)).reshape(1, positions) % 96

    def build(remat, impl="interpret"):
        style = BlockStyle(num_kv_heads=2, remat=remat,
                           window=8 if mask == "window" else 0)
        return TransformerCausalLm(
            vocab_size=96, hidden_size=heads * head_dim, dtype=jnp.float32,
            attention_impl=impl,
            blocks=tuple((i, heads, 64, style) for i in range(2)))

    def grad_of(model):
        return jax.grad(lambda p: jnp.mean(jnp.square(
            model.apply({"params": p}, ids, layout=layout))))

    registry = get_tracer().registry
    kept = registry.counter("model.blocks.kept_flash")
    kept_bytes = registry.gauge("model.blocks.kept_bytes")
    shapes = jax.eval_shape(build(False, "reference").init,
                            jax.random.PRNGKey(0), ids[:, :length])["params"]
    rng = np.random.default_rng(0)
    params = jax.tree_util.tree_map(
        lambda s: jnp.asarray(rng.normal(0, 0.3, s.shape), s.dtype), shapes)
    before = kept.value()
    programs = {remat: jax.make_jaxpr(grad_of(build(remat)))(params)
                for remat in (False, True)}
    # One trace with recomputed blocks, two blocks; none where they are kept.
    assert kept.value() - before == 2
    assert kept_bytes.value() == 2 * positions * heads * (head_dim * 4 + 4)
    # Off the kernel path a recomputed block names nothing to keep.
    jax.eval_shape(lambda p: build(True, "reference").apply(
        {"params": p}, ids, layout=layout), params)
    assert kept.value() - before == 2 and kept_bytes.value() == 0
    with monkeypatch.context() as patch:
        patch.setattr(jax.checkpoint_policies, "save_only_these_names",
                      lambda *names: None)
        programs["no policy"] = jax.make_jaxpr(grad_of(build(True)))(params)
    for kernel, calls in (("flash_fwd", (2, 2, 4)), ("flash_bwd_dq", (2,) * 3),
                          ("flash_bwd_dkdv", (2,) * 3)):
        assert tuple(_kernel_calls(programs[how].jaxpr, kernel)
                     for how in (False, True, "no policy")) == calls, kernel
    leaves = jax.tree_util.tree_leaves(params)
    stayed, again = (jax.core.eval_jaxpr(programs[remat].jaxpr,
                                         programs[remat].consts, *leaves)
                     for remat in (False, True))
    assert len(stayed) == len(again) == len(leaves)
    assert all(np.any(np.asarray(a)) and np.array_equal(a, b)
               for a, b in zip(stayed, again))


def test_a_recomputed_expert_block_is_refused():
    """Where its router hands a state to the next block's (an
    ``MlpStateRouter``); a router that keeps none is recomputed with its
    block since PR 43 (``tests/test_sdar.py``)."""
    from deeplearning_cfn_tpu.models.lm import TransformerCausalLm
    from deeplearning_cfn_tpu.models.transformer import BlockStyle

    model = TransformerCausalLm(vocab_size=96, hidden_size=64, blocks=(
        (0, 4, 64, BlockStyle(mlp="experts", remat=True,
                              router=(("kind", "mlp_state"),))),))
    with pytest.raises(NotImplementedError, match="router state"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_an_unknown_mixer_is_refused():
    from deeplearning_cfn_tpu.models.transformer import BlockStyle, \
        TransformerLayer

    layer = TransformerLayer(4, 64, style=BlockStyle(mixer="rwkv"))
    with pytest.raises(ValueError, match="unknown BlockStyle.mixer"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 64)))


# -- the published widths ----------------------------------------------------


def test_preset_is_the_chips_share_at_published_widths():
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train.task import build_task

    cfg = get_preset("granite4_h_micro_lm")
    assert (cfg.data.seq_len, cfg.train.global_batch,
            cfg.data.vocab_size) == (8192, 1, 12_544)
    assert cfg.model.kwargs["remat_blocks"]
    task = build_task(cfg)
    shapes = jax.eval_shape(task.init, jax.random.PRNGKey(0))["params"]
    count = lambda tree: sum(int(np.prod(s.shape))
                             for s in jax.tree_util.tree_leaves(tree))
    # The issue's table: a Mamba layer, the attention layer, what is held.
    assert count(shapes["layer_0"]["self_attn"]) == 25_847_232
    assert count(shapes["layer_0"]) == 76_182_976
    assert count(shapes["layer_5"]) == 60_821_504
    assert count(shapes) == 772_160_448
    assert sorted(shapes) == sorted(
        [f"layer_{i}" for i in range(10)] + ["token", "final_norm"])
    assert shapes["token"]["embedding"].shape == (12_544, 2048)
    mixer = shapes["layer_9"]["self_attn"]
    assert mixer["in_proj"]["kernel"].shape == (2048, 4096 + 4352 + 64)
    assert mixer["conv"]["kernel"].shape == (4, 4352)
    assert mixer["out_proj"]["kernel"].shape == (4096, 2048)
    attention = shapes["layer_5"]["self_attn"]
    assert attention["query"]["kernel"].shape == (2048, 2048)
    assert attention["key"]["kernel"].shape == (2048, 512)
    assert "in_proj" not in attention and "query" not in mixer
    # The configuration's file states the same count.
    with open(os.path.join(BENCH, "configs", "granite4_h_micro.json")) as fh:
        assert "772,160,448 parameters" in json.load(fh)["deployment"]


# -- the styled models that were there ---------------------------------------
# (their parameter trees, leaf for leaf: ``tests/test_lm.py``'s recorded
# digests, which PR 41 extended to ZAYA's and Mellum2's)


def test_default_style_fields_are_todays_block():
    from deeplearning_cfn_tpu.models.transformer import BlockStyle

    st = BlockStyle()
    assert (st.mixer, st.ssm, st.residual_multiplier, st.attn_scale,
            st.remat) == ("attention", (), 1.0, 0.0, False)
