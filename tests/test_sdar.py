"""SDAR-30B-A3B-Chat's block-diffusion training on the normal path, at a size
a test run can hold: the third static mask through the flash kernels
(interpret mode) and the XLA path against an explicit mask built from the
definition; the tiny twin through ``build_task`` against the benchmark's plain
reference (``benchmark/references/sdar_30b_a3b.py``) on weights seeded as the
benchmark seeds them, the noise redrawn by the reference; the shares of eight
chips adding up to the uncut layer; the noise; the published widths."""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops import attention as A

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


# -- the mask ----------------------------------------------------------------

def explicit_mask(length, block):
    """``[2 L, 2 L]`` booleans, pair by pair from ``B(i) = i // block``."""
    blk = lambda i: (i % length) // block
    seen = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            if i < length and j < length:
                seen[i, j] = blk(j) == blk(i)
            elif i < length:
                seen[i, j] = blk(j) < blk(i)
            elif j >= length:
                seen[i, j] = blk(j) <= blk(i)
    return seen


def _plain(q, k, v, seen):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen, scores, -1e30), axis=-1), v)


# (L, block, forced plan): L a multiple of the grid tile and not (each copy
# is then padded to whole tiles), blocks of 4 and of 32, sub-tiles smaller
# than a block, a tile that is one block (the staircase's diagonal is dead).
LAYOUTS = [(64, 4, (32, 32, 16, 16)), (40, 4, (16, 16, 8, 8)),
           (64, 32, (32, 32, 8, 8)), (96, 32, (32, 32, 16, 16))]


@pytest.mark.parametrize("length,block,plan", LAYOUTS)
def test_flash_kernels_compute_the_layouts_mask(length, block, plan):
    """Forward and the three gradients of the kernels (interpret mode, a
    forced plan) and of the XLA path against the explicit mask; two query
    heads to a K/V head."""
    layout = A.BlockDiffusion(length, block)
    seen = explicit_mask(length, block)
    assert np.array_equal(np.asarray(layout.mask()), seen)
    assert seen.sum() == length * length + length * block
    keys = jax.random.split(jax.random.PRNGKey(length + block), 4)
    q, w = (jax.random.normal(key, (1, 2, 2 * length, 16))
            for key in keys[:2])
    k, v = (jax.random.normal(key, (1, 1, 2 * length, 16))
            for key in keys[2:])
    want = _plain(q, k, v, seen)
    want_grads = jax.grad(lambda *qkv: jnp.sum(_plain(*qkv, seen) * w),
                          (0, 1, 2))(q, k, v)

    out, lse = A._flash_forward(q, k, v, None, False, 0.25, interpret=True,
                                return_stats=True, plan=plan, layout=layout)
    grads = A._flash_backward(q, k, v, out, lse, w, False, 0.25, True,
                              plan=plan, layout=layout)
    xla = jax.value_and_grad(lambda *qkv: jnp.sum(A.fused_attention(
        *qkv, layout=layout, implementation="reference") * w), (0, 1, 2))
    for got, ref in zip((out, *grads), (want, *want_grads)):
        np.testing.assert_allclose(got, ref, atol=2e-5)
    for got, ref in zip(xla(q, k, v)[1], want_grads):
        np.testing.assert_allclose(got, ref, atol=2e-5)


@pytest.mark.parametrize("length,block,plan", LAYOUTS + [
    (8192, 4, None), (8192, 32, None)])
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkdv",
                                    "flash_bwd_dq"])
def test_subtile_counts_are_the_explicit_masks(length, block, plan, kernel):
    """``_subtile_counts`` from the very schedule and walk a kernel runs:
    the sub-tiles with a live pair, and those of them with a dead one,
    counted from the definition over the padded square; no dead grid step
    copies a block."""
    layout = A.BlockDiffusion(length, block)
    backward, by_columns = kernel != "flash_fwd", kernel == "flash_bwd_dkdv"
    plan = plan or A._tile_plan(0, 0, 128, False, backward, layout=layout)
    padded = -(-length // plan[0]) * plan[0]
    cases = A._schedule(2 * padded, 2 * padded, plan, False,
                        by_columns=by_columns, layout=layout)
    walk = A._walk(2 * padded, 2 * padded, plan, False, 0, by_columns,
                   layout=layout)
    at = np.arange(2 * padded)
    clean, blk = at >= padded, (at % padded) // block
    # A sub-tile's rows and columns by their first and last block, quadrant
    # by quadrant, without the pairs (8192: 268 M of them).
    sub_q, sub_k = plan[2:]
    total = live = masked = 0
    for r in range(0, 2 * padded, sub_q):
        for c in range(0, 2 * padded, sub_k):
            total += 1
            if clean[r] and not clean[c]:
                continue
            q0, q1, k0, k1 = blk[r], blk[r + sub_q - 1], blk[c], \
                blk[c + sub_k - 1]
            if not clean[c]:
                some, every = k0 <= q1 and q0 <= k1, q0 == q1 == k0 == k1
            else:
                reach = int(clean[r])
                some, every = k0 < q1 + reach, k1 < q0 + reach
            live += some
            masked += some and not every
    assert A._subtile_counts(2 * padded, 2 * padded, plan, cases, walk) == (
        total, live, masked)
    A._record_grid(kernel, walk, cases, 8)
    steps, dead, copies = A._grid_gauges(kernel, layout=layout)
    assert copies == 0 and 0 <= dead < steps
    if length == 8192:
        assert 0.25 < live / total < 0.27


def test_subtile_rule_agrees_with_the_pairs():
    """The quadrant rule the count above goes by, against the pairs
    themselves at a size that holds them."""
    length, block, sub = 64, 4, 8
    seen = explicit_mask(length, block)
    tiles = seen.reshape(2 * length // sub, sub, 2 * length // sub, sub)
    for edge, rows, cols in ((A._SAME_BLOCK, 0, 0),
                             (A._EARLIER_BLOCKS, 0, length),
                             (A._BLOCKS_UP_TO, length, length)):
        for r in range(0, length, sub):
            for c in range(0, length, sub):
                tile = tiles[(rows + r) // sub, :, (cols + c) // sub, :]
                kind = A._subtile_kind(r, r + sub, c, c + sub, 0, edge, block)
                assert (kind is None) == (not tile.any())
                assert (kind == 0) == bool(tile.all())


def test_layout_is_exclusive_and_checked():
    q = jnp.zeros((1, 2, 16, 8))
    layout = A.BlockDiffusion(8, 4)
    for kw in (dict(causal=True), dict(window=4, causal=True),
               dict(bias=jnp.zeros((1, 1, 16, 16)))):
        with pytest.raises(ValueError, match="whole mask"):
            A.fused_attention(q, q, q, layout=layout, **kw)
    with pytest.raises(ValueError, match="whole number of blocks"):
        A.BlockDiffusion(10, 4)
    with pytest.raises(ValueError, match="power of two"):
        layout = A.BlockDiffusion(24, 12)
        A._check_layout_plan(layout, A._tile_plan(0, 0, 128, False,
                                                  layout=layout))


def test_causal_and_window_calls_keep_their_labels():
    """``_labels`` goes by the mask's name: none for a causal call."""
    assert A._labels("flash_fwd", "") == {"kernel": "flash_fwd"}
    assert A._labels("flash_fwd", A._mask_name(512)) == {
        "kernel": "flash_fwd", "mask": "window"}
    assert A._mask_name(0, A.BlockDiffusion(8, 4)) == "block_diffusion"


# The flash calls of the five cells that were there before the layout, by
# shape (batch cut to the least that keeps the grid's form): their traced
# text, forward and backward kernels with their index maps, is what it was
# at PR 42 (sha256 of the jaxpr's first 16 hex digits, read there).
PARENT_CALLS = [
    ("gpt2_small", (2, 12, 1024, 64), 12, 1024, True, 0, "13b0add32f64620d"),
    ("laguna_full", (1, 48, 4096, 128), 8, 4096, True, 0,
     "e724595d9db8fc0c"),
    ("laguna_window", (1, 64, 4096, 128), 8, 4096, True, 512,
     "e773cef9080958b8"),
    ("mellum2_window", (1, 32, 8192, 128), 4, 8192, True, 1024,
     "fc8d722b716e2984"),
    ("mellum2_full", (1, 32, 8192, 128), 4, 8192, True, 0,
     "281e45890fd05438"),
    ("granite4h", (1, 32, 8192, 64), 8, 8192, True, 0, "2a033189b962a533"),
    ("zaya1", (2, 8, 4096, 128), 2, 4096, True, 0, "48fe19397b05f1df"),
    ("not_causal_padded", (2, 4, 1500, 64), 4, 1500, False, 0,
     "26b88830b3a87e67"),
    ("causal_sq_lt_sk", (1, 4, 512, 64), 4, 2048, True, 0,
     "a269f49a1781137e"),
]


@pytest.mark.parametrize("name,shape,kv_heads,sk,causal,window,digest",
                         PARENT_CALLS, ids=[c[0] for c in PARENT_CALLS])
def test_the_other_cells_flash_calls_are_traced_as_before(
        name, shape, kv_heads, sk, causal, window, digest, monkeypatch):
    import hashlib

    # The two names a recomputed block's policy reads (PR 44) are identity
    # equations after the forward kernel; the text is read without them.
    monkeypatch.setattr(A, "checkpoint_name", lambda x, name: x)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((shape[0], kv_heads, sk, shape[3]),
                              jnp.bfloat16)
    step = jax.value_and_grad(lambda *qkv: jnp.sum(A.fused_attention(
        *qkv, causal=causal, window=window,
        implementation="pallas").astype(jnp.float32)), (0, 1, 2))
    text = str(jax.make_jaxpr(step)(q, kv, kv))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# -- the model against the reference ------------------------------------------

# What ``gpt_sdar_tiny`` (models/lm.py) is, in the source's keys.
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "vocab_size": 96,
    "layers_held": [0, 1], "experts_held": [0, 8],
    "published": {"num_experts": 8},
}
SEED = 2 ** 31 + 43
LENGTH, BLOCK = 64, 4


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        from harness import manifest, train_steps, weights
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "sdar_30b_a3b.json")) as fh:
        published = json.load(fh)
    reference = manifest.load_module(
        "benchmark/references/sdar_30b_a3b.py", "ref_sdar_30b_a3b")
    return types.SimpleNamespace(
        train_steps=train_steps, weights=weights, reference=reference,
        published=published, sizes=dict(published, **TINY))


def _tiny_cfg(*more):
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset

    cfg = get_preset("sdar_30b_a3b_lm")
    cfg.model.kwargs = dict(remat_blocks=True)
    apply_overrides(cfg, [
        "model.name=gpt_sdar_tiny", "train.dtype=float32",
        "train.global_batch=2", f"data.seq_len={LENGTH}",
        "data.vocab_size=96", "mesh.data=1", "data.synthetic=true",
        "data.use_native_loader=false", "checkpoint.every_steps=0",
        "eval.enabled=false", "train.log_every_steps=1", *more])
    return cfg


@pytest.fixture(scope="module")
def program(bench):
    from deeplearning_cfn_tpu.train.task import build_task

    task = build_task(_tiny_cfg())
    w = bench.weights
    shapes = jax.eval_shape(task.init, w.seed_key(SEED))["params"]
    params = jax.jit(lambda key: w.make(shapes, key))(w.seed_key(SEED))
    tokens = bench.train_steps.make_tokens(
        SEED, {"num_examples": 2, "repeat_min": 0.0, "repeat_max": 0.9},
        LENGTH, 96)
    batch = {"tokens": jnp.asarray(tokens),
             "loss_mask": jnp.ones((2, LENGTH), jnp.float32)}
    return task, params, batch


def _close(got, want, what, tol=1e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0), \
        (what, np.max(np.abs(got - want)), np.max(np.abs(want)))


def test_the_plain_causal_call_is_the_references(bench, program):
    """The same model called without a layout: q/k norm, the rotary turn,
    the router and the held experts against the reference's causal call."""
    task, params, batch = program
    ids = batch["tokens"][:, :LENGTH]
    logits, _ = task.model.apply({"params": params}, ids)
    _close(logits, bench.reference.logits_fn(params, ids, bench.sizes),
           "causal logits")


def test_the_block_diffusion_step_is_the_references(bench, program):
    """Loss and every gradient of ``BlockDiffusionLmTask.loss_fn`` against
    the reference's, which redraws the noise from the same key."""
    task, params, batch = program
    key = jax.random.fold_in(jax.random.PRNGKey(7), 1)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: task.loss_fn(p, {}, batch, key, True), has_aux=True)(params)
    want, want_grads = jax.value_and_grad(bench.reference.loss_fn)(
        params, batch["tokens"], key, bench.sizes)
    _close(loss, want, "loss", 1e-6)
    flat = bench.weights.flat
    for (name, got), ref in zip(flat(grads).items(),
                                flat(want_grads).values()):
        _close(got, ref, name)
    assert 0.0 < float(aux["bd_masked_share"]) < 1.0
    assert float(aux["moe_rows_held"]) == 2 * 2 * LENGTH * 2 * 2  # layers too


@pytest.fixture(scope="module")
def sound_loss(bench, program):
    _, params, batch = program
    return float(bench.reference.loss_fn(
        params, batch["tokens"], jax.random.PRNGKey(11), bench.sizes))


@pytest.mark.parametrize("fault", [
    "clean_sees_noised", "staircase_off_by_one", "positions_run_on",
    "no_qk_norm", "no_rate_weight", "an_expert_out"])
def test_each_control_moves_the_reference(bench, program, sound_loss, fault):
    """The faults the calibration runs on the chip are faults: each moves
    the reference's own loss at the tiny size."""
    _, params, batch = program
    kw = dict(experts_out=(3,)) if fault == "an_expert_out" \
        else dict(faults=(fault,))
    faulty = float(bench.reference.loss_fn(
        params, batch["tokens"], jax.random.PRNGKey(11), bench.sizes, **kw))
    assert abs(faulty - sound_loss) > 1e-6 * sound_loss, fault


def test_recomputed_blocks_are_the_blocks_kept(program):
    from deeplearning_cfn_tpu.train.task import build_task

    task, params, batch = program
    kept = build_task(_tiny_cfg("model.kwargs.remat_blocks=false"))
    key = jax.random.PRNGKey(5)
    step = lambda t: jax.value_and_grad(
        lambda p: t.loss_fn(p, {}, batch, key, True)[0])(params)
    for got, want in zip(jax.tree_util.tree_leaves(step(task)),
                         jax.tree_util.tree_leaves(step(kept))):
        _close(got, want, "recomputed", 1e-6)


def test_eight_shares_add_up_to_the_uncut_layer(bench):
    """The parts that eight shares of 16 experts give, the residual counted
    once, are the uncut layer's result (128 experts, 8 a token, at a small
    width): in the program's expert layer and in the reference's alike."""
    from deeplearning_cfn_tpu.models.moe import HeldExpertsMlp, \
        SoftmaxTopKRouter

    f, width, experts, top_k, tokens = 32, 16, 128, 8, 8
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (1, tokens, f))
    layer = lambda held: HeldExpertsMlp(
        num_experts=experts, mlp_dim=width, held=held, dtype=jnp.float32,
        implementation="ragged_dot",
        router=SoftmaxTopKRouter(experts, top_k, parent=None))
    whole = layer((0, experts))
    params = whole.init(key, x)["params"]
    want, _ = whole.apply({"params": params}, x)
    w_in = params["experts_in"]["kernel"].reshape(experts, f, -1)
    w_out = params["experts_out"]["kernel"].reshape(experts, -1, f)
    sizes = {"num_experts_per_tok": top_k,
             "published": {"num_experts": experts}}
    mm = lambda a, b: jnp.matmul(a, b, precision="highest")
    got = ref = 0.0
    for first in range(0, experts, 16):
        share = {"router": params["router"],
                 "experts_in": {"kernel": w_in[first:first + 16].reshape(
                     -1, w_in.shape[-1])},
                 "experts_out": {"kernel": w_out[first:first + 16].reshape(
                     -1, f)}}
        got = got + layer((first, 16)).apply({"params": share}, x)[0]
        ref = ref + bench.reference.moe_layer(
            mm, x[0], share, dict(sizes, experts_held=[first, 16]))
    _close(got, want, "the program's shares")
    _close(ref, want[0], "the reference's shares")


# -- the noise ----------------------------------------------------------------

def test_the_noise_is_a_pure_function_of_the_key(bench, program):
    from deeplearning_cfn_tpu.train.task import BD_MASK_ID, BD_MIN_RATE, \
        draw_block_diffusion_noise

    key = jax.random.PRNGKey(21)
    rate, masked = draw_block_diffusion_noise(key, 64, 512)
    again = draw_block_diffusion_noise(key, 64, 512)
    assert np.array_equal(rate, again[0]) and np.array_equal(masked, again[1])
    other = draw_block_diffusion_noise(jax.random.PRNGKey(22), 64, 512)
    assert not np.array_equal(masked, other[1])
    assert float(rate.min()) >= BD_MIN_RATE and float(rate.max()) <= 1.0
    # A row's masked share follows its own rate (512 draws: 4.5 sigma).
    assert float(jnp.max(jnp.abs(masked.mean(axis=1) - rate))) < 0.1
    want = bench.reference.draw_noise(key, 64, 512, bench.published["noise"])
    assert np.array_equal(rate, want[0]) and np.array_equal(masked, want[1])
    assert (BD_MASK_ID, BD_MIN_RATE) == (
        bench.published["noise"]["mask_id"],
        bench.published["noise"]["min_rate"])


def test_a_masked_tokens_weight_is_one_over_its_rate(program):
    """With uniform logits (every parameter zero) a token's cross-entropy is
    ``log V``, so the loss is ``log V`` times the mean weight: ``1 / t`` on a
    masked token, nothing on an unmasked one."""
    from deeplearning_cfn_tpu.train.task import draw_block_diffusion_noise

    task, params, batch = program
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    key = jax.random.PRNGKey(9)
    loss, _ = task.loss_fn(zeros, {}, batch, key, True)
    rate, masked = draw_block_diffusion_noise(key, 2, LENGTH)
    want = np.log(96.0) * float(jnp.sum(masked / rate[:, None])) \
        / (2 * LENGTH)
    np.testing.assert_allclose(float(loss), want, rtol=1e-5)
    half = dict(batch, loss_mask=batch["loss_mask"].at[:, LENGTH // 2:].set(0))
    want = np.log(96.0) * float(jnp.sum(
        (masked / rate[:, None])[:, :LENGTH // 2])) / LENGTH
    np.testing.assert_allclose(
        float(task.loss_fn(zeros, {}, half, key, True)[0]), want, rtol=1e-5)


# -- the preset -----------------------------------------------------------------

def test_the_preset_is_the_published_model_cut_as_the_file_says(bench):
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train.task import BlockDiffusionLmTask, \
        CausalLmTask, build_task

    cfg = get_preset("sdar_30b_a3b_lm")
    task = build_task(cfg)
    assert isinstance(task, BlockDiffusionLmTask)
    assert (task.layout.length, task.layout.block) == (8192, 4)
    shapes = jax.eval_shape(task.init, jax.random.PRNGKey(0))["params"]
    count = sum(int(np.prod(s.shape))
                for s in jax.tree_util.tree_leaves(shapes))
    assert count == 645_950_976
    p = bench.published
    layer = shapes["layer_0"]
    assert layer["self_attn"]["query"]["kernel"].shape == (
        p["hidden_size"], p["num_attention_heads"] * p["head_dim"])
    assert layer["self_attn"]["key"]["kernel"].shape == (
        p["hidden_size"], p["num_key_value_heads"] * p["head_dim"])
    assert layer["self_attn"]["query_norm"]["scale"].shape == (p["head_dim"],)
    assert layer["mlp"]["router"]["kernel"].shape == (
        p["hidden_size"], p["published"]["num_experts"])
    assert layer["mlp"]["experts_in"]["kernel"].shape == (
        p["num_experts"] * p["hidden_size"], 2 * p["moe_intermediate_size"])
    assert shapes["lm_head"]["kernel"].shape == (p["hidden_size"],
                                                 p["vocab_size"])
    assert sorted(k for k in shapes if k.startswith("layer_")) == [
        f"layer_{i}" for i in p["layers_held"]]
    cfg.train.block_diffusion = 0
    assert type(build_task(cfg)) is CausalLmTask


def test_the_tiny_twin_trains_through_fit(tmp_path):
    """``build_task`` -> ``Trainer.fit`` on the CPU: the objective falls, the
    registry has the task's gauges, the flash counter and the step's masked
    share."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.train.run import run_experiment

    cfg = _tiny_cfg("train.steps=6", "train.global_batch=8", "mesh.data=-1",
                    "data.num_train_examples=64", "schedule.name=constant",
                    "schedule.base_lr=0.003", "schedule.warmup_steps=0",
                    f"workdir={tmp_path}")
    registry = get_tracer().registry
    before = registry.histogram("train.bd.masked_share.steps").count()
    run_experiment(cfg)
    rows = [json.loads(line) for line in open(
        os.path.join(tmp_path, "sdar_30b_a3b_lm", "metrics.jsonl"))]
    losses = [r["loss"] for r in rows if "loss" in r]
    assert len(losses) >= 6 and all(np.isfinite(losses))
    assert registry.gauge("train.bd.block_length").value() == BLOCK
    assert registry.gauge("train.bd.positions_per_token").value() == 2
    assert registry.counter("attention.flash.calls").value(
        mask="block_diffusion", path="xla") > 0
    assert registry.histogram(
        "train.bd.masked_share.steps").count() >= before + 6
