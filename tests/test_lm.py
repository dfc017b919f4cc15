"""Decoder-only causal LM: data source, causality, KV-cache decode
consistency, and short-horizon convergence through the full trainer."""

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
from deeplearning_cfn_tpu.data.text import make_lm_source
from deeplearning_cfn_tpu.metrics import read_metrics
from deeplearning_cfn_tpu.models import build_model
from deeplearning_cfn_tpu.train.run import run_experiment


def _init(model, ids):
    """The model's variables from key 0, made by one compiled program: run
    eagerly, every initializer and every operation of the forward pass is a
    program of its own to compile."""
    return jax.jit(lambda key, ids: model.init(key, ids, train=False))(
        jax.random.PRNGKey(0), ids)


def test_lm_source_invariants():
    src = make_lm_source(64, seq_len=16, vocab_size=32, seed=0)
    batch = src.gather(np.arange(64))
    assert batch["tokens"].shape == (64, 17)  # seq_len + 1
    assert batch["loss_mask"].shape == (64, 16)
    assert batch["tokens"].min() >= 0 and batch["tokens"].max() < 32
    # Deterministic across constructions.
    again = make_lm_source(64, seq_len=16, vocab_size=32, seed=0)
    np.testing.assert_array_equal(batch["tokens"],
                                  again.gather(np.arange(64))["tokens"])


def test_prepare_lm_text_roundtrip(tmp_path):
    """prepare-text → real-data lm_text pipeline → a training step: the
    fully-offline byte-level path."""
    from deeplearning_cfn_tpu.data.text import build_text_source, \
        prepare_lm_text

    src = tmp_path / "corpus.txt"
    src.write_bytes(bytes(range(256)) * 40)  # 10240 bytes
    out = str(tmp_path / "tok")
    info = prepare_lm_text(str(src), out, seq_len=31)
    assert info["train_examples"] + info["eval_examples"] == 10240 // 32
    assert info["vocab_size"] == 260

    cfg = DataConfig(name="lm_text", seq_len=31, vocab_size=260,
                     data_dir=out, synthetic=False)
    train_src = build_text_source(cfg, train=True)
    batch = train_src.gather(np.arange(4))
    assert batch["tokens"].shape == (4, 32)
    # Byte values shifted past the 4 reserved specials.
    assert batch["tokens"].min() >= 4 and batch["tokens"].max() < 260

    with pytest.raises(ValueError, match="at least"):
        tiny = tmp_path / "tiny.txt"
        tiny.write_bytes(b"x" * 10)
        prepare_lm_text(str(tiny), out, seq_len=31)
    with pytest.raises(ValueError, match="eval_fraction"):
        prepare_lm_text(str(src), out, seq_len=31, eval_fraction=1.5)


def test_lm_is_causal():
    """Changing a future token must not change past logits."""
    model = build_model("gpt_tiny", 0, jnp.float32, vocab_size=32,
                        max_len=16, dropout_rate=0.0)
    ids = jnp.arange(12, dtype=jnp.int32)[None, :] % 32
    variables = _init(model, ids)
    base = model.apply(variables, ids, train=False)
    bumped = ids.at[0, 8].set((ids[0, 8] + 7) % 32)
    out = model.apply(variables, bumped, train=False)
    np.testing.assert_allclose(np.asarray(base[0, :8]),
                               np.asarray(out[0, :8]), atol=1e-5)
    assert not np.allclose(np.asarray(base[0, 8:]), np.asarray(out[0, 8:]))


@pytest.mark.parametrize("num_experts", [0, 2])
def test_lm_kv_cache_decode_matches_full_forward(num_experts):
    """Incremental decode through the KV cache must reproduce the full
    forward's logits position by position — the correctness claim behind
    cached generation (including through MoE FFN layers, whose routing
    is per-token and so decode-invariant)."""
    # capacity_factor high enough that the full-sequence pass drops no
    # tokens — per-position decode never drops (1 token vs capacity>=1),
    # so drop-free routing is a precondition for exact parity.
    model = build_model("gpt_tiny", 0, jnp.float32, vocab_size=32,
                        max_len=16, dropout_rate=0.0,
                        num_experts=num_experts, moe_capacity_factor=4.0)
    T = 10
    ids = (jax.random.randint(jax.random.PRNGKey(1), (1, T), 0, 32)
           .astype(jnp.int32))
    variables = _init(model, ids)
    full = model.apply(variables, ids, train=False)
    if num_experts:
        full = full[0]  # (logits, moe_aux) when MoE layers exist

    # Create the cache via a decode_step init (the documented contract).
    from deeplearning_cfn_tpu.models.lm import TransformerCausalLm

    dec_vars = model.init(jax.random.PRNGKey(0), ids[:, :1], 0,
                          method=TransformerCausalLm.decode_step)
    cache = dec_vars["cache"]
    step_logits = []
    for t in range(T):
        logits, mutated = model.apply(
            {"params": variables["params"], "cache": cache},
            ids[:, t:t + 1], t, method=TransformerCausalLm.decode_step,
            mutable=["cache"])
        cache = mutated["cache"]
        step_logits.append(np.asarray(logits[0, 0]))
    np.testing.assert_allclose(np.stack(step_logits), np.asarray(full[0]),
                               atol=1e-4)


def test_lm_trains_end_to_end(tmp_workdir):
    cfg = ExperimentConfig(
        model=ModelConfig(name="gpt_tiny",
                          kwargs=dict(vocab_size=64, max_len=32,
                                      dropout_rate=0.0)),
        data=DataConfig(name="lm_text", seq_len=32, vocab_size=64,
                        num_train_examples=256, num_eval_examples=64),
        train=TrainConfig(global_batch=32, dtype="float32", eval_batch=32),
        optimizer=OptimizerConfig(name="adamw", weight_decay=0.01,
                                  grad_clip_norm=1.0),
        schedule=ScheduleConfig(name="constant", base_lr=3e-3,
                                warmup_steps=5),
        mesh=MeshConfig(data=-1),
    )
    cfg.workdir = os.path.join(tmp_workdir, "work")
    cfg.train.steps = 40
    cfg.train.log_every_steps = 5
    cfg.data.prefetch = 0
    cfg.checkpoint.async_write = False
    final = run_experiment(cfg)
    records = [r for r in read_metrics(
        os.path.join(cfg.workdir, "gpt_tiny", "metrics.jsonl"))
        if "loss" in r]
    first, last = records[0], records[-1]
    # Next-token CE over a 64-vocab Markov chain starts near ln(60)≈4.1;
    # the fixed transitions must pull it well below within 40 steps.
    assert last["loss"] < first["loss"] - 0.5, (first, last)
    assert "perplexity" in final and "token_accuracy" in final
    assert final["perplexity"] < np.exp(first["loss"])
    # Derived post-aggregation, so it must be exactly exp of the exact
    # token-weighted eval CE (not a mean of per-batch exps; without MoE
    # layers ce_loss == loss).
    assert final["perplexity"] == pytest.approx(np.exp(final["ce_loss"]))
    assert final["ce_loss"] == pytest.approx(final["loss"])


def test_lm_generate_greedy_matches_manual_rollout():
    """lm_generate(temperature=0) must equal the brute-force rollout that
    re-runs the FULL forward and takes argmax of the last position each
    step — the cached scan is an optimization, not a different sampler."""
    from deeplearning_cfn_tpu.models.decoding import lm_generate

    model = build_model("gpt_tiny", 0, jnp.float32, vocab_size=32,
                        max_len=16, dropout_rate=0.0)
    prompt = jnp.array([[5, 9, 3], [1, 2, 7]], jnp.int32)
    variables = _init(model, prompt)

    out = lm_generate(model, variables, prompt, max_new_tokens=6)
    assert out.shape == (2, 9)
    np.testing.assert_array_equal(np.asarray(out[:, :3]),
                                  np.asarray(prompt))

    manual = prompt
    apply = jax.jit(lambda v, ids: model.apply(v, ids, train=False))
    for _ in range(6):
        logits = apply(variables, manual)
        nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
        manual = jnp.concatenate([manual, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(manual))


def test_lm_generate_recompute_fallback_for_gpt_long():
    """Models without decode_step (gpt_long) take the recompute drive
    mode — greedy output must still equal the brute-force rollout."""
    from deeplearning_cfn_tpu.models.decoding import lm_generate

    model = build_model("gpt_long", 0, jnp.float32, vocab_size=32,
                        hidden_size=32, num_layers=1, num_heads=2,
                        mlp_dim=64, max_len=16)
    assert not hasattr(type(model), "decode_step")
    prompt = jnp.array([[3, 7, 1]], jnp.int32)
    variables = _init(model, prompt)
    out = lm_generate(model, variables, prompt, max_new_tokens=5)
    manual = prompt
    apply = jax.jit(lambda v, ids: model.apply(v, ids, train=False))
    for _ in range(5):
        logits = apply(variables, manual)
        nxt = jnp.argmax(logits[:, -1, :], -1).astype(jnp.int32)
        manual = jnp.concatenate([manual, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(manual))


def test_lm_generate_sampling_is_seeded_and_in_vocab():
    from deeplearning_cfn_tpu.models.decoding import lm_generate

    model = build_model("gpt_tiny", 0, jnp.float32, vocab_size=32,
                        max_len=16, dropout_rate=0.0)
    prompt = jnp.array([[4, 8]], jnp.int32)
    variables = _init(model, prompt)
    a = lm_generate(model, variables, prompt, 5, temperature=1.0,
                    top_k=8, rng=jax.random.PRNGKey(7))
    b = lm_generate(model, variables, prompt, 5, temperature=1.0,
                    top_k=8, rng=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(a.min()) >= 0 and int(a.max()) < 32
    with pytest.raises(ValueError, match="rng"):
        lm_generate(model, variables, prompt, 5, temperature=1.0)
    # Generating past max_len would silently clamp the cache writes —
    # it must refuse instead.
    with pytest.raises(ValueError, match="max_len"):
        lm_generate(model, variables, prompt, 15)


def test_generate_verb_end_to_end(tmp_path, capsys):
    """train (byte-level corpus) → `generate` verb continues the learned
    text from a prompt — the LM family's full user loop via the CLI."""
    from deeplearning_cfn_tpu.cli.main import main
    from deeplearning_cfn_tpu.data.text import prepare_lm_text

    src = tmp_path / "c.txt"
    src.write_bytes(b"abcdefgh" * 600)
    tok = str(tmp_path / "tok")
    prepare_lm_text(str(src), tok, seq_len=15)
    common = [
        "--preset", "gpt_small_lm", "--accelerator", "cpu",
        f"workdir={tmp_path}", "model.name=gpt_tiny",
        'model.kwargs={"vocab_size": 260, "max_len": 16}',
        "data.name=lm_text", f"data.data_dir={tok}",
        "data.synthetic=false", "data.vocab_size=260", "data.seq_len=15",
        "train.global_batch=16", "train.dtype=float32",
        "train.eval_batch=16", "schedule.name=constant",
        "schedule.base_lr=3e-3", "schedule.warmup_steps=5",
        "train.shard_opt_state=false", "checkpoint.async_write=false",
        "data.prefetch=0",
    ]
    assert main(["train", *common, "train.steps=40",
                 "train.log_every_steps=10"]) == 0
    capsys.readouterr()
    assert main(["generate", *common, "--prompt", "abcd",
                 "--max-new-tokens", "8"]) == 0
    out = capsys.readouterr().out
    # The corpus is the 8-cycle "abcdefgh": a model at ~100% token
    # accuracy must continue it exactly.
    assert "abcdefghabcd" in out, out
    # --vocab plumbing (load → prompt encode → output decode, no crash):
    # a zero-merge BPE over MLM_SPECIALS maps bytes to the same ids as the
    # byte tokenizer EXCEPT it appends an end-of-word space token (36) the
    # space-free corpus never saw — so the continuation after it is
    # arbitrary and only the decoded prompt echo is asserted. Continuation
    # QUALITY is covered by the byte-path assertion above.
    from deeplearning_cfn_tpu.data.bpe import Bpe, MLM_SPECIALS

    vocab_path = str(tmp_path / "vocab.json")
    Bpe([], MLM_SPECIALS).save(vocab_path)
    capsys.readouterr()
    assert main(["generate", *common, "--prompt", "abcd",
                 "--vocab", vocab_path, "--max-new-tokens", "4"]) == 0
    out = capsys.readouterr().out
    assert "abcd" in out, out
    # A prompt that BPE-encodes to nothing (pure whitespace) exits 1.
    assert main(["generate", *common, "--prompt", "   ",
                 "--vocab", vocab_path]) == 1
    # Misuse exits 1 with an error, not a traceback: wrong preset/workdir
    # (no checkpoint), and an explicit step that was never committed.
    assert main(["generate", "--preset", "cifar10_resnet20",
                 "--accelerator", "cpu", f"workdir={tmp_path}",
                 "--prompt", "x"]) == 1
    assert main(["generate", *common, "--prompt", "abcd",
                 "--step", "999"]) == 1


def test_lm_moe_trains_and_shards_experts(tmp_workdir, devices):
    """gpt with num_experts: MoE aux losses thread into the objective and
    expert weights shard over the 'expert' mesh axis (the GShard
    convention the bert_moe flagship uses)."""
    from deeplearning_cfn_tpu.parallel import build_mesh
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(name="gpt_tiny",
                          kwargs=dict(vocab_size=64, max_len=32,
                                      num_experts=2)),
        data=DataConfig(name="lm_text", seq_len=32, vocab_size=64,
                        num_train_examples=64, num_eval_examples=32,
                        prefetch=0),
        train=TrainConfig(global_batch=16, dtype="float32"),
        mesh=MeshConfig(data=4, expert=2),
    )
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, 16, 4)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules)
    n_expert_sharded = 0
    for leaf in jax.tree_util.tree_leaves(state.params):
        spec = getattr(leaf.sharding, "spec", None)
        if spec and any(ax == "expert" for ax in spec if ax):
            n_expert_sharded += 1
    assert n_expert_sharded >= 2, n_expert_sharded  # 1 MoE layer's w1/w2

    from deeplearning_cfn_tpu.data import build_pipeline

    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    pipe = build_pipeline(cfg.data, 16, 0, seed=0, train=True)
    batch = trainer.device_batch(next(iter(pipe.one_epoch(0))))
    state, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(1))
    assert np.isfinite(float(metrics["loss"]))
    assert "moe_load_balance" in metrics


def test_lm_tensor_parallel_shards_kernels(tmp_workdir, devices):
    """gpt models carry the transformer PARAM_RULES: on a data×model mesh
    the block kernels must actually shard over 'model'."""
    from deeplearning_cfn_tpu.parallel import build_mesh
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, build_schedule
    from deeplearning_cfn_tpu.train.task import build_task

    cfg = ExperimentConfig(
        model=ModelConfig(name="gpt_tiny",
                          kwargs=dict(vocab_size=64, max_len=32)),
        data=DataConfig(name="lm_text", seq_len=32, vocab_size=64,
                        num_train_examples=64, num_eval_examples=32),
        train=TrainConfig(global_batch=16, dtype="float32"),
        mesh=MeshConfig(data=4, model=2),
    )
    mesh = build_mesh(cfg.mesh)
    task = build_task(cfg)
    sched = build_schedule(cfg.schedule, 4, 16, 4)
    tx = build_optimizer(cfg.optimizer, sched)
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh,
                               param_rules=task.param_rules)
    n_sharded = 0
    for leaf in jax.tree_util.tree_leaves(state.params):
        spec = getattr(leaf.sharding, "spec", None)
        if spec and any(ax == "model" for ax in spec if ax):
            n_sharded += 1
    assert n_sharded >= 6, n_sharded  # 2 layers × (qkv/out/mlp kernels)


# -- the `zaya` decoder: attention in a convolved latent, a router with a
# state carried from block to block, a scaled residual --------------------


def _zaya1_tiny(**kw):
    model = build_model("gpt_zaya1_tiny", 0, jnp.float32, **kw)
    ids = (jnp.arange(2 * 32, dtype=jnp.int32).reshape(2, 32) * 7) % 96
    return model, ids, jax.jit(model.init)(jax.random.PRNGKey(0), ids)


def test_zaya1_is_causal():
    """Changing token t changes no output before t: the convolutions and
    the value shift look one position back and none forward, and a token's
    one expert is chosen from the token alone."""
    model, ids, variables = _zaya1_tiny()
    apply = jax.jit(model.apply)
    base, _ = apply(variables, ids)
    bumped = ids.at[0, 20].set((ids[0, 20] + 11) % 96)
    out, _ = apply(variables, bumped)
    np.testing.assert_array_equal(np.asarray(base[0, :20]),
                                  np.asarray(out[0, :20]))
    np.testing.assert_array_equal(np.asarray(base[1]), np.asarray(out[1]))
    # The token after it sees it twice over: through the softmax, and
    # through the taps and the shifted value half.
    assert not np.allclose(np.asarray(base[0, 20:]), np.asarray(out[0, 20:]))


def test_zaya1_carries_the_router_state_from_block_to_block():
    """Layer 0 is handed no state and has no gamma; layers 1 and 2 are
    handed the state of the layer before, and a block given zeros in its
    place gives another result. The registry says how many were handed one,
    and how many attention calls mixed their latent."""
    from deeplearning_cfn_tpu.models.transformer import TransformerLayer
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    registry = get_tracer().registry
    calls = registry.counter("attention.cca.calls")
    before = calls.value()
    model, ids, variables = _zaya1_tiny()
    assert calls.value() - before == 3
    assert registry.gauge("moe.router.state_layers").value() == 2
    params = variables["params"]
    assert "scale" not in params["layer_0"]["mlp"]["router"]
    assert params["layer_1"]["mlp"]["router"]["scale"].shape == (16,)
    assert "self_attn_stream" not in params["layer_0"]
    assert {"self_attn_stream", "self_attn_result", "mlp_stream",
            "mlp_result"} <= set(params["layer_1"])

    _, heads, width, style = model.blocks[1]
    layer = TransformerLayer(heads, width, dtype=jnp.float32, style=style)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    state = jax.random.normal(jax.random.PRNGKey(2), (2, 32, 16))
    call = jax.jit(lambda r: layer.apply({"params": params["layer_1"]}, x,
                                         causal=True, router_state=r))
    given, aux = call(state)
    zeros, aux0 = call(jnp.zeros_like(state))
    assert aux["router_state"].shape == (2, 32, 16)
    # What is handed on is this layer's own z with gamma times the state
    # it was handed.
    np.testing.assert_allclose(
        np.asarray(aux["router_state"] - aux0["router_state"]),
        np.asarray(state), atol=1e-5)
    assert not np.allclose(np.asarray(given), np.asarray(zeros))


def test_zaya1_flash_path_matches_the_xla_path_at_published_heads():
    """8 query heads over 2 K/V heads of 128 (a group of 4) with the latent
    mixing before them: the flash and rotary kernels in interpret mode
    against XLA's attention and the plain rotation, output and parameter
    gradients to bf16 rounding."""
    from deeplearning_cfn_tpu.models.lm import _ZAYA1_8B
    from deeplearning_cfn_tpu.models.transformer import BlockStyle, \
        MultiHeadAttention

    z = _ZAYA1_8B
    attention = lambda implementation: MultiHeadAttention(
        num_heads=z["heads"], dtype=jnp.bfloat16,
        attention_impl=implementation, style=BlockStyle(
            num_kv_heads=z["kv_heads"], head_dim=z["head_dim"],
            rope=z["rope"], latent_mix=z["latent_mix"]))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 512, 256))
    params = jax.jit(lambda key, x: attention("reference").init(
        key, x, causal=True))(jax.random.PRNGKey(4), x)
    assert params["params"]["conv_heads"]["kernel"].shape == (256, 1280)
    assert params["params"]["value_prev"]["kernel"].shape == (256, 128)

    def loss(params, implementation):
        out = attention(implementation).apply(params, x, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    value_and_grad = jax.jit(jax.value_and_grad(loss, has_aux=True),
                             static_argnums=1)
    (_, got), got_grad = value_and_grad(params, "interpret")
    (_, want), want_grad = value_and_grad(params, "reference")
    # bf16 outputs of size up to 4: an ulp there is 0.03.
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2, rtol=2e-2)
    # The taps' gradients come through the norm of q and k, which spreads
    # the two backward passes' bf16 rounding: 2.4 % there, under 2 % elsewhere.
    for a, b in zip(jax.tree_util.tree_leaves(got_grad),
                    jax.tree_util.tree_leaves(want_grad)):
        assert float(jnp.linalg.norm(a - b)) \
            < 4e-2 * float(jnp.linalg.norm(b)), (a.shape,)


def _tree_digest(name, **kw):
    import hashlib

    model = build_model(name, 0, jnp.float32, **kw)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 32), jnp.int32))["params"]
    leaves = sorted(
        ("/".join(str(getattr(k, "key", k)) for k in path), tuple(s.shape))
        for path, s in jax.tree_util.tree_flatten_with_path(shapes)[0])
    return len(leaves), hashlib.sha256(repr(leaves).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name,kw,want", [
    ("gpt_tiny", {}, (38, "5b6624f63f96cc4c")),
    ("gpt_small", dict(vocab_size=50257), (198, "2d04cfa0c3412b6c")),
    ("gpt_laguna_tiny", {}, (36, "a8568ff88e01a690")),
    ("gpt_laguna_xs2", dict(vocab_size=12544, layers_held=(0, 1, 2, 3, 4),
                            experts_held=(0, 32)), (60, "d036ae9448e190f7")),
    # Recorded at the commit before ``BlockStyle`` gained its mixer, constant
    # multiplier, score scale and recomputation (PR 41): default fields leave
    # the styled models that were there as they were.
    ("gpt_zaya1_tiny", {}, (89, "007ab5a8a9cf01ab")),
    ("gpt_zaya1_8b", dict(vocab_size=32896, layers_held=(0, 1, 2, 3, 4),
                          experts_held=(0, 8)), (149, "f77c4fa43f6f3243")),
    ("gpt_mellum2_tiny", {}, (39, "966c2af0aa03351a")),
    ("gpt_mellum2_12b", dict(vocab_size=24576, layers_held=(0, 1, 2, 3)),
     (39, "6419822411503a6b")),
])
def test_parameter_trees_of_the_other_decoders_are_as_recorded(name, kw,
                                                               want):
    """Leaf for leaf, names and shapes: the benchmark's references look
    their parameters up by name. The first four recorded at the commit
    before the router became a module of its own."""
    assert _tree_digest(name, **kw) == want


def test_zaya1_preset_is_the_chips_share():
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train.task import build_task

    cfg = get_preset("zaya1_8b_lm")
    assert (cfg.data.seq_len, cfg.train.global_batch,
            cfg.data.vocab_size) == (4096, 2, 32_896)
    task = build_task(cfg)
    shapes = jax.eval_shape(task.init, jax.random.PRNGKey(0))["params"]
    count = sum(int(np.prod(s.shape))
                for s in jax.tree_util.tree_leaves(shapes))
    # 5 x (5.57 M attention + 0.66 M router + 100.66 M experts) + 67.4 M.
    assert 601.7e6 < count < 602.1e6
    assert shapes["token"]["embedding"].shape == (32_896, 2048)
    assert "lm_head" not in shapes
    assert shapes["layer_4"]["mlp"]["experts_in"]["kernel"].shape \
        == (8 * 2048, 2 * 2048)
    assert shapes["layer_4"]["mlp"]["router"]["out"]["kernel"].shape \
        == (256, 16)


@pytest.mark.parametrize("accum", [1, 2])
def test_zaya1_train_step_moves_the_balancing_biases(devices, accum):
    """No gradient reaches a router's balancing bias; the step adds what the
    router sowed for it after the optimizer's update: ``-rate * min(n_e /
    mean(n) - 1, 1)`` from the step's own loads (the mean over the
    microbatches where gradients accumulate), and to nothing else."""
    from deeplearning_cfn_tpu.models.moe import BALANCE_RATE as rate
    from deeplearning_cfn_tpu.parallel import build_mesh
    from deeplearning_cfn_tpu.train import create_train_state
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    cfg = ExperimentConfig(
        model=ModelConfig(name="gpt_zaya1_tiny",
                          kwargs=dict(vocab_size=96, experts_held=(0, 4))),
        data=DataConfig(name="lm_text", seq_len=32, vocab_size=96),
        train=TrainConfig(global_batch=4, dtype="float32",
                          grad_accum_steps=accum),
        mesh=MeshConfig(data=1),
    )
    mesh = build_mesh(cfg.mesh, devices=devices[:1])
    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer, build_schedule(cfg.schedule, 4, 4, 4))
    state = create_train_state(jax.random.PRNGKey(0), task.init, tx, mesh)
    assert not state.batch_stats
    tokens = np.random.default_rng(0).integers(0, 96, (4, 33), np.int32)
    batch = {"tokens": jnp.asarray(tokens),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    start = jax.device_get(state.params)
    _, aux = jax.jit(lambda params: task.loss_fn(
        params, {}, batch, None, True))(state.params)
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh, donate=False)
    new, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(1))
    assert "nudges" not in metrics
    for i in range(3):
        old = start[f"layer_{i}"]["mlp"]["router"]
        moved = np.asarray(new.params[f"layer_{i}"]["mlp"]["router"]["bias"]
                           - old["bias"])
        assert np.max(np.abs(moved)) > 0.1 * rate
        assert np.all(np.abs(moved) <= rate * (1 + 1e-6))
        if accum == 1:
            np.testing.assert_allclose(
                moved, np.asarray(
                    aux["nudges"][f"layer_{i}"]["mlp"]["router"]["bias"]),
                atol=1e-7)
    # An evaluation sows nothing.
    assert "nudges" not in jax.eval_shape(lambda params: task.loss_fn(
        params, {}, batch, None, False), state.params)[1]


def test_nudges_for_no_parameter_are_an_error():
    from deeplearning_cfn_tpu.train.state import _nudged

    params = {"a": {"bias": jnp.zeros(3)}, "b": {"kernel": jnp.ones((2, 2))}}
    out = _nudged(params, {"a": {"bias": jnp.ones(3)}})
    assert np.all(np.asarray(out["a"]["bias"]) == 1)
    assert out["b"]["kernel"] is params["b"]["kernel"]
    with pytest.raises(KeyError):
        _nudged(params, {"a": {"scale": jnp.ones(3)}})
