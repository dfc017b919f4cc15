"""The ``mellum2_12b`` configuration at a size a test run can hold, on the
CPU: the program against the plain reference (logits, loss, every gradient)
on one device; the program's first steps through the benchmark's own
``first_steps`` on an ``expert=4`` mesh of four virtual devices against the
reference placed over four devices; the cell rehearsed end to end through
``run.py`` on four virtual devices in a tiny tree built by adding files; the
controls reading ``correct`` false (a rank's parts left out among them); the
operations a token by hand; what the committed manifest lists the cell on,
found by name; and the six per-layer metrics' readers on fixtures."""

import contextlib
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import (collectives, compare, manifest,  # noqa: E402
                     opcount_mellum2, train_steps, weights)

import benchmark_tiny_tree  # noqa: E402
from test_benchmark_cells_train import last_line, run_cell  # noqa: E402

CELL = "mellum2_12b_train_8k_ep4"
# What ``gpt_mellum2_tiny`` (models/lm.py) is, in the source's keys: hidden
# 64, 4 query heads over 2 K/V heads of 16, window 8, 16 experts of width 32,
# 4 a token, four layers: sliding x 3, full.
TINY_MELLUM = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads": 4, "moe_intermediate_size": 32,
    "num_hidden_layers": 4, "num_experts": 16, "num_experts_per_tok": 4,
    "sliding_window": 8, "vocab_size": 96, "layers_held": [0, 1, 2, 3],
    "experts_held": [0, 16], "expert_parallel_ranks": 4,
    "num_attention_heads_per_layer": [4] * 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 4,
            "original_max_position_embeddings": 16, "beta_fast": 2,
            "beta_slow": 1, "attention_factor": 1.1386294361119891},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "published": {"vocab_size": 96, "num_hidden_layers": 4,
                  "num_experts": 16},
    "overrides": [
        "model.name=gpt_mellum2_tiny", "model.kwargs.layers_held=[0,1,2,3]",
        "train.dtype=float32", "data.synthetic=true",
        "train.log_every_steps=1", "data.use_native_loader=false",
        "checkpoint.every_steps=0", "eval.enabled=false"],
    "precision": "float32",
    # float32 on the CPU against float32: what is left is the order of the
    # sums (sorted rows multiplied group by group on four ranks and summed
    # over them; every expert over every token, sixteen turns of four side
    # by side). A rank's parts left out move them a thousandfold.
    "limits": {"train_loss_rel": 1e-5, "train_grad_norm_gap": 1e-4,
               "train_change_norm_gap": 1e-2},
}
TINY_TRAFFIC = {
    "overrides": ["train.global_batch=4", "data.seq_len=32",
                  "data.vocab_size=96", "mesh.data=1", "mesh.expert=4",
                  "train.shard_opt_state=false"],
    "num_examples": 32, "trace_steps": 3}
SEED = 2 ** 31 + 35


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as fh:
        return json.load(fh)


PUBLISHED = _load("benchmark/configs/mellum2_12b.json")


@pytest.fixture(scope="module")
def cell():
    return types.SimpleNamespace(
        name="tiny_mellum2", chips=4,
        config=dict(PUBLISHED, **TINY_MELLUM),
        traffic=dict(_load(
            "benchmark/traffic/train_packed_8k_ep4_v24576.json"),
            **TINY_TRAFFIC),
        reference=manifest.load_module(
            "benchmark/references/mellum2_12b.py", "ref_mellum2_12b"))


@pytest.fixture(scope="module")
def program(cell):
    """The task built as the benchmark builds it (told no mesh: one device
    holds everything), seeded weights, a batch."""
    import jax

    from deeplearning_cfn_tpu.train.task import build_task

    cfg = train_steps.build_program_config(cell, SEED)
    task = build_task(cfg)
    shapes = jax.eval_shape(task.init, weights.seed_key(SEED))["params"]
    params = jax.jit(lambda key: weights.make(shapes, key))(
        weights.seed_key(SEED))
    tokens = train_steps.make_tokens(SEED, cell.traffic, 32, 96)[:4]
    return task, params, tokens


# -- the configuration's file ------------------------------------------------


def test_file_states_every_published_number_and_the_cut():
    """Every number of the catalog's entry under its own key, but the
    vocabulary; ``reduced`` is the depth and the vocabulary and nothing
    else; all 64 experts held, 16 a rank."""
    c = PUBLISHED
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"]) == (2304, 32, 4, 128)
    assert (c["num_experts"], c["num_experts_per_tok"],
            c["moe_intermediate_size"], c["sliding_window"]) \
        == (64, 8, 896, 1024)
    assert c["norm_topk_prob"] is True and c["tie_word_embeddings"] is False
    assert c["num_hidden_layers"] == 28 and c["intermediate_size"] == 7168
    assert c["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 7
    assert c["mlp_layer_types"] == ["sparse"] * 28
    full = c["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"]) == (
        "yarn", 500000, 16, 8192, 32, 1, 1.2772588722239782)
    assert c["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}
    assert sorted(c["reduced"]) == ["layers_held", "vocab_size"]
    assert c["layers_held"] == [0, 1, 2, 3] and c["vocab_size"] == 24576
    assert c["published"]["vocab_size"] == 98304
    assert c["experts_held"] == [0, 64] and c["expert_parallel_ranks"] == 4
    for key in ("published", "assumed", "deployment", "limits", "precision",
                "limits_set_from"):
        assert key in c, key
    for reading in ("router", "qk_norm", "router_bias", "aux_loss",
                    "mtp_head", "seq_len", "optimizer"):
        assert reading in c["assumed"], reading
    traffic = _load("benchmark/traffic/train_packed_8k_ep4_v24576.json")
    assert {"train.global_batch=4", "data.seq_len=8192",
            "data.vocab_size=24576", "mesh.data=1", "mesh.expert=4"} \
        <= set(traffic["overrides"])
    assert (traffic["repeat_min"], traffic["repeat_max"],
            traffic["trace_steps"]) == (0.0, 0.9, 12)


def test_preset_builds_the_published_block():
    """``mellum2_12b_lm`` through ``build_task``: four blocks, sliding x 3
    then full, the published widths, all 64 experts, on ``expert=4``."""
    import jax

    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train.task import build_task

    cfg = get_preset("mellum2_12b_lm")
    assert (cfg.mesh.data, cfg.mesh.expert) == (1, 4)
    assert (cfg.train.global_batch, cfg.data.seq_len,
            cfg.data.vocab_size) == (4, 8192, 24576)
    model = build_task(cfg).model
    assert [(i, h, w, st.window, st.rope.yarn_factor)
            for i, h, w, st in model.blocks] == [
        (0, 32, 896, 1024, 0.0), (1, 32, 896, 1024, 0.0),
        (2, 32, 896, 1024, 0.0), (3, 32, 896, 0, 16.0)]
    style = model.blocks[3][3]
    assert (style.num_kv_heads, style.head_dim, style.rope.theta,
            style.rope.rotary_dim, style.rope.original_len,
            style.rope.attention_factor) == (
        4, 128, 500000.0, 0, 8192, 1.2772588722239782)
    assert dict(style.experts)["num_experts"] == 64
    assert dict(style.experts)["held"] == (0, 64)
    assert dict(style.router) == {"kind": "softmax_top_k", "top_k": 8}
    shapes = jax.eval_shape(
        build_task(cfg).init, jax.ShapeDtypeStruct((2,), np.uint32))
    flat = weights.flat(shapes["params"])
    assert flat["layer_1/mlp/experts_in/kernel"].shape == (64 * 2304, 1792)
    assert flat["layer_1/mlp/experts_out/kernel"].shape == (64 * 896, 2304)
    assert flat["layer_1/mlp/router/kernel"].shape == (2304, 64)
    assert flat["lm_head/kernel"].shape == (2304, 24576)
    total = sum(int(np.prod(s.shape)) for s in flat.values())
    # 4 x (21.2 M attention + 0.15 M router + 396.4 M experts) + 113.2 M.
    assert 1.783e9 < total < 1.785e9, total


# -- the program against the reference ----------------------------------------

TOL = 1e-5


def _close(got, want, what, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0), \
        (what, np.max(np.abs(got - want)), np.max(np.abs(want)))


def test_program_matches_reference(cell, program):
    import jax
    import jax.numpy as jnp

    task, params, tokens = program
    ref, sizes = cell.reference, cell.config
    logits, aux = jax.jit(lambda p, ids: task.model.apply({"params": p}, ids))(
        params, tokens[:, :-1])
    _close(logits, jax.jit(lambda p, ids: ref.logits_fn(p, ids, sizes))(
        params, tokens[:, :-1]), "logits")
    # 4 x 32 tokens, 4 choices each, four layers, every expert held.
    assert float(aux["rows_held"]) == 4 * 32 * 4 * 4
    assert set(aux) == {"rows_held", "load_max_over_mean"}
    batch = {"tokens": jnp.asarray(tokens),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: task.loss_fn(p, {}, batch, None, True), has_aux=True))(
            params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss_fn(p, t, sizes)))(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= TOL * float(want_loss)
    got, want = weights.flat(grads), weights.flat(want)
    # Four projections, two norms, a router and two stacks a layer; the
    # embedding, the final norm, the head.
    assert set(got) == set(want) and len(got) == 4 * 9 + 3
    for name in want:
        _close(got[name], want[name], name)
        assert np.any(np.asarray(got[name])), name


def test_softmax_router_is_the_references(cell, program):
    import jax

    from deeplearning_cfn_tpu.models.moe import SoftmaxTopKRouter

    _, params, _ = program
    p = params["layer_2"]["mlp"]
    m = np.random.RandomState(9).normal(0, 1, (64, 64)).astype(np.float32)
    chosen, weight, _ = SoftmaxTopKRouter(16, 4).apply(
        {"params": {"kernel": p["router"]["kernel"]}}, m)
    mm = cell.reference._precision.matmul("float32")
    want_chosen, want_weight = cell.reference.route(mm, m, p, cell.config)
    np.testing.assert_array_equal(chosen, want_chosen)
    _close(weight, want_weight, "weights")
    del jax


def test_rope_tables_are_the_references(cell):
    from deeplearning_cfn_tpu.models.lm import _MELLUM2_12B, _MELLUM2_TINY

    for sizes, config, s in ((_MELLUM2_TINY, cell.config, 32),
                             (_MELLUM2_12B, PUBLISHED, 8192)):
        for kind in ("full", "sliding"):
            cos, sin, rot = cell.reference.rope_tables(
                s, config["head_dim"],
                config["rope_parameters"][f"{kind}_attention"])
            assert rot == config["head_dim"]     # the whole head turns
            for got, want in zip(
                    sizes[f"{kind}_rope"].tables(s, config["head_dim"]),
                    (cos, sin)):
                np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)


@pytest.fixture(scope="module")
def first_steps_on_four_ranks(cell):
    """The benchmark's own set-up on four virtual devices: the trainer on
    ``expert=4``, its first three steps through ``Trainer.fit``, and the
    reference following them with its stacks placed over four devices."""
    import jax

    cfg = train_steps.build_program_config(cell, SEED)
    devices = jax.devices()[:4]
    trainer, state, shapes, mesh = train_steps.build_trainer(
        cell, cfg, SEED, devices)
    assert dict(mesh.shape)["expert"] == 4 and mesh.devices.size == 4
    feed = train_steps.build_feed(
        cell, cfg, SEED, mesh, lambda _name: contextlib.nullcontext())
    rng = jax.random.split(jax.random.PRNGKey(cfg.train.seed), 3)[2]
    said = []
    state, got = train_steps.first_steps(trainer, state, feed, rng, shapes,
                                         SEED, said.append)
    make = jax.jit(lambda key: weights.make(shapes, key))
    follow = lambda **kw: cell.reference.train_steps(
        make(weights.seed_key(SEED)), list(feed.first), cell.config,
        dict(cell.config["optimizer"]), **kw)
    return got, follow


def test_steps_on_four_ranks_match_the_reference(cell,
                                                 first_steps_on_four_ranks):
    """Loss of each step, the first gradient's per-leaf norms as Adam got
    them, every leaf's change after three steps: the ``expert=4`` step
    against the float32 reference of the whole layers with no ranks."""
    from jax.sharding import PartitionSpec as P

    got, follow = first_steps_on_four_ranks
    import jax

    placed = cell.reference.state_shardings(
        {"layer_0": {"mlp": {"experts_in": {"kernel": 0},
                             "router": {"kernel": 0}}},
         "token": {"embedding": 0}, "lm_head": {"kernel": 0}}, cell.config)
    assert len(jax.devices()) >= 4 and placed is not None
    assert placed["layer_0/mlp/experts_in/kernel"].spec == P("ranks")
    assert placed["layer_0/mlp/router/kernel"].spec == P()
    assert placed["token/embedding"].spec == P("ranks")
    assert placed["lm_head/kernel"].spec == P(None, "ranks")
    assert cell.reference.state_shardings({}, cell.config, [0]) is None
    want = follow()
    numbers = compare.train_numbers(got, want)
    assert numbers["train_loss_rel"] <= 2e-6, numbers
    assert numbers["train_grad_norm_gap"] <= 2e-5, numbers
    assert numbers["train_change_norm_gap"] <= 1e-3, numbers
    assert compare.train(got, want, cell.config["limits"], lambda _s: None)


@pytest.mark.parametrize("control", [
    dict(groups_out=(2,)), dict(rows=2), dict(precision="bfloat16")])
def test_a_control_is_not_correct(cell, first_steps_on_four_ranks, control):
    """The configuration's own controls at the tiny size: the reference in
    the program's place with one rank's parts left out of every expert
    layer's sum, with half of each batch, or one precision below the tiny
    cell's float32, reads ``correct`` false."""
    _, follow = first_steps_on_four_ranks
    said = []
    sound = follow()
    limits = cell.config["limits"]
    assert compare.train(sound, sound, limits, said.append)
    assert not compare.train(follow(**control), sound, limits, said.append)
    assert any("OVER THE LIMIT" in s for s in said)


# -- the operations a token, by hand -------------------------------------------


def test_operations_a_token_are_the_count_by_hand():
    parts = opcount_mellum2.forward_parts(PUBLISHED, 8192)
    # Four layers. q and o 2304 x 4096, k and v 2304 x 512.
    assert parts["projections"] == 4 * 2 * 2304 * (2 * 4096 + 2 * 512)
    # A row of a sliding layer sees 1024 columns but for the first 1023
    # rows; of the full layer 4096.5 on average; q k^T and p v, 32 heads.
    band = (8192 * 1024 - 1024 * 1023 / 2) / 8192
    assert parts["cores"] == pytest.approx(
        2 * 2 * 32 * 128 * (3 * band + 4096.5), rel=1e-12)
    assert parts["router"] == 4 * 2 * 2304 * 64
    # All 8 of a token's experts are on the host: 3 matrices of 2304 x 896.
    assert parts["experts"] == 4 * 8 * 2 * 3 * 2304 * 896
    assert parts["head"] == 2 * 2304 * 24576
    total = opcount_mellum2.train_flops_per_token(PUBLISHED, 8192)
    assert total == 3 * sum(parts.values())
    assert 2.37e9 < total < 2.40e9
    # The head's share of the work, which the cut was made to keep near the
    # model's own (PERF.md section 4).
    assert 0.13 < parts["head"] / sum(parts.values()) < 0.16


# -- what the committed manifest lists the cell on -----------------------------

# Every training cell's, the four of Laguna's that read sensibly on this
# cell, the six PR 35 brought.
LISTS_THE_CELL = benchmark_tiny_tree.EVERY_TRAINING_CELL | {
    "moe_ms", "moe_gmm_roofline", "attn_core_ms", "moe_load_max_over_mean",
    "collective_ms", "collective_exposed_ms", "moe_exchange_ms",
    "moe_rank_load_max_over_mean", "mfu_mellum2", "moe_exchange_ici_share"}


def test_the_real_manifest_has_the_four_chip_cell_and_lists_what_it_reads():
    """Found by name, wherever later cells and metrics stand: how many
    four-chip cells there may be is ``test_benchmark_manifest.py``'s
    business, and a later PR may list the cell on more."""
    real = _load("BENCHMARK.json")
    mine = next(w for w in real["workloads"] if w["name"] == CELL)
    assert mine["chips"] == 4 and mine["config"] == "mellum2_12b"
    assert any(c["name"] == "mellum2_12b" for c in real["configs"])
    listed = benchmark_tiny_tree.metrics_listing(real, CELL)
    assert listed >= LISTS_THE_CELL
    # A sequence a chip where the configuration's head lists say otherwise:
    # these four read 120-235 % here (PERF.md section 7); and ``mfu_sparse``
    # is ``mfu_mellum2``'s number a second time.
    assert not listed & {
        "flash_window_fwd_roofline", "flash_window_bwd_roofline",
        "flash_full_fwd_roofline", "flash_full_bwd_roofline", "mfu_sparse"}


def _trace(ops):
    return types.SimpleNamespace(ops=ops)


def test_collective_readers_on_a_hand_written_trace():
    """Two chips, two steps. Chip 0: an all-gather's start and done round a
    fusion that hides 3 of the transfer's 4 us; a reduce-scatter that
    nothing hides. Chip 1: the same, and an all-reduce half hidden."""
    read = lambda name: manifest.load_module(
        f"benchmark/layer_metrics/{name}.py", name).read
    chip0 = [("all-gather-start.1", 0, 1000), ("fusion.7", 1000, 4000),
             ("all-gather-done.1", 3000, 5000),
             ("reduce-scatter.2", 5000, 9000), ("fusion.8", 9000, 10000)]
    chip1 = chip0 + [("all-reduce.3", 10000, 12000),
                     ("fusion.9", 11000, 13000)]
    ctx = {"trace": _trace({0: chip0, 1: chip1}), "window": (0, 20000),
           "run": {"steps": 2}}
    # Chip 0: 1 + 2 + 4 us in collectives, 1 of them under fusion.7; chip 1:
    # 2 more, 1 of them under fusion.9.
    assert read("collective_ms")(ctx) == pytest.approx(
        (7000 + 9000) / 2 / 1e6 / 2)
    assert read("collective_exposed_ms")(ctx) == pytest.approx(
        (6000 + 7000) / 2 / 1e6 / 2)
    for name in ("all-to-all.4", "collective-permute-done", "all_gather.5",
                 "reduce_scatter.88", "psum", "async-collective-start.3",
                 "async-collective-done", "all-reduce-start.12"):
        assert collectives.COLLECTIVE.match(name), name
    for name in ("fusion.12", "all-gather-fusion", "reduce_sum.3",
                 "gather.7", "dynamic_update_slice.2"):
        assert not collectives.COLLECTIVE.match(name), name
    # One chip, no collective: the metric is left out; no trace: the same.
    alone = dict(ctx, trace=_trace({0: [("fusion.7", 0, 10)]}))
    assert read("collective_ms")(alone) is None
    assert read("collective_exposed_ms")(alone) is None
    assert read("collective_ms")(dict(ctx, trace=None)) is None


def test_the_new_readers_find_their_scopes_and_nothing_elsewhere():
    """On hand-written operations: each reader sums its own scope, forward
    and backward; a program without the scope or the gauge (the parent
    commit, another configuration) leaves the metric out and does not
    raise."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    m = "jit(train_step)/jvp(TransformerCausalLm)/layer_1/mlp/shard_map"
    t = "jit(train_step)/transpose(jvp(TransformerCausalLm))/layer_1/mlp/" \
        "shard_map"
    ops = [(f"{m}/moe_exchange_in/all_gather", 0.004),
           (f"{m}/moe_exchange_out/reduce_scatter", 0.006),
           (f"{t}/moe_exchange_out/all_gather", 0.006),
           (f"{t}/moe_exchange_in/reduce_scatter", 0.004),
           (f"{m}/moe_experts/gmm/pallas_call", 0.5),
           (f"{m}/moe_dispatch/sort", 0.1)]
    said = []
    mine = types.SimpleNamespace(config=PUBLISHED)
    peaks = {"bf16_flops_per_s": 197e12, "ici_bits_per_s": 1600e9}
    ctx = {"trace": object(), "scoped_ops": ops, "run": {"steps": 2},
           "cell": mine, "peaks": peaks, "say": said.append}
    read = lambda name: manifest.load_module(
        f"benchmark/layer_metrics/{name}.py", name).read
    assert read("moe_exchange_ms")(ctx) == pytest.approx(10.0)
    assert "moe_exchange_in 4.00, moe_exchange_out 6.00" in said[0]
    bare = dict(ctx, scoped_ops=ops[4:])
    assert read("moe_exchange_ms")(bare) is None
    # The gauge is the program's: 679.5 MB a rank a layer a step at the
    # cell's size (3 other ranks x 8192 tokens x 2304 x (2 + 4) bytes, twice).
    registry = get_tracer().registry
    gauge = registry.gauge("moe.exchange.bytes")
    was = gauge.value()
    try:
        gauge.set(0)
        assert read("moe_exchange_ici_share")(ctx) is None
        sent = 2 * 3 * 8192 * 2304 * 6
        gauge.set(sent)
        assert sent == 679_477_248
        # Four layers' bytes over 10 ms a step over 200 GB/s.
        assert read("moe_exchange_ici_share")(ctx) == pytest.approx(
            100 * 4 * sent / 0.010 / 200e9)
        assert read("moe_exchange_ici_share")(bare) is None
        laguna = types.SimpleNamespace(
            config=_load("benchmark/configs/gpt2_small.json"))
        assert read("moe_exchange_ici_share")(dict(ctx, cell=laguna)) is None
    finally:
        gauge.set(was or 0)
    end = {"cell": mine, "peaks": peaks, "run": {"seq_len": 8192},
           "end_to_end": {"train_tokens_per_s": 100_000.0},
           "device": {"count": 4}, "say": said.append}
    assert read("mfu_mellum2")(end) == pytest.approx(
        100 * opcount_mellum2.train_flops_per_token(PUBLISHED, 8192)
        * 100_000 / (4 * 197e12))
    assert "GFLOP a trained token" in said[-1]
    other = types.SimpleNamespace(
        config=_load("benchmark/configs/laguna_xs2.json"))
    assert read("mfu_mellum2")(dict(end, cell=other)) is None
    assert read("mfu_mellum2")(dict(end, peaks=None)) is None
    # The histogram is the trainer's, from the step metric the exchange
    # adds: its mean, or nothing where no step had one.
    hist = registry.histogram("moe.rank_load_max_over_mean.steps")
    if hist.mean() is None:
        assert read("moe_rank_load_max_over_mean")({}) is None
    hist.observe(1.5)
    assert read("moe_rank_load_max_over_mean")({}) == hist.mean()


# -- the cell rehearsed through run.py; the calibration ------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``benchmark_tiny_tree``'s copy with a tiny ``mellum`` configuration,
    traffic and four-chip cell added beside what is there, on every list
    that names the real cell."""
    dst = benchmark_tiny_tree.build(str(tmp_path_factory.mktemp("mellum2")))
    bench = os.path.join(dst, "benchmark")
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    with open(os.path.join(bench, "configs", "mellum2_tiny.json"), "w") as fh:
        json.dump(dict(PUBLISHED, **TINY_MELLUM, name="mellum2_tiny"), fh,
                  indent=1)
    shutil.copy(os.path.join(bench, "references", "mellum2_12b.py"),
                os.path.join(bench, "references", "mellum2_tiny.py"))
    with open(os.path.join(bench, "traffic", "tiny_train_mellum2.json"),
              "w") as fh:
        json.dump(dict(_load(
            "benchmark/traffic/train_packed_8k_ep4_v24576.json"),
            **TINY_TRAFFIC), fh, indent=1)
    m["configs"].append({
        "name": "mellum2_tiny", "source": "CPU rehearsal",
        "file": "benchmark/configs/mellum2_tiny.json", "reduced": ["tiny"],
        "why": "CPU rehearsal"})
    m["workloads"].append({
        "name": "tiny_mellum2", "config": "mellum2_tiny",
        "traffic": "tiny_train_mellum2", "chips": 4, "why": "CPU rehearsal"})
    benchmark_tiny_tree.list_like(m, "tiny_mellum2", CELL)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh, indent=1)
    return dst


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_mellum2_cell_runs_on_four_devices_and_is_correct(tree, trace):
    p = run_cell(tree, "tiny_mellum2", n_devices=4, trace=trace)
    line = last_line(p)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["device"]["count"] == 4
    wanted = {"train_tokens_per_s", "setup_s"} if not trace else \
        {"step_ms", "compile_s", "input_stall_ms", "dispatch_ms",
         "moe_rank_load_max_over_mean"}
    assert wanted <= set(line["metrics"])
    if trace:
        # The step metric the exchange adds reached the trainer's histogram.
        assert 1.0 <= line["metrics"]["moe_rank_load_max_over_mean"][
            "value"] < 4.0
        # Nothing of the device trace on a CPU; no reader raised.
        for name in ("collective_ms", "collective_exposed_ms",
                     "moe_exchange_ms", "moe_exchange_ici_share",
                     "mfu_mellum2", "blocks_ms", "hbm_peak_gb"):
            assert f"per-layer {name}: nothing to read" in p.stdout
    assert "mesh data=1" in p.stdout
    assert "compile requests inside the window: 0" in p.stdout
    assert "compare train_change_norm_gap" in p.stdout


def test_fewer_devices_than_the_cell_asks_for_fail_at_once(tree):
    p = run_cell(tree, "tiny_mellum2", n_devices=1)
    assert p.returncode != 0 and "cannot measure" in p.stdout
    assert not p.stdout.strip().splitlines()[-1].startswith("{")


def test_calibration_script_reads_sound_steps_and_controls(tree):
    """``calibrate_mellum2_12b.py`` end to end at the tiny size on four
    virtual devices: two seeds' sound readings within the tiny limits, one
    seed's controls far over them."""
    p = subprocess.run(
        [sys.executable, "benchmark/calibrate_mellum2_12b.py", "--workload",
         "tiny_mellum2", "--seeds", "2", "--control-seeds", "1",
         "--controls", "rank_out", "--first-seed-controls", "half_batch"],
        cwd=tree,
        env=benchmark_tiny_tree.env(4), capture_output=True, text=True,
        timeout=900)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    readings = [line for line in p.stdout.splitlines() if "READING" in line]
    kinds = [line.split("READING seed ")[1].split(" ", 1)[1].split(":")[0]
             for line in readings]
    assert kinds == ["sound (within the file's limits)", "control rank_out",
                     "control half_batch",
                     "sound (within the file's limits)"]
    change = lambda line: float(
        line.split("train_change_norm_gap ")[1].split(";")[0])
    limit = TINY_MELLUM["limits"]["train_change_norm_gap"]
    assert change(readings[0]) < limit and change(readings[3]) < limit
    assert change(readings[1]) > limit and change(readings[2]) > limit
