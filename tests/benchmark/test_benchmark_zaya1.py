"""The ``zaya1_8b`` configuration at a size a test run can hold, on the CPU:
the program against the plain reference (logits, loss, every gradient, three
AdamW steps), the two expert-parallel shares adding up to the whole layer
with the router counted once, the cell rehearsed end to end through
``run.py`` in a tiny tree built by adding files, the configuration's own
controls reading ``correct`` false, the operations a token by hand, and what
the committed manifest lists the cell on."""

import json
import os
import shutil
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "benchmark"))

from harness import (compare, manifest, opcount_zaya1,  # noqa: E402
                     train_steps, weights)

import benchmark_tiny_tree  # noqa: E402
from test_benchmark_cells_train import last_line, run_cell  # noqa: E402

# What ``gpt_zaya1_tiny`` (models/lm.py) is, in the source's keys: hidden 64,
# 4 query heads over 2 K/V heads of 16, 8 experts of width 64 one a token of
# which 4 are held, a router of width 16, three layers.
TINY_ZAYA = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads": 4, "moe_intermediate_size": 64,
    "router_hidden_size": 16, "num_hidden_layers": 3, "num_experts": 4,
    "experts_held": [0, 4], "vocab_size": 96,
    "layer_types": ["hybrid"] * 3, "layers_held": [0, 1, 2],
    "published": {"num_experts": 8, "vocab_size": 96,
                  "num_hidden_layers": 3},
    "overrides": [
        "model.name=gpt_zaya1_tiny", "model.kwargs.layers_held=[0,1,2]",
        "model.kwargs.experts_held=[0,4]", "train.dtype=float32",
        "data.synthetic=true", "train.log_every_steps=1",
        "data.use_native_loader=false", "checkpoint.every_steps=0",
        "eval.enabled=false"],
    "precision": "float32",
    # float32 on the CPU against float32: what is left is the order of the
    # sums (sorted rows multiplied group by group against every expert over
    # every token; one einsum against a head at a time). A router that is
    # handed no state, or a held expert left out, moves them a thousandfold.
    "limits": {"train_loss_rel": 1e-5, "train_grad_norm_gap": 1e-4,
               "train_change_norm_gap": 1e-2},
}
TINY_TRAFFIC = {
    "overrides": ["train.global_batch=4", "data.seq_len=32",
                  "data.vocab_size=96", "mesh.data=1",
                  "train.shard_opt_state=false"],
    "num_examples": 32, "trace_steps": 3}
SEED = 2 ** 31 + 17


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell():
    return types.SimpleNamespace(
        name="tiny_zaya1", chips=1,
        config=dict(_load("benchmark/configs/zaya1_8b.json"), **TINY_ZAYA),
        traffic=dict(_load("benchmark/traffic/train_packed_4k_v32896.json"),
                     **TINY_TRAFFIC),
        reference=manifest.load_module(
            "benchmark/references/zaya1_8b.py", "ref_zaya1_8b"))


@pytest.fixture(scope="module")
def program(cell):
    """The task built as the benchmark builds it, seeded weights, a batch."""
    import jax

    from deeplearning_cfn_tpu.train.task import build_task

    cfg = train_steps.build_program_config(cell, SEED)
    task = build_task(cfg)
    shapes = jax.eval_shape(task.init, weights.seed_key(SEED))["params"]
    params = jax.jit(lambda key: weights.make(shapes, key))(
        weights.seed_key(SEED))
    tokens = train_steps.make_tokens(SEED, cell.traffic, 32, 96)[:4]
    return task, params, tokens


# -- the program against the reference -------------------------------------

# Both sides are float32 on the CPU; they differ in the order of their sums.
# 1e-5 of a tensor's largest entry is a few float32 roundings of sums this
# long.
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
    # 4 x 32 tokens, one choice each, three layers; 4 of 8 experts held.
    assert 0 < float(aux["rows_held"]) < 3 * 4 * 32
    assert set(aux) == {"rows_held", "load_max_over_mean"}
    batch = {"tokens": jnp.asarray(tokens),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: task.loss_fn(p, {}, batch, None, True), has_aux=True))(
            params)
    (want_loss, loads), want = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss_fn(p, t, sizes), has_aux=True))(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= TOL * float(want_loss)
    assert {"moe_rows_held", "moe_load_max_over_mean"} <= set(metrics)
    # The balancing biases' next step, from the loads the reference counted:
    # every token chose one of the 8 experts, 16 of them a uniform share.
    for layer, load in loads.items():
        assert float(jnp.sum(load)) == 4 * 32
        _close(metrics["nudges"][layer]["mlp"]["router"]["bias"],
               -sizes["router_balance_rate"]
               * np.minimum(np.asarray(load) / 16 - 1, 1), layer)
    got, want = weights.flat(grads), weights.flat(want)
    assert set(got) == set(want) and len(got) == 89
    silent = [name for name in want if not np.any(np.asarray(want[name]))]
    # No gradient reaches the balancing bias, in either.
    assert silent == [f"layer_{i}/mlp/router/bias" for i in range(3)]
    for name in want:
        _close(got[name], want[name], name)
        assert np.any(np.asarray(got[name])) == (name not in silent), name


def test_three_adamw_steps_match_the_reference(cell, program):
    """The program's optimizer (optax, as ``Trainer`` composes it) and the
    reference's, three steps from the same weights over the same batches:
    every loss, the first gradient's norms as Adam got them, every leaf's
    change. The tiny cell below does the same through ``Trainer.fit``."""
    import jax
    import jax.numpy as jnp
    import optax

    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.state import _nudged

    task, params, _ = program
    cfg = task.cfg
    tokens = train_steps.make_tokens(SEED, cell.traffic, 32, 96)
    batches = [tokens[i * 4:(i + 1) * 4] for i in range(3)]
    tx = build_optimizer(cfg.optimizer, build_schedule(
        cfg.schedule, cfg.train.steps, cfg.train.global_batch, None))

    @jax.jit
    def step(p, opt, toks):
        batch = {"tokens": toks, "loss_mask": jnp.ones((4, 32), jnp.float32)}
        (loss, aux), grads = jax.value_and_grad(
            lambda q: task.loss_fn(q, {}, batch, None, True),
            has_aux=True)(p)
        updates, opt = tx.update(grads, opt, p)
        return _nudged(optax.apply_updates(p, updates), aux["nudges"]), \
            opt, loss

    p, opt, losses = params, tx.init(params), []
    for toks in batches:
        p, opt, loss = step(p, opt, jnp.asarray(toks))
        losses.append(float(loss))
    moved = jax.tree_util.tree_map(
        lambda new, old: float(jnp.sqrt(jnp.sum(jnp.square(new - old)))),
        p, params)
    want = cell.reference.train_steps(
        jax.tree_util.tree_map(lambda a: a + 0, params), batches,
        cell.config, dict(cell.config["optimizer"]))
    for got_loss, want_loss in zip(losses, want["loss"]):
        assert abs(got_loss - want_loss) <= TOL * want_loss
    # Adam's first steps are +-lr whatever the gradient's size, so a leaf's
    # change is its size times the rate: 1e-3 of it is float32's sign noise
    # on the all-but-zero gradients, as in ``compare.driven_leaves``.
    driven = compare.driven_leaves(want)
    gap, leaf = compare.norm_gap(
        {k: weights.flat(moved)[k] for k in driven},
        {k: want["change_norms"][k] for k in driven})
    assert gap <= 1e-3, (gap, leaf)
    # The balancing biases have no gradient and are not among the driven;
    # their controller moved them all the same, and by the same.
    biases = [f"layer_{i}/mlp/router/bias" for i in range(3)]
    assert not set(biases) & set(driven)
    for name in biases:
        assert want["change_norms"][name] > 0.1 * cell.config[
            "router_balance_rate"]
        assert abs(weights.flat(moved)[name] - want["change_norms"][name]) \
            <= 1e-5 * want["change_norms"][name], name


def test_rope_tables_are_the_references(cell):
    from deeplearning_cfn_tpu.models.lm import _ZAYA1_8B, _ZAYA1_TINY
    from deeplearning_cfn_tpu.models.moe import BALANCE_RATE

    published = _load("benchmark/configs/zaya1_8b.json")
    for sizes, config, s in ((_ZAYA1_TINY, cell.config, 32),
                             (_ZAYA1_8B, published, 4096)):
        cos, sin, rot = cell.reference.rope_tables(
            s, config["head_dim"], config["rope_parameters"]["hybrid"])
        assert rot == sizes["rope"].rotary_dim == config["head_dim"] // 2
        # The controller's rate is the program's and the reference's alike.
        assert BALANCE_RATE == config["router_balance_rate"]
        for got, want in zip(sizes["rope"].tables(s, config["head_dim"]),
                             (cos, sin)):
            np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)


# -- the shares add up -------------------------------------------------------


def test_the_two_shares_add_up_to_the_whole_layer(cell, program):
    """8 experts held 4 at a time: the two shares' routed parts are the
    uncut reference's layer output, and each hands on the same router state
    (attention and the router are whole on every chip: counted once)."""
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.models.moe import HeldExpertsMlp, \
        MlpStateRouter

    _, params, _ = program
    p = jax.tree_util.tree_map(np.asarray, params["layer_1"]["mlp"])
    rng = np.random.RandomState(7)
    f, width = 64, 64
    # The seeded layer holds 4 experts; the whole layer's 8 are drawn here.
    whole = dict(p, experts_in={"kernel": rng.normal(
        0, 0.2, (8 * f, 2 * width)).astype(np.float32)},
        experts_out={"kernel": rng.normal(
            0, 0.2, (8 * width, f)).astype(np.float32)})
    x = rng.normal(0, 1, (2, 32, f)).astype(np.float32)
    r = rng.normal(0, 1, (2, 32, 16)).astype(np.float32)
    sizes = dict(cell.config, num_experts=8, experts_held=[0, 8])
    mm = cell.reference._precision.matmul("float32")
    want, want_state, load = cell.reference.moe_layer(
        mm, x.reshape(-1, f), r.reshape(-1, 16), whole, sizes)
    assert load.shape == (8,) and float(load.sum()) == 64

    def share(first):
        cut = dict(whole, experts_in={"kernel": whole["experts_in"]["kernel"][
            first * f:(first + 4) * f]}, experts_out={
                "kernel": whole["experts_out"]["kernel"][
                    first * width:(first + 4) * width]})
        layer = HeldExpertsMlp(
            num_experts=8, mlp_dim=width, held=(first, 4),
            dtype=jnp.float32, router=MlpStateRouter(8, 16, 1e-5))
        out, aux = jax.jit(layer.apply)({"params": cut}, x, r)
        _close(out.reshape(-1, f), cell.reference.moe_layer(
            mm, x.reshape(-1, f), r.reshape(-1, 16), cut,
            dict(sizes, num_experts=4, experts_held=[first, 4]))[0],
            f"share {first}")
        return out, aux

    (low, low_aux), (high, high_aux) = share(0), share(4)
    assert float(low_aux["rows_held"]) + float(high_aux["rows_held"]) == 64
    _close((low + high).reshape(-1, f), want, "the whole layer")
    for aux in (low_aux, high_aux):
        _close(aux["router_state"].reshape(-1, 16), want_state, "the state")


# -- the operations a token, by hand -----------------------------------------


def test_operations_a_token_are_the_count_by_hand():
    config = _load("benchmark/configs/zaya1_8b.json")
    parts = opcount_zaya1.forward_parts(config, 4096)
    # Five layers held. Projections: q 2048 x 1024, k 2048 x 256, two value
    # halves 2048 x 128, o 1024 x 2048, a multiply-add two operations.
    assert parts["projections"] == 5 * 2 * (
        2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048)
    # 2 taps on 1280 channels; 2 taps of a 128 x 128 block on 10 heads.
    assert parts["convolutions"] == 5 * 2 * (2 * 1280 + 2 * 10 * 128 * 128)
    # q k^T and p v, 8 heads of 128, a row sees 2048.5 columns on average.
    assert parts["cores"] == 5 * 2 * 2 * 8 * 128 * 2048.5
    assert parts["router"] == 5 * 2 * (
        2048 * 256 + 256 * 256 + 256 * 256 + 256 * 16)
    # One expert a token, half of them here: 3 matrices of 2048 x 2048.
    assert parts["experts"] == 5 * 0.5 * 2 * 3 * 2048 * 2048
    assert parts["head"] == 2 * 2048 * 32896
    total = opcount_zaya1.train_flops_per_token(config, 4096)
    assert total == 3 * sum(parts.values())
    assert 0.90e9 < total < 0.92e9
    # The head's share of the matmul work, which the cut was made to keep
    # near the model's 42 %.
    assert 0.40 < parts["head"] / sum(parts.values()) < 0.50


# -- the five per-layer metrics PR 31 brought --------------------------------


def test_the_new_readers_find_their_scopes_and_nothing_elsewhere():
    """On hand-written operations: each reader sums its own scope, forward
    and backward; a program without the scope (the parent commit, another
    configuration) leaves the metric out and does not raise."""
    m = "jit(train_step)/jvp(TransformerCausalLm)/layer_1"
    t = "jit(train_step)/transpose(jvp(TransformerCausalLm))/layer_1"
    ops = [(f"{m}/self_attn/cca_mix/conv_depth/mul", 0.004),
           (f"{t}/self_attn/cca_mix/mul", 0.006),
           (f"{m}/self_attn/query/dot_general", 0.5),
           (f"{m}/mlp/moe_router/router/down/dot_general", 0.002),
           (f"{t}/mlp/moe_router/router/hidden_0/dot_general", 0.003),
           (f"{m}/mlp/moe_experts/gmm/pallas_call", 0.5)]
    ctx = {"trace": object(), "scoped_ops": ops, "run": {"steps": 2}}
    read = lambda name: manifest.load_module(
        f"benchmark/layer_metrics/{name}.py", name).read
    assert read("cca_mix_ms")(ctx) == pytest.approx(5.0)
    assert read("moe_router_ms")(ctx) == pytest.approx(2.5)
    bare = dict(ctx, scoped_ops=[ops[2], ops[5]])
    assert read("cca_mix_ms")(bare) is None
    assert read("moe_router_ms")(bare) is None
    laguna = types.SimpleNamespace(
        config=_load("benchmark/configs/laguna_xs2.json"))
    peaks = {"bf16_flops_per_s": 197e12}
    assert read("mfu_zaya1")({"cell": laguna, "peaks": peaks}) is None
    said = []
    mine = types.SimpleNamespace(
        config=_load("benchmark/configs/zaya1_8b.json"))
    share = read("mfu_zaya1")({
        "cell": mine, "peaks": peaks, "run": {"seq_len": 4096},
        "end_to_end": {"train_tokens_per_s": 60_000.0},
        "device": {"count": 1}, "say": said.append})
    assert share == pytest.approx(100 * 0.9057e9 * 60_000 / 197e12, rel=1e-3)
    assert "GFLOP a trained token" in said[0]
    # The flash kernels at 8 query heads over 2 K/V heads of 128, causal over
    # 4096: 4 operations a pair and dimension forward (10 backward) over the
    # 4096 * 4097 / 2 pairs a head sees, the peak the bound. 5 layers took
    # 2 x 1.5 ms forward and 2 x 4.5 ms backward in two steps.
    flash = [(f"{m}/self_attn/core_attention/flash_fwd", 0.0030),
             (f"{t}/self_attn/core_attention/flash_bwd_dkdv", 0.0050),
             (f"{t}/self_attn/core_attention/flash_bwd_dq", 0.0040),
             (f"{m}/self_attn/rope/rope_fwd", 0.5)]
    pairs = 2 * 8 * 4096 * 4097 / 2 * 128
    kernels = {"cell": mine, "peaks": {"bf16_flops_per_s": 197e12,
                                        "hbm_bytes_per_s": 819e9},
               "trace": object(), "scoped_ops": flash, "say": said.append,
               "run": {"steps": 2, "global_batch": 2, "seq_len": 4096}}
    assert read("flash_hybrid_fwd_roofline")(kernels) == pytest.approx(
        100 * 5 * 4 * pairs / 197e12 / 0.0015, rel=1e-6)
    assert read("flash_hybrid_bwd_roofline")(kernels) == pytest.approx(
        100 * 5 * 10 * pairs / 197e12 / 0.0045, rel=1e-6)
    assert read("flash_hybrid_fwd_roofline")(
        dict(kernels, scoped_ops=[flash[3]])) is None
    assert read("flash_hybrid_fwd_roofline")(
        dict(kernels, cell=laguna)) is None


# -- the cell rehearsed through run.py; the controls -------------------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``benchmark_tiny_tree``'s copy with a tiny ``zaya`` configuration,
    traffic and cell added beside what is there, on every list that names
    the real cell."""
    dst = benchmark_tiny_tree.build(str(tmp_path_factory.mktemp("zaya1")))
    bench = os.path.join(dst, "benchmark")
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    with open(os.path.join(bench, "configs", "zaya1_tiny.json"), "w") as fh:
        json.dump(dict(_load("benchmark/configs/zaya1_8b.json"),
                       **TINY_ZAYA, name="zaya1_tiny"), fh, indent=1)
    shutil.copy(os.path.join(bench, "references", "zaya1_8b.py"),
                os.path.join(bench, "references", "zaya1_tiny.py"))
    with open(os.path.join(bench, "traffic", "tiny_train_zaya1.json"),
              "w") as fh:
        json.dump(dict(_load("benchmark/traffic/train_packed_4k_v32896.json"),
                       **TINY_TRAFFIC), fh, indent=1)
    m["configs"].append({
        "name": "zaya1_tiny", "source": "CPU rehearsal",
        "file": "benchmark/configs/zaya1_tiny.json", "reduced": ["tiny"],
        "why": "CPU rehearsal"})
    m["workloads"].append({
        "name": "tiny_zaya1", "config": "zaya1_tiny",
        "traffic": "tiny_train_zaya1", "chips": 1, "why": "CPU rehearsal"})
    benchmark_tiny_tree.list_like(m, "tiny_zaya1", "zaya1_8b_train_4k")
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh, indent=1)
    return dst


# Every training cell's, the three of Laguna's that read sensibly here, the
# five PR 31 brought.
LISTS_THE_CELL = benchmark_tiny_tree.EVERY_TRAINING_CELL | {
    "moe_ms", "attn_core_ms", "moe_load_max_over_mean",
    "cca_mix_ms", "moe_router_ms", "mfu_zaya1", "flash_hybrid_fwd_roofline",
    "flash_hybrid_bwd_roofline"}


def test_the_real_manifest_lists_the_cell_on_what_it_reads():
    """Found by name; a later PR may list the cell on more."""
    real = _load("BENCHMARK.json")
    name = "zaya1_8b_train_4k"
    mine = next(w for w in real["workloads"] if w["name"] == name)
    assert mine["chips"] == 1 and mine["config"] == "zaya1_8b"
    listed = benchmark_tiny_tree.metrics_listing(real, name)
    assert listed >= LISTS_THE_CELL
    # These ask the configuration for ``mlp_layer_types`` and for sliding or
    # full layers and find nothing in one whose layers are all ``hybrid``,
    # and ``mfu_sparse`` raises on it (PERF.md section 3).
    assert not listed & {
        "moe_gmm_roofline", "mfu_sparse", "flash_window_fwd_roofline",
        "flash_window_bwd_roofline", "flash_full_fwd_roofline",
        "flash_full_bwd_roofline"}


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_zaya1_cell_runs_and_is_correct(tree, trace):
    p = run_cell(tree, "tiny_zaya1", trace=trace)
    line = last_line(p)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    wanted = {"train_tokens_per_s", "setup_s"} if not trace else \
        {"step_ms", "compile_s", "input_stall_ms", "dispatch_ms"}
    assert wanted <= set(line["metrics"])
    if trace:
        # Nothing of the device trace on a CPU; no reader raised.
        for name in ("cca_mix_ms", "moe_router_ms", "mfu_zaya1",
                     "flash_hybrid_fwd_roofline", "flash_hybrid_bwd_roofline",
                     "blocks_ms", "head_loss_ms", "hbm_peak_gb"):
            assert f"per-layer {name}: nothing to read" in p.stdout
    assert "compile requests inside the window: 0" in p.stdout
    assert "compare train_change_norm_gap" in p.stdout


@pytest.mark.parametrize("control", [
    dict(carry_state=False), dict(experts=3), dict(rows=2)])
def test_a_control_is_not_correct(cell, program, control):
    """The configuration's own controls: the reference in the program's
    place with no router handed a state, with 3 of its 4 held experts, with
    half of each batch, reads ``correct`` false. (That the program's own
    steps read true is the rehearsal above.)"""
    import jax

    _, params, _ = program
    said = []
    tokens = train_steps.make_tokens(SEED, cell.traffic, 32, 96)
    batches = [tokens[i * 4:(i + 1) * 4] for i in range(3)]
    hp = dict(cell.config["optimizer"])
    follow = lambda **kw: cell.reference.train_steps(
        jax.tree_util.tree_map(lambda a: a + 0, params), batches,
        cell.config, hp, **kw)   # the reference consumes what it is given
    sound = follow()
    limits = cell.config["limits"]
    assert compare.train(sound, sound, limits, said.append)
    assert not compare.train(follow(**control), sound, limits, said.append)
    assert any("OVER THE LIMIT" in s for s in said)


def test_calibration_script_reads_its_controls_in_the_tiny_tree(tree):
    """``calibrate_zaya1_8b.py`` end to end at the tiny size: it finds the
    cell, runs the reference sound and under two of its controls, and prints
    a reading for each, both far over the tiny cell's limits."""
    import subprocess

    p = subprocess.run(
        [sys.executable, "benchmark/calibrate_zaya1_8b.py", "--workload",
         "tiny_zaya1", "--seeds", "1", "--controls",
         "state_dropped,one_expert_out"], cwd=tree,
        env=benchmark_tiny_tree.env(), capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    readings = [line for line in p.stdout.splitlines() if "READING" in line]
    assert [line.split("control ")[1].split(":")[0] for line in readings] \
        == ["state_dropped", "one_expert_out"]
    for line in readings:
        change = float(line.split("train_change_norm_gap ")[1].split(";")[0])
        assert change > TINY_ZAYA["limits"]["train_change_norm_gap"], line
