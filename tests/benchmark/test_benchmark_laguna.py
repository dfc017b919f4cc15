"""The ``laguna_xs2`` configuration at a size a test run can hold, on the CPU:
the program against the plain reference (logits, loss, every gradient), the
expert-parallel shares adding up to the whole layer, a routing no static
buffer was sized for, the cell rehearsed end to end through ``run.py`` in a
tiny tree built by adding files, what the committed manifest lists the cell
on, and the control that leaves one held expert out reading ``correct``
false."""

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

from harness import compare, manifest, train_steps, weights  # noqa: E402

import benchmark_tiny_tree  # noqa: E402
from test_benchmark_cells_train import last_line, run_cell  # noqa: E402

# What ``gpt_laguna_tiny`` (models/lm.py) is, in the source's keys: hidden 64,
# head size 16, 2 K/V heads under 4 (full) and 6 (sliding) query heads,
# window 8 at S = 32, 16 experts 4 a token of which 4 are held, layers dense,
# sliding, full.
TINY_LAGUNA = {
    "hidden_size": 64, "head_dim": 16, "num_key_value_heads": 2,
    "num_attention_heads": 4, "intermediate_size": 128,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "num_hidden_layers": 3, "num_experts": 4, "experts_held": [0, 4],
    "num_experts_per_tok": 4, "vocab_size": 96, "sliding_window": 8,
    "layer_types": ["full_attention", "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse"],
    "num_attention_heads_per_layer": [4, 6, 4],
    "layers_held": [0, 1, 2],
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 2, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "overrides": [
        "model.name=gpt_laguna_tiny", "model.kwargs.layers_held=[0,1,2]",
        "model.kwargs.experts_held=[0,4]", "train.dtype=float32",
        "data.synthetic=true", "train.log_every_steps=1",
        "data.use_native_loader=false", "checkpoint.every_steps=0",
        "eval.enabled=false"],
    "precision": "float32",
    # float32 on the CPU against float32: what is left is the order of the
    # sums (rows sorted and multiplied group by group against every expert
    # over every token). A held expert left out moves them a thousandfold.
    "limits": {"train_loss_rel": 1e-5, "train_grad_norm_gap": 1e-4,
               "train_change_norm_gap": 1e-2},
}
TINY_TRAFFIC = {
    "overrides": ["train.global_batch=4", "data.seq_len=32",
                  "data.vocab_size=96", "mesh.data=1",
                  "train.shard_opt_state=false"],
    "num_examples": 32, "trace_steps": 3}


def _load(relpath):
    with open(os.path.join(REPO, relpath)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def cell():
    return types.SimpleNamespace(
        name="tiny_laguna", chips=1,
        config=dict(_load("benchmark/configs/laguna_xs2.json"),
                    **TINY_LAGUNA),
        traffic=dict(_load("benchmark/traffic/train_packed_4k.json"),
                     **TINY_TRAFFIC),
        reference=manifest.load_module(
            "benchmark/references/laguna_xs2.py", "ref_laguna_xs2"))


@pytest.fixture(scope="module")
def program(cell):
    """The task built as the benchmark builds it, seeded weights, a batch."""
    import jax

    from deeplearning_cfn_tpu.train.task import build_task

    seed = 2 ** 31 + 17
    cfg = train_steps.build_program_config(cell, seed)
    task = build_task(cfg)
    shapes = jax.eval_shape(task.init, weights.seed_key(seed))["params"]
    params = jax.jit(lambda key: weights.make(shapes, key))(
        weights.seed_key(seed))
    tokens = train_steps.make_tokens(seed, cell.traffic, 32, 96)[:4]
    return task, params, tokens


# -- (a) the program against the reference --------------------------------

# Both sides are float32 on the CPU; they differ in the order of their sums
# (the kernels' reference path and one group of heads at a time; sorted rows
# group by group and every expert over every token). 1e-5 of a tensor's
# largest entry is a few float32 roundings of sums this long.
TOL = 1e-5


def _close(got, want, what):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= TOL * max(np.max(np.abs(want)), 1.0), \
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
    assert 0 < float(aux["rows_held"]) < 2 * 4 * 32 * 4
    batch = {"tokens": jnp.asarray(tokens),
             "loss_mask": jnp.ones((4, 32), jnp.float32)}
    (loss, metrics), grads = jax.jit(jax.value_and_grad(
        lambda p: task.loss_fn(p, {}, batch, None, True), has_aux=True))(
            params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p, t: ref.loss_fn(p, t, sizes)))(params, tokens)
    assert abs(float(loss) - float(want_loss)) <= TOL * float(want_loss)
    assert {"moe_rows_held", "moe_load_max_over_mean"} <= set(metrics)
    got, want = weights.flat(grads), weights.flat(want)
    assert set(got) == set(want) and len(got) == 36
    for name in want:
        _close(got[name], want[name], name)


def test_rope_tables_are_the_references(cell):
    from deeplearning_cfn_tpu.models.lm import _LAGUNA_TINY, _LAGUNA_XS2

    published = _load("benchmark/configs/laguna_xs2.json")
    for sizes, config, s in ((_LAGUNA_TINY, cell.config, 32),
                             (_LAGUNA_XS2, published, 4096)):
        for kind in ("full", "sliding"):
            rope = sizes[f"{kind}_rope"]
            cos, sin, rot = cell.reference.rope_tables(
                s, config["head_dim"],
                config["rope_parameters"][f"{kind}_attention"])
            assert rot == (rope.rotary_dim or config["head_dim"])
            for got, want in zip(rope.tables(s, config["head_dim"]),
                                 (cos, sin)):
                np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)


# -- (b) the shares add up; (d) dropless ----------------------------------


def _whole_layer(cell, seed=5, tokens=64):
    """The expert layer of all 16 experts: its parameters under the
    reference's names, ``sizes`` that hold them all, an input."""
    rng = np.random.RandomState(seed)
    f, m, e = 64, 32, 16
    p = {"router": {"kernel": rng.normal(0, 0.3, (f, e))},
         "experts_in": {"kernel": rng.normal(0, 0.2, (e * f, 2 * m))},
         "experts_out": {"kernel": rng.normal(0, 0.2, (e * m, f))},
         "shared": {"mlp_in": {"kernel": rng.normal(0, 0.2, (f, 2 * m))},
                    "mlp_out": {"kernel": rng.normal(0, 0.2, (m, f))}}}
    import jax

    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32), p)
    sizes = dict(cell.config, num_experts=e, experts_held=[0, e])
    x = rng.normal(0, 1, (2, tokens // 2, f)).astype(np.float32)
    return p, sizes, x


def _share(p, first, count, f=64, m=32):
    return dict(p, experts_in={"kernel": p["experts_in"]["kernel"][
        first * f:(first + count) * f]},
        experts_out={"kernel": p["experts_out"]["kernel"][
            first * m:(first + count) * m]})


def _held_layer(first, count, shared_dim=32, **kw):
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.models.moe import HeldExpertsMlp

    return HeldExpertsMlp(num_experts=16, mlp_dim=32, top_k=4,
                          held=(first, count), routed_scale=2.5,
                          shared_dim=shared_dim, dtype=jnp.float32, **kw)


def test_the_shares_add_up_to_the_whole_layer(cell):
    """16 experts held 4 at a time: the four shares' routed parts, plus the
    shared expert counted once, are the uncut reference's layer output."""
    from deeplearning_cfn_tpu.models.transformer import GatedMlp
    import jax.numpy as jnp

    p, sizes, x = _whole_layer(cell)
    mm = cell.reference._precision.matmul("float32")
    whole = cell.reference.moe_layer(mm, x.reshape(-1, 64), p, sizes)
    routed = {k: v for k, v in p.items() if k != "shared"}
    total, rows = 0.0, 0.0
    import jax

    for first in (0, 4, 8, 12):
        part, aux = jax.jit(_held_layer(first, 4, shared_dim=0).apply)(
            {"params": _share(routed, first, 4)}, x)
        total, rows = total + part, rows + float(aux["rows_held"])
        # One share alone is the reference's share.
        _close(part.reshape(-1, 64), cell.reference.moe_layer(
            mm, x.reshape(-1, 64), _share(p, first, 4),
            dict(sizes, num_experts=4, experts_held=[first, 4]),
            shared=False), f"share {first}")
    assert rows == 64 * 4  # every (token, choice) is held by one share
    shared = GatedMlp(32, jnp.float32).apply({"params": p["shared"]}, x)
    _close((total + shared).reshape(-1, 64), whole, "the whole layer")
    # And the layer that holds them all, shared expert and all.
    all_held, _ = jax.jit(_held_layer(0, 0).apply)({"params": p}, x)
    _close(all_held.reshape(-1, 64), whole, "all 16 held")


@pytest.mark.parametrize("forced,rows_held,second_buffer", [
    ([1], 64, False),              # every token's first choice is expert 1
    ([0, 1, 2, 3], 256, True),     # all four choices are held: every pair
])
def test_dropless_whatever_the_routing(cell, forced, rows_held,
                                       second_buffer):
    """A router forced to send every token to held experts: more rows than
    a uniform router's buffer holds, and still the reference's result, in
    the value and in the gradient."""
    import jax

    p, sizes, x = _whole_layer(cell)
    x[..., 0] = 3.0                       # a feature every token has
    kernel = p["router"]["kernel"].copy()
    kernel[0] = -3.0
    kernel[0, forced] = 3.0
    p = dict(p, router={"kernel": kernel})
    share = _share(p, 0, 4)
    sizes = dict(sizes, num_experts=4, experts_held=[0, 4])
    mm = cell.reference._precision.matmul("float32")
    layer = _held_layer(0, 4)

    @jax.jit
    def program(params):
        out, aux = layer.apply({"params": params}, x)
        return out.reshape(-1, 64), aux

    (out, aux) = program(share)
    assert float(aux["rows_held"]) >= rows_held
    # 64 tokens x 4 choices, 4 of 16 held: the usual buffer is 128 rows.
    assert (float(aux["rows_held"]) > 128) == second_buffer
    _close(out, cell.reference.moe_layer(mm, x.reshape(-1, 64), share, sizes),
           "forced routing")
    weigh = np.random.RandomState(9).normal(0, 1, out.shape).astype(np.float32)
    got = jax.grad(lambda q: (program(q)[0] * weigh).sum())(share)
    want = jax.grad(lambda q: (cell.reference.moe_layer(
        mm, x.reshape(-1, 64), q, sizes) * weigh).sum())(share)
    for name, g in weights.flat(want).items():
        _close(weights.flat(got)[name], g, name)


# -- (e) the cell rehearsed through run.py; (f) the control -----------------


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """``benchmark_tiny_tree``'s copy with a tiny ``laguna`` configuration,
    traffic and cell added beside what is there, as it adds ``gpt2_tiny``."""
    dst = benchmark_tiny_tree.build(str(tmp_path_factory.mktemp("laguna")))
    bench = os.path.join(dst, "benchmark")
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    with open(os.path.join(bench, "configs", "laguna_tiny.json"), "w") as fh:
        json.dump(dict(_load("benchmark/configs/laguna_xs2.json"),
                       **TINY_LAGUNA, name="laguna_tiny"), fh, indent=1)
    shutil.copy(os.path.join(bench, "references", "laguna_xs2.py"),
                os.path.join(bench, "references", "laguna_tiny.py"))
    with open(os.path.join(bench, "traffic", "tiny_train_4k.json"),
              "w") as fh:
        json.dump(dict(_load("benchmark/traffic/train_packed_4k.json"),
                       **TINY_TRAFFIC), fh, indent=1)
    m["configs"].append({
        "name": "laguna_tiny", "source": "CPU rehearsal",
        "file": "benchmark/configs/laguna_tiny.json", "reduced": ["tiny"],
        "why": "CPU rehearsal"})
    m["workloads"].append({
        "name": "tiny_laguna", "config": "laguna_tiny",
        "traffic": "tiny_train_4k", "chips": 1, "why": "CPU rehearsal"})
    benchmark_tiny_tree.list_like(m, "tiny_laguna", "laguna_xs2_train_4k")
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(m, fh, indent=1)
    return dst


def test_the_tiny_tree_keeps_every_metric_through_like(tree):
    """Every per-layer metric a real cell lists reaches the tiny cell built
    `like` it and no other does, each has its reader in the copy, and the
    copy's list is the real one's, name for name: nothing is appended twice."""
    with open(os.path.join(tree, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    real = {metric["name"]: metric for metric in _load(
        "BENCHMARK.json")["per_layer"]}
    assert [metric["name"] for metric in m["per_layer"]] == list(real)
    for metric in m["per_layer"]:
        was = real[metric["name"]]
        for real_cell, tiny in (("gpt2_small_train", "tiny_train"),
                                ("laguna_xs2_train_4k", "tiny_laguna")):
            assert (tiny in metric.get("workloads", ())) \
                == (real_cell in was.get("workloads", ()))
        assert metric["moves"] in ("train_tokens_per_s", "setup_s")
        assert os.path.exists(os.path.join(
            tree, "benchmark", "layer_metrics", metric["name"] + ".py"))


# Every training cell's, and the nine PR 26 brought.
LISTS_THE_CELL = benchmark_tiny_tree.EVERY_TRAINING_CELL | {
    "moe_ms", "moe_gmm_roofline", "flash_window_fwd_roofline",
    "flash_window_bwd_roofline", "attn_core_ms", "moe_load_max_over_mean",
    "flash_full_fwd_roofline", "flash_full_bwd_roofline", "mfu_sparse"}


def test_the_real_manifest_lists_the_cell_on_what_it_reads():
    """Found by name; a later PR may list the cell on more."""
    real = _load("BENCHMARK.json")
    name = "laguna_xs2_train_4k"
    mine = next(w for w in real["workloads"] if w["name"] == name)
    assert mine["chips"] == 1 and mine["config"] == "laguna_xs2"
    listed = benchmark_tiny_tree.metrics_listing(real, name)
    assert listed >= LISTS_THE_CELL
    # What counts GPT-2's block and its dropout, and what times the
    # pipeline's thread (PERF.md section 3).
    assert not listed & {"mfu", "flash_fwd_roofline", "flash_bwd_roofline",
                         "dropout_ms", "input_wait_ms"}


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_laguna_cell_runs_and_is_correct(tree, trace):
    p = run_cell(tree, "tiny_laguna", trace=trace)
    line = last_line(p)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    wanted = {"train_tokens_per_s", "setup_s"} if not trace else \
        {"step_ms", "compile_s", "moe_load_max_over_mean"}
    assert wanted <= set(line["metrics"])
    if trace:
        # Nothing of the device trace on a CPU; no reader raised.
        assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] < 4
        for name in ("moe_ms", "moe_gmm_roofline", "attn_core_ms",
                     "flash_window_fwd_roofline", "flash_full_bwd_roofline",
                     "mfu_sparse"):
            assert f"per-layer {name}: nothing to read" in p.stdout
    assert "compile requests inside the window: 0" in p.stdout


def test_a_held_expert_left_out_is_not_correct(cell, program):
    """The configuration's own control: the reference computing 3 of its 4
    held experts in the program's place reads ``correct`` false. (That the
    program's own steps read true is the rehearsal above.)"""
    import jax

    _, params, _ = program
    said = []
    tokens = train_steps.make_tokens(2 ** 31 + 17, cell.traffic, 32, 96)
    batches = [tokens[i * 4:(i + 1) * 4] for i in range(3)]
    hp = dict(cell.config["optimizer"])
    follow = lambda **kw: cell.reference.train_steps(
        jax.tree_util.tree_map(lambda a: a + 0, params), batches,
        cell.config, hp, **kw)   # the reference consumes what it is given
    sound = follow()
    limits = cell.config["limits"]
    assert compare.train(sound, sound, limits, said.append)
    assert not compare.train(follow(experts=3), sound, limits, said.append)
    assert any("OVER THE LIMIT" in s for s in said)
