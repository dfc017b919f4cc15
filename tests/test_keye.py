"""Keye-VL-2.0-30B-A3B's language model on the normal path, at a size a test
run can hold: the fourth flash mask, the one that is data, through the three
kernels (interpret mode) and the XLA path against an explicit mask; the
selection's threshold against ``lax.top_k``; the indexer's loss and its
gradient against the plain form; sectioned rotary tables; the tiny twin
through ``build_task`` against the benchmark's plain reference
(``benchmark/references/keye_vl2_30b_a3b.py``) on weights seeded as the
benchmark seeds them, and each control; the shares of eight chips adding up
to the uncut layer; the published widths."""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models.transformer import Rope
from deeplearning_cfn_tpu.ops import attention as A
from deeplearning_cfn_tpu.ops import sparse_index as SI

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


def _close(got, want, what, tol=1e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0), \
        (what, np.max(np.abs(got - want)), np.max(np.abs(want)))


# -- the selection as a mask -------------------------------------------------

def _operands(s, seed=0, b=2, h=4, hk=2, d=16, hi=2, di=8, whole=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32)
    snap = (lambda t: jnp.round(2 * t)) if whole else (lambda t: t)
    return (normal(ks[0], (b, h, s, d)), normal(ks[1], (b, hk, s, d)),
            normal(ks[2], (b, hk, s, d)), snap(normal(ks[3], (b, hi, s, di))),
            snap(normal(ks[4], (b, s, di))), snap(normal(ks[5], (b, s, hi))))


def explicit_selection(scores, topk):
    """``[B, S, S]`` booleans pair by pair: row ``t`` keeps the causal ``s``
    whose score is at or over its ``topk``-th largest causal score."""
    scores = np.asarray(scores)
    keep = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(scores.shape[1]):
            row = scores[b, t, :t + 1]
            tau = -np.inf if t + 1 <= topk else np.sort(row)[-topk]
            keep[b, t, :t + 1] = row >= tau
    return keep


def _plain(q, k, v, seen):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(
        jnp.where(seen[:, None], scores, -1e30), axis=-1), v)


def test_the_packed_mask_is_its_booleans():
    keep = jax.random.bernoulli(jax.random.PRNGKey(0), 0.3, (2, 200, 200))
    words = SI.pack_selection(keep)
    assert words.shape == (2, 200, 128) and words.dtype == jnp.int32
    assert np.array_equal(SI.unpack_selection(words, 200), keep)
    # Column c is bit (c % 4096) // 128 of lane c % 128 of run c // 4096.
    one = np.zeros((1, 1, 4500), bool)
    one[0, 0, 4096 + 130] = True
    words = np.asarray(SI.pack_selection(jnp.asarray(one)))
    assert words.shape == (1, 1, 256) and words[0, 0, 128 + 2] == 1 << 1
    assert np.count_nonzero(words) == 1
    assert SI.packed_width(16384) == 512


# (S, topk, forced plan): S a multiple of the tile and not, topk below, at
# and above S, several K/V blocks (bits from a program_id) and one.
SELECTIONS = [(256, 48, (128, 128, 128, 128)), (200, 64, None),
              (384, 100, (128, 128, 128, 128)), (96, 96, None),
              (96, 128, None), (512, 40, (256, 256, 128, 128))]


@pytest.mark.parametrize("s,topk,plan", SELECTIONS)
def test_flash_kernels_compute_the_selections_mask(s, topk, plan):
    """Forward and the three gradients through the three kernels under a
    selection, against plain attention under the explicit mask; the XLA path
    likewise."""
    q, k, v, qi, ki, w = _operands(s, seed=s)
    keep = explicit_selection(SI.index_scores(qi, ki, w), topk)
    words = SI.pack_selection(jnp.asarray(keep))
    weigh = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))
    want, want_grads = jax.value_and_grad(
        lambda q, k, v: jnp.sum(_plain(q, k, v, keep) * weigh),
        argnums=(0, 1, 2))(q, k, v)

    def kernels(q, k, v):
        out, lse = A._flash_forward(q, k, v, None, True, 0.25,
                                    interpret=True, return_stats=True,
                                    plan=plan, selected=words)
        return out, lse

    out, lse = kernels(q, k, v)
    _close(out, _plain(q, k, v, keep), "forward")
    g = jnp.broadcast_to(weigh, out.shape)
    back = plan and (plan[0], plan[1], 128, 128)
    for got, ref in zip(A._flash_backward(
            q, k, v, out, lse, g, True, 0.25, True, plan=back,
            selected=words), want_grads):
        _close(got, ref, "a kernel's gradient", 2e-5)
    for impl in ("interpret", "reference"):
        value, grads = jax.value_and_grad(
            lambda q, k, v: jnp.sum(A.fused_attention(
                q, k, v, causal=True, implementation=impl,
                selected=words)[0] * weigh), argnums=(0, 1, 2))(q, k, v)
        _close(value, want, impl, 2e-5)
        for got, ref in zip(grads, want_grads):
            _close(got, ref, impl, 2e-5)
    # The row statistics are the logsumexp over what a row keeps.
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 2, axis=1)) / 4
    _close(A.fused_attention(q, k, v, causal=True, implementation="interpret",
                             selected=words)[1],
           jax.nn.logsumexp(jnp.where(keep[:, None], scores, -jnp.inf), -1),
           "lse")


def test_selected_is_exclusive_and_checked():
    q, k, v, *_ = _operands(64)
    words = jnp.zeros((2, 64, 128), jnp.int32)
    call = lambda **kw: A.fused_attention(
        q, k, v, implementation="reference", **{"causal": True,
                                                "selected": words, **kw})
    for kw in (dict(causal=False), dict(window=8),
               dict(layout=A.BlockDiffusion(32, 4), causal=False),
               dict(bias=jnp.zeros((1, 1, 64, 64))),
               dict(selected=words[:, :32]), dict(selected=words[..., :64])):
        with pytest.raises(ValueError, match="selection"):
            call(**kw)
    with pytest.raises(ValueError, match="whole runs"):
        A._flash_forward(q, k, v, None, True, 0.25, interpret=True,
                         plan=(32, 32, 32, 32), selected=words)


def test_a_selected_call_is_counted_and_labelled():
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    registry = get_tracer().registry
    calls = registry.counter("attention.flash.calls")
    q, k, v, qi, ki, w = _operands(256, b=1)
    words, _, _ = SI.select_top_k(qi, ki, w, 32, "interpret")
    assert registry.gauge("attention.selected.topk").value() == 32
    before = calls.value(mask="selected", path="kernel")
    A.fused_attention(q, k, v, causal=True, implementation="interpret",
                      selected=words)
    assert calls.value(mask="selected", path="kernel") == before + 1
    assert A._mask_name(selected=True) == "selected"
    for kernel in ("flash_fwd",):
        # One tile of 256: the causal triangle's, every piece masked by bits.
        assert A._grid_gauges(kernel, selected=True) == (1, 0, 0)
        assert registry.gauge("attention.flash.live_subtile_share").value(
            kernel=kernel, mask="selected") == 1.0


# -- the selection -----------------------------------------------------------

@pytest.mark.parametrize("s,topk", [(200, 64), (640, 100), (96, 96),
                                    (96, 128), (513, 1)])
def test_the_selections_threshold_is_top_ks(s, topk):
    """The kernel's bisection against ``lax.top_k`` and against the explicit
    form, on scores that are whole numbers (exact in any order of summing,
    and full of ties)."""
    _, _, _, qi, ki, w = _operands(s, seed=3, whole=True)
    scores = SI.index_scores(qi, ki, w)
    keep = explicit_selection(scores, topk)
    words, lse, kept = SI.select_top_k(qi, ki, w, topk, "interpret")
    assert np.array_equal(SI.unpack_selection(words, s), keep)
    assert np.array_equal(kept, keep.sum(-1))
    plain = SI.select_from_scores(scores, topk)
    assert np.array_equal(plain[0], keep)
    _close(lse, plain[1], "lse", 1e-6)
    for got, want in zip(SI.select_top_k(qi, ki, w, topk, "reference"),
                         (words, lse, kept)):
        _close(got, want, "the XLA path", 1e-6)
    if topk < s:
        assert int(jnp.sum(jnp.maximum(kept - topk, 0))) > 0  # rows with ties
        assert int(kept.min()) >= 1 and int(kept[:, topk:].min()) >= topk


def test_ordered_keys_order_as_floats():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, jnp.inf],
                    jnp.float32)
    keys = SI._order_keys(x)
    assert np.all(np.diff(np.asarray(keys, np.int64)) >= 0)
    assert np.array_equal(SI._keys_back(keys), x)
    assert int(keys.min()) > SI._INT_MIN


# -- the indexer's loss ------------------------------------------------------

@pytest.mark.parametrize("s,topk", [(200, 64), (640, 100), (96, 128)])
def test_the_indexers_loss_and_gradient_are_the_plain_forms(s, topk):
    q, k, v, qi, ki, w = _operands(s, seed=5)
    words, lse_i, _ = SI.select_top_k(qi, ki, w, topk, "reference")
    keep = SI.unpack_selection(words, s)
    _, lse = A.fused_attention(q, k, v, causal=True,
                               implementation="reference", selected=words)

    def plain(qi, ki, w):
        scores = SI.index_scores(qi, ki, w)
        log_soft = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        p = jnp.where(keep, SI.head_mean_attention(q, k, lse, 0.25), 0.0)
        return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.maximum(
            p, 1e-37)) - log_soft), 0.0))

    want, want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2))(qi, ki, w)
    assert float(want) > 0
    for impl in ("interpret", "reference"):
        got, grads = jax.value_and_grad(
            lambda qi, ki, w: jnp.sum(SI.index_loss(
                qi, ki, w, q, k, lse, words, lse_i, 0.25, impl)),
            argnums=(0, 1, 2))(qi, ki, w)
        _close(got, want, impl, 2e-5)
        for g, ref in zip(grads, want_grads):
            _close(g, ref, impl, 5e-5)
    # Constants to differentiation: q, k and the row statistics.
    for n in (3, 4, 5):
        zero = jax.grad(lambda *a: jnp.sum(SI.index_loss(
            *a, words, lse_i, 0.25, "interpret")), argnums=n)(
                qi, ki, w, q, k, lse)
        assert not np.any(np.asarray(zero))


# -- sectioned rotary positions ----------------------------------------------

def test_sectioned_tables_are_the_plain_form_a_stream_a_section():
    rope = Rope(theta=1e7, sections=(16, 24, 24))
    streams = np.stack([np.arange(40), 3 * np.arange(40) + 1,
                        np.arange(40)[::-1]])
    cos, sin = rope.tables(40, 128, streams)
    inv_freq = 1.0 / 1e7 ** (np.arange(0, 128, 2) / 128)
    for pair in range(64):
        stream = 0 if pair < 16 else 1 if pair < 40 else 2
        angle = streams[stream].astype(np.float64) * inv_freq[pair]
        np.testing.assert_array_equal(cos[:, pair],
                                      np.cos(angle).astype(np.float32))
        np.testing.assert_array_equal(sin[:, pair],
                                      np.sin(angle).astype(np.float32))
    # Three equal streams: a plain Rope's tables, bit for bit.
    plain = Rope(theta=1e7).tables(40, 128)
    for got, want in zip(rope.tables(40, 128, np.tile(np.arange(40), (3, 1))),
                         plain):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(rope.tables(40, 128), plain):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="position streams"):
        rope.tables(40, 128, streams[:2])
    with pytest.raises(ValueError, match="position streams"):
        Rope(theta=1e7, sections=(16, 24)).tables(40, 128, streams[:2])


# -- the program against the reference ---------------------------------------

SEED = 2 ** 31 + 47
LENGTH = 64
TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "vocab_size": 96,
    "layers_held": [0, 1], "experts_held": [0, 8],
    "rope_scaling": {"mrope_section": [2, 3, 3]},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 16},
    "published": {"num_experts": 8, "vocab_size": 96,
                  "num_hidden_layers": 2}}


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH)
    try:
        from harness import manifest, train_steps, weights
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "keye_vl2_30b_a3b.json")) as fh:
        published = json.load(fh)
    reference = manifest.load_module(
        "benchmark/references/keye_vl2_30b_a3b.py", "ref_keye_vl2_30b_a3b")
    return types.SimpleNamespace(
        train_steps=train_steps, weights=weights, reference=reference,
        published=published, sizes=dict(published, **TINY))


def _tiny_cfg(*more):
    from deeplearning_cfn_tpu.config import apply_overrides
    from deeplearning_cfn_tpu.presets import get_preset

    cfg = get_preset("keye_vl2_30b_a3b_lm")
    cfg.model.kwargs = dict(remat_blocks=True)
    apply_overrides(cfg, [
        "model.name=gpt_keye_tiny", "train.dtype=float32",
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


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "references", "keye_vl2_30b_a3b.py")) as fh:
        code = fh.read().split('"""', 2)[2]
    assert "deeplearning_cfn_tpu" not in code and "pallas" not in code


def test_the_step_is_the_references(bench, program):
    """The objective (cross-entropy plus the indexers' loss), both parts
    and every gradient of ``CausalLmTask.loss_fn`` against the reference's;
    the step reports the cross-entropy as its loss."""
    task, params, batch = program
    (objective, aux), grads = jax.value_and_grad(
        lambda p: task.loss_fn(p, {}, batch, None, True), has_aux=True)(params)
    (want, (ce, kl)), want_grads = jax.value_and_grad(
        bench.reference.loss_fn, has_aux=True)(params, batch["tokens"],
                                               bench.sizes)
    _close(objective, want, "objective", 1e-6)
    _close(aux["loss"], ce, "cross-entropy", 1e-6)
    _close(aux["indexer_kl"], kl, "indexer loss", 1e-5)
    assert float(kl) > 1e-3
    flat = bench.weights.flat
    for (name, got), ref in zip(flat(grads).items(),
                                flat(want_grads).values()):
        _close(got, ref, name, 2e-5)
    # 0.435 but for ties: two index heads are both at zero on a quarter of
    # the pairs.
    assert 0.43 < float(aux["sel_kept_share"]) < 0.75
    assert float(aux["moe_rows_held"]) == 2 * LENGTH * 2 * 2
    logits, _ = task.model.apply({"params": params},
                                 batch["tokens"][:, :LENGTH])
    _close(logits, bench.reference.logits_fn(
        params, batch["tokens"][:, :LENGTH], bench.sizes), "logits")


def test_the_references_tiling_changes_nothing(bench, program, monkeypatch):
    """Its pieces of rows are for memory: the objective and the gradients
    are the same in eight pieces as in one."""
    _, params, batch = program
    step = lambda: jax.value_and_grad(bench.reference.loss_fn, has_aux=True)(
        params, batch["tokens"], bench.sizes)
    (want, _), want_grads = step()
    monkeypatch.setattr(bench.reference, "ATTN_ROWS", 8)
    (got, _), grads = step()
    _close(got, want, "objective", 1e-6)
    for g, ref in zip(jax.tree_util.tree_leaves(grads),
                      jax.tree_util.tree_leaves(want_grads)):
        _close(g, ref, "gradient", 1e-5)


def test_the_two_objectives_are_kept_apart(program):
    """The cross-entropy moves nothing of the indexer; the indexers' loss
    nothing outside it."""
    task, params, batch = program

    def part(p, which):
        _, aux = task.loss_fn(p, {}, batch, None, True)
        return aux[which]

    flat = lambda tree: {
        "/".join(str(k.key) for k in path): np.asarray(leaf) for path, leaf
        in jax.tree_util.tree_flatten_with_path(tree)[0]}
    inside = lambda name: "/index_" in name
    ce = flat(jax.grad(part)(params, "loss"))
    kl = flat(jax.grad(part)(params, "indexer_kl"))
    assert sum(map(inside, ce)) == 2 * 5
    for name in ce:
        if inside(name):
            assert not np.any(ce[name]), name
            assert np.any(kl[name]), name
        else:
            assert not np.any(kl[name]), name
    assert np.any(ce["layer_0/self_attn/query/kernel"])


@pytest.fixture(scope="module")
def sound(bench, program):
    _, params, batch = program
    return jax.value_and_grad(bench.reference.loss_fn, has_aux=True)(
        params, batch["tokens"], bench.sizes)


@pytest.mark.parametrize("fault", [
    "no_selection", "topk_1024", "selection_not_causal",
    "indexer_loss_dropped", "indexer_sees_lm_gradient", "an_expert_out"])
def test_each_control_moves_the_reference(bench, program, sound, fault):
    """The faults the calibration runs on the chip are faults: each moves
    the reference's own objective or its gradient at the tiny size."""
    _, params, batch = program
    kw = dict(experts_out=(3,)) if fault == "an_expert_out" \
        else dict(faults=(fault,))
    (objective, _), grads = jax.value_and_grad(
        bench.reference.loss_fn, has_aux=True)(params, batch["tokens"],
                                               bench.sizes, **kw)
    (want, _), want_grads = sound
    norm = lambda tree: float(jnp.sqrt(sum(
        jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree))))
    moved = norm(jax.tree_util.tree_map(jnp.subtract, grads, want_grads))
    assert abs(float(objective) - float(want)) > 1e-6 * float(want) \
        or moved > 1e-4 * norm(want_grads), fault


def test_recomputed_blocks_are_the_blocks_kept(program):
    from deeplearning_cfn_tpu.train.task import build_task

    task, params, batch = program
    kept = build_task(_tiny_cfg("model.kwargs.remat_blocks=false"))
    step = lambda t: jax.value_and_grad(
        lambda p: t.loss_fn(p, {}, batch, None, True)[0])(params)
    for got, want in zip(jax.tree_util.tree_leaves(step(task)),
                         jax.tree_util.tree_leaves(step(kept))):
        _close(got, want, "recomputed", 1e-6)


def test_a_recomputed_block_runs_each_of_its_kernels_once():
    """In interpret mode: the selection, the loss's pass and the forward
    flash kernel once a block in the step's text, kept over the
    recomputation (``models/lm.py``'s policy)."""
    from deeplearning_cfn_tpu.train.task import build_task

    task = build_task(_tiny_cfg("model.kwargs.attention_impl=interpret"))
    shapes = jax.eval_shape(task.init, jax.random.PRNGKey(0))["params"]
    batch = {"tokens": jax.ShapeDtypeStruct((2, LENGTH + 1), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((2, LENGTH), jnp.float32)}
    step = jax.make_jaxpr(jax.grad(
        lambda p, b: task.loss_fn(p, {}, b, None, True)[0]))(shapes, batch)

    def count(jaxpr, name):
        return sum((eqn.primitive.name == "pallas_call"
                    and eqn.params["name"] == name)
                   + sum(count(inner, name) for inner in
                         jax.core.jaxprs_in_params(eqn.params))
                   for eqn in jaxpr.eqns)

    assert [count(step.jaxpr, name) for name in (
        "index_select", "index_loss", "flash_fwd", "flash_bwd_dq",
        "flash_bwd_dkdv")] == [2] * 5


def test_eight_shares_add_up_to_the_uncut_layer(bench):
    """A layer's result from eight shares of 16 experts, attention and the
    indexer counted once (they are whole on every chip), is the uncut
    layer's: in the reference, whose layer is the one the program was held
    to above."""
    sizes = dict(bench.sizes, num_experts=128, num_experts_per_tok=8,
                 layers_held=[0], experts_held=[0, 128],
                 published={"num_experts": 128})
    f, width, s = 64, 32, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 16)
    mat = lambda i, shape: 0.2 * jax.random.normal(ks[i], shape)
    attn = {"query": {"kernel": mat(0, (f, 64))},
            "key": {"kernel": mat(1, (f, 32))},
            "value": {"kernel": mat(2, (f, 32))},
            "attn_out": {"kernel": mat(3, (64, f))},
            "query_norm": {"scale": jnp.ones(16)},
            "key_norm": {"scale": jnp.ones(16)},
            "index_query": {"kernel": mat(4, (f, 16))},
            "index_key": {"kernel": mat(5, (f, 8))},
            "index_key_norm": {"scale": jnp.ones(8), "bias": jnp.zeros(8)},
            "index_weight": {"kernel": mat(6, (f, 2))}}
    w_in, w_out = mat(7, (128, f, 2 * width)), mat(8, (128, width, f))
    norms = {"self_attn_norm": {"scale": jnp.ones(f)},
             "mlp_norm": {"scale": jnp.ones(f)}}
    share = lambda first, n: dict(
        norms, self_attn=attn,
        mlp={"router": {"kernel": mat(9, (f, 128))},
             "experts_in": {"kernel": w_in[first:first + n].reshape(
                 -1, 2 * width)},
             "experts_out": {"kernel": w_out[first:first + n].reshape(-1,
                                                                      f)}})
    x = jax.random.normal(ks[10], (1, s, f))
    mm = lambda a, b: jnp.matmul(a, b, precision="highest")
    layer = lambda first, n: bench.reference._layer(
        mm, x, share(first, n), dict(sizes, experts_held=[first, n]), (), ())
    want, kl = layer(0, 128)
    eighths = [layer(first, 16) for first in range(0, 128, 16)]
    # A share's result is the stream after attention plus its experts'
    # part: the stream is counted once.
    after_attention = eighths[0][0] - (eighths[0][0] - bench.reference._layer(
        mm, x, share(0, 16), dict(sizes, experts_held=[0, 16]), (),
        tuple(range(16)))[0])
    total = after_attention + sum(out - after_attention
                                  for out, _ in eighths)
    _close(total, want, "the shares", 1e-5)
    for _, kl_share in eighths:
        _close(kl_share, kl, "the indexer's loss is every chip's", 1e-6)


# -- the preset ---------------------------------------------------------------

def test_the_preset_is_the_published_model_cut_as_the_file_says(bench):
    from deeplearning_cfn_tpu.presets import get_preset
    from deeplearning_cfn_tpu.train.task import CausalLmTask, build_task

    cfg = get_preset("keye_vl2_30b_a3b_lm")
    task = build_task(cfg)
    assert type(task) is CausalLmTask
    shapes = jax.eval_shape(task.init, jax.random.PRNGKey(0))["params"]
    count = lambda tree: sum(int(np.prod(s.shape))
                             for s in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 659_517_696
    assert count(shapes["layer_0"]) == 96_899_456
    p = bench.published
    attn, sa = shapes["layer_0"]["self_attn"], p["sa_config"]
    indexer = {name: attn[name] for name in attn if name.startswith("index_")}
    assert count(indexer) == 2_097_152 + 131_072 + 32_768 + 128
    assert attn["index_query"]["kernel"].shape == (
        p["hidden_size"], sa["indexer_num_heads"] * sa["indexer_head_dim"])
    assert attn["index_key"]["kernel"].shape == (
        p["hidden_size"], sa["indexer_num_kv_heads"] * sa["indexer_head_dim"])
    assert attn["index_weight"]["kernel"].shape == (p["hidden_size"],
                                                    sa["indexer_num_heads"])
    assert attn["query"]["kernel"].shape == (
        p["hidden_size"], p["num_attention_heads"] * p["head_dim"])
    assert attn["key"]["kernel"].shape == (
        p["hidden_size"], p["num_key_value_heads"] * p["head_dim"])
    assert attn["query_norm"]["scale"].shape == (p["head_dim"],)
    layer = shapes["layer_0"]
    assert layer["mlp"]["router"]["kernel"].shape == (
        p["hidden_size"], p["published"]["num_experts"])
    assert layer["mlp"]["experts_in"]["kernel"].shape == (
        p["num_experts"] * p["hidden_size"], 2 * p["moe_intermediate_size"])
    assert shapes["lm_head"]["kernel"].shape == (p["hidden_size"],
                                                 p["vocab_size"])
    assert sorted(k for k in shapes if k.startswith("layer_")) == [
        f"layer_{i}" for i in p["layers_held"]]


def test_the_tiny_twin_trains_through_fit(tmp_path):
    """``build_task`` -> ``Trainer.fit`` on the CPU: the loss falls, the
    registry has the flash counter, the indexers' loss and what the
    selections kept."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer
    from deeplearning_cfn_tpu.train.run import run_experiment

    cfg = _tiny_cfg("train.steps=6", "train.global_batch=8", "mesh.data=-1",
                    "data.num_train_examples=64", "schedule.name=constant",
                    "schedule.base_lr=0.003", "schedule.warmup_steps=0",
                    f"workdir={tmp_path}")
    registry = get_tracer().registry
    before = registry.histogram("train.indexer_kl.steps").count()
    run_experiment(cfg)
    rows = [json.loads(line) for line in open(
        os.path.join(tmp_path, "keye_vl2_30b_a3b_lm", "metrics.jsonl"))]
    losses = [r["loss"] for r in rows if "loss" in r]
    kls = [r["indexer_kl"] for r in rows if "indexer_kl" in r]
    assert len(losses) >= 6 and all(np.isfinite(losses + kls))
    assert losses[-1] < losses[0] and kls[-1] < kls[0]
    assert registry.counter("attention.flash.calls").value(
        mask="selected", path="xla") > 0
    assert registry.histogram("train.indexer_kl.steps").count() > before
    assert 0.43 < registry.gauge(
        "attention.selected.kept_share").value() < 0.75
    assert registry.gauge("attention.selected.ties").value() >= 0
    assert registry.gauge("attention.selected.topk").value() == 16
