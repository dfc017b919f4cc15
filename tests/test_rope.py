"""The rotary-position kernel (ops/rope.py) in Pallas interpreter mode against
the plain form (models/transformer.py:apply_rope and the transpose), at both
of Laguna's rope settings; when it engages; and what it counts. Then the same
kernel with a block's ``qk_norm`` inside it (SDAR's and Keye's blocks) against
the norm by XLA before it."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import build_model
from deeplearning_cfn_tpu.models.lm import (
    _KEYE_VL2_30B_A3B, _LAGUNA_TINY, _LAGUNA_XS2, _SDAR_30B_A3B)
from deeplearning_cfn_tpu.models.transformer import (
    BlockStyle, MultiHeadAttention, RMSNorm, Rope, apply_rope, rms_norm,
    rope_to_heads)
from deeplearning_cfn_tpu.obs.trace import get_tracer
from deeplearning_cfn_tpu.ops import rope as R



class _ShortTables(Rope):
    """The same rope with cos and sin rounded to bfloat16's eight bits, so
    that a bf16 value times one is exact in float32. The CPU's compiler fuses
    a multiply and an add into one rounding where it likes, the kernel's body
    and the plain form are fused differently, and a few results in a million
    then differ by a bf16 step; with exact products a fused multiply-add is
    the multiply and the add. (The chip's vector unit has no fused
    multiply-add: tools/rope_sweep.py prints ``unequal`` with the real
    tables.)"""

    def tables(self, seq_len, head_dim, positions=None):
        return tuple(t.astype(jnp.bfloat16).astype(np.float32)
                     for t in super().tables(seq_len, head_dim, positions))


REAL = {"sliding": _LAGUNA_XS2["sliding_rope"],    # rot = 128 of 128
        "full": _LAGUNA_XS2["full_rope"]}          # rot = 64, YaRN, factor
ROPES = {name: _ShortTables(**dataclasses.asdict(rope))
         for name, rope in REAL.items()}
# [B, S, H, D]: q-like (three head blocks of 8, two row blocks), k-like.
SHAPES = {"q": (2, 1024, 24, 128), "k": (2, 512, 8, 128)}


def _x(shape, seed=0, dtype=jnp.bfloat16):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _plain(rope):
    # Under jit, as the step runs it: run operation by operation the CPU
    # rounds each product, compiled it fuses them into multiply-adds.
    return jax.jit(lambda x: apply_rope(x, rope).transpose(0, 2, 1, 3))


def _kernel(rope):
    return jax.jit(lambda x: rope_to_heads(x, rope, "interpret"))


def _calls():
    counter = get_tracer().registry.counter("attention.rope.calls")
    return {path: counter.value(path=path) for path in ("kernel", "xla")}


def _gained(before):
    return {path: n - before[path] for path, n in _calls().items()}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("rope", sorted(ROPES))
def test_kernel_is_the_plain_form_bit_for_bit(rope, shape):
    x = _x(SHAPES[shape])
    before = _calls()
    got = _kernel(ROPES[rope])(x)
    assert _gained(before) == {"kernel": 1, "xla": 0}
    want = _plain(ROPES[rope])(x)
    assert got.dtype == want.dtype == jnp.bfloat16
    assert got.shape == want.shape
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("rope", sorted(REAL))
def test_kernel_with_the_real_tables(rope):
    """Laguna's own tables: equal but for the few results the CPU's fused
    multiply-adds move, and those by one bf16 step (or, where the two
    products cancel, by a float32 rounding of one of them)."""
    x = _x(SHAPES["q"])
    got = np.asarray(_kernel(REAL[rope])(x), np.float32)
    want = np.asarray(_plain(REAL[rope])(x), np.float32)
    assert np.mean(got != want) < 1e-4
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=1e-6)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("rope", sorted(ROPES))
def test_kernel_vjp_is_the_plain_forms(rope, shape):
    """The backward kernel reads [B,H,S,D] and writes the projection's layout,
    turned by the negative angle: what ``jax.vjp`` makes of the plain form."""
    x = _x(SHAPES[shape])
    b, s, h, d = x.shape
    g = _x((b, h, s, d), seed=1)
    before = _calls()
    got = jax.jit(lambda x, g: jax.vjp(_kernel(ROPES[rope]), x)[1](g)[0])(x, g)
    # Counted where the forward call is traced, not again for its backward.
    assert _gained(before) == {"kernel": 1, "xla": 0}
    want = jax.jit(lambda x, g: jax.vjp(_plain(ROPES[rope]), x)[1](g)[0])(x, g)
    assert got.shape == want.shape == x.shape and got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("rope", sorted(ROPES))
def test_backward_of_forward_is_the_factor_squared(rope):
    """The rotation's transpose undoes it: lanes that turn come back times
    ``attention_factor`` squared, the others as they were, to bf16 rounding
    (two of them)."""
    x = _x(SHAPES["k"])
    turn = _kernel(REAL[rope])
    back = np.asarray(jax.vjp(turn, x)[1](turn(x))[0], np.float32)
    rot = REAL[rope].rotary_dim or x.shape[-1]
    want = np.array(x, np.float32)
    want[..., :rot] *= REAL[rope].attention_factor ** 2
    assert np.linalg.norm(back - want) < 2 ** -7 * np.linalg.norm(want)
    np.testing.assert_array_equal(back[..., rot:], want[..., rot:])


@pytest.mark.parametrize("rope", sorted(ROPES))
@pytest.mark.parametrize("blocks", [(256, 1), (128, 4), (512, 16)])
def test_blocks_do_not_change_the_result(rope, blocks):
    x = _x(SHAPES["k"])
    b, s, h, d = x.shape
    cos, sin = ROPES[rope].tables(s, d)
    got = jax.jit(lambda x: R.rotate_to_heads(
        x, cos, sin, d, interpret=True, blocks=blocks))(x.reshape(b, s, h * d))
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(_plain(ROPES[rope])(x), np.float32))


def test_spread_tables():
    cos, sin = ROPES["full"].tables(16, 128)
    assert cos.shape == sin.shape == (16, 32)
    cos_full, sin_signed = R.spread_tables(cos, sin, 128)
    assert cos_full.shape == sin_signed.shape == (16, 128)
    assert cos_full.dtype == sin_signed.dtype == np.float32
    np.testing.assert_array_equal(cos_full[:, :32], cos)
    np.testing.assert_array_equal(cos_full[:, 32:64], cos)
    np.testing.assert_array_equal(cos_full[:, 64:], 1.0)
    np.testing.assert_array_equal(sin_signed[:, :32], -sin)
    np.testing.assert_array_equal(sin_signed[:, 32:64], sin)
    np.testing.assert_array_equal(sin_signed[:, 64:], 0.0)
    whole = R.spread_tables(*ROPES["sliding"].tables(16, 128), 128)
    assert whole[0].shape == (16, 128)


@pytest.mark.parametrize("implementation,seq_len,head_dim,want", [
    ("interpret", 4096, 128, (True, True)),
    ("pallas", 4096, 128, (True, False)),
    ("pallas", 512, 256, (True, False)),
    # The CPU of the tests is no TPU, and "reference" rules kernels out.
    ("auto", 4096, 128, (False, False)),
    ("reference", 4096, 128, (False, False)),
    # The tiny Laguna model's head; a head that is not whole lane tiles; an
    # S that the row block does not divide.
    ("interpret", 512, 16, (False, True)),
    ("pallas", 4096, 64, (False, False)),
    ("pallas", 4096, 192, (False, False)),
    ("interpret", 4096 + 256, 128, (False, True)),
    ("interpret", 32, 128, (False, True)),
])
def test_when_the_kernel_engages(implementation, seq_len, head_dim, want):
    assert R.kernel_engages(implementation, seq_len, head_dim) == want


def test_auto_takes_the_kernel_on_a_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert R.kernel_engages("auto", 4096, 128) == (True, False)
    assert R.kernel_engages("auto", 4096, 16) == (False, False)
    assert R.kernel_engages("reference", 4096, 128) == (False, False)


@pytest.mark.parametrize("shape,rope", [
    ((2, 32, 6, 16), _LAGUNA_TINY["sliding_rope"]),   # head size 16
    ((2, 32, 4, 16), _LAGUNA_TINY["full_rope"]),
    ((1, 768 + 8, 2, 128), ROPES["sliding"]),         # 512 does not divide S
    ((1, 256, 2, 128), ROPES["full"]),
])
def test_fallback_is_the_plain_form(shape, rope):
    x = _x(shape, dtype=jnp.float32)
    before = _calls()
    got = rope_to_heads(x, rope, "interpret")
    assert _gained(before) == {"kernel": 0, "xla": 1}
    np.testing.assert_array_equal(got,
                                  apply_rope(x, rope).transpose(0, 2, 1, 3))


def test_tiny_laguna_model_counts_the_plain_form():
    """Head size 16: q and k of each of its three layers take ``path=xla``,
    whatever implementation is named."""
    model = build_model("gpt_laguna_tiny", 0, jnp.float32,
                        attention_impl="interpret")
    before = _calls()
    jax.eval_shape(model.init, jax.random.PRNGKey(0),
                   jnp.zeros((2, 32), jnp.int32))
    assert _gained(before) == {"kernel": 0, "xla": 6}


def _attention(implementation, rope):
    return MultiHeadAttention(
        num_heads=4, dtype=jnp.bfloat16, attention_impl=implementation,
        style=BlockStyle(num_kv_heads=2, head_dim=128, rope=rope))


@pytest.mark.parametrize("rope", sorted(ROPES))
def test_attention_with_a_128_wide_head_takes_the_kernel(rope):
    """Through ``MultiHeadAttention``: q and k count ``path=kernel``, and the
    layer's output and parameter gradients are those of the plain form and
    XLA's attention, to bf16 rounding."""
    x = _x((1, 512, 64), seed=3, dtype=jnp.float32)
    params = jax.jit(lambda key: _attention("reference", ROPES[rope]).init(
        key, x, causal=True))(jax.random.PRNGKey(4))

    def loss(params, implementation):
        out = _attention(implementation, ROPES[rope]).apply(
            params, x, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    value_and_grad = jax.jit(jax.value_and_grad(loss, has_aux=True),
                             static_argnums=1)
    before = _calls()
    (_, got), got_grad = value_and_grad(params, "interpret")
    assert _gained(before) == {"kernel": 2, "xla": 0}
    before = _calls()
    (_, want), want_grad = value_and_grad(params, "reference")
    assert _gained(before) == {"kernel": 0, "xla": 2}
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2)
    for a, b in zip(jax.tree_util.tree_leaves(got_grad),
                    jax.tree_util.tree_leaves(want_grad)):
        assert float(jnp.linalg.norm(a - b)) \
            < 2e-2 * float(jnp.linalg.norm(b)), (a.shape,)


# A block's ``qk_norm`` inside the rotary kernel (PR 49).

EPS = 1e-6
# SDAR's rope, a partial rotary with YaRN, Keye's sections.
NORMED_ROPES = {"sdar": _SDAR_30B_A3B["rope"], "partial": REAL["full"],
                "sections": _KEYE_VL2_30B_A3B["rope"]}
# [B, S, H, D]: two row blocks of one head block, and of K/V's four heads.
NORMED_SHAPES = {"q": (1, 1024, 8, 128), "k": (2, 512, 4, 128)}


def _scale(seed=5):
    return 1.0 + 0.25 * jax.random.normal(jax.random.PRNGKey(seed), (128,),
                                          jnp.float32)


def _positions(rope, seq_len):
    return np.tile(np.arange(seq_len), (len(rope.sections), 1)) \
        if rope.sections else None


def _normed(rope, implementation, blocks=None):
    """The norm and the turn of ``x [B,S,H,D]`` with ``scale``: by
    ``rope_to_heads`` as ``implementation`` has it, or (``"apart"``) the norm
    by XLA and then the kernel without one: what the fused kernel took the
    place of."""
    def turn(x, scale):
        b, s, h, d = x.shape
        positions = _positions(rope, s)
        if implementation == "apart":
            return rope_to_heads(rms_norm(x, scale, EPS, x.dtype), rope,
                                 "interpret", positions=positions)
        if blocks is not None:
            return R.rotate_to_heads(
                x.reshape(b, s, h * d), *rope.tables(s, d, positions), d,
                interpret=True, blocks=blocks, norm=(scale, EPS))
        return rope_to_heads(x, rope, implementation, positions=positions,
                             norm=(scale, EPS))
    return turn


def _close_but_for_bf16_steps(got, want, share=1e-4):
    """Equal but for a few results a bf16 step apart: the row's float32 sums
    are taken in another order."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.mean(got != want) < share, np.mean(got != want)
    np.testing.assert_allclose(got, want, rtol=2 ** -7, atol=2 ** -9)


@pytest.mark.parametrize("shape", sorted(NORMED_SHAPES))
@pytest.mark.parametrize("rope", sorted(NORMED_ROPES))
def test_norm_in_the_kernel_is_the_norm_before_it(rope, shape):
    x = 3.0 * _x(NORMED_SHAPES[shape], seed=6)
    before = _calls()
    got = jax.jit(_normed(NORMED_ROPES[rope], "interpret"))(x, _scale())
    assert _gained(before) == {"kernel": 1, "xla": 0}
    want = jax.jit(_normed(NORMED_ROPES[rope], "apart"))(x, _scale())
    assert got.dtype == want.dtype == jnp.bfloat16
    _close_but_for_bf16_steps(got, want)


@pytest.mark.parametrize("shape", sorted(NORMED_SHAPES))
@pytest.mark.parametrize("rope", sorted(NORMED_ROPES))
def test_norm_in_the_kernel_vjp_is_the_pairs(rope, shape):
    """The backward kernel's ``dx`` and the scale's gradient, summed by XLA
    over more than one grid step's parts, against what ``jax.vjp`` makes of
    the norm by XLA and the kernel's own transpose."""
    x = 3.0 * _x(NORMED_SHAPES[shape], seed=6)
    b, s, h, d = x.shape
    g = _x((b, h, s, d), seed=7)

    def grads(implementation):
        turn = _normed(NORMED_ROPES[rope], implementation)
        return jax.jit(lambda x, scale, g: jax.vjp(turn, x, scale)[1](g))(
            x, _scale(), g)

    (dx, dscale), (want_dx, want_dscale) = grads("interpret"), grads("apart")
    assert dx.shape == x.shape and dx.dtype == x.dtype
    assert dscale.shape == (d,) and dscale.dtype == jnp.float32
    _close_but_for_bf16_steps(dx, want_dx, share=1e-3)
    np.testing.assert_allclose(dscale, want_dscale, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("blocks", [(256, 2), (128, 4), (512, 1)])
def test_blocks_do_not_change_the_normed_result(blocks):
    """Neither the heads nor ``dx``: a row's sums are its own. The scale's
    gradient is summed over other parts."""
    rope = NORMED_ROPES["partial"]
    x, g = 3.0 * _x(NORMED_SHAPES["k"], seed=6), _x((2, 4, 512, 128), seed=7)

    def run(blocks):
        turn = _normed(rope, "interpret", blocks)
        return jax.jit(lambda x, scale, g: (
            turn(x, scale), *jax.vjp(turn, x, scale)[1](g)))(x, _scale(), g)

    got, want = run(blocks), run((512, 4))
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32).reshape(b.shape),
            np.asarray(b, np.float32))
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5, atol=1e-4)


def test_a_call_without_a_scale_builds_the_kernel_it_built():
    """The norm is a branch taken while the call is traced: without a scale
    the two kernels have the operands and the bodies they had before the norm
    came (the text of the jaxprs, bodies included, of PR 48's tree under jax
    0.9.0: Laguna's, ZAYA1's and Mellum2's steps are timed on them; after an
    upgrade of jax, record the digest again from the parent commit), with one
    they have the scale and the root of a sum too."""
    import hashlib

    x = _x(NORMED_SHAPES["k"])
    g = _x((2, 4, 512, 128), seed=1)
    rope = NORMED_ROPES["sdar"]
    turn = lambda x: rope_to_heads(x, rope, "interpret")
    plain = str(jax.make_jaxpr(
        lambda x, g: (turn(x), jax.vjp(turn, x)[1](g)))(x, g))
    normed = str(jax.make_jaxpr(_normed(rope, "interpret"))(x, _scale()))
    assert "name=rope_fwd" in plain and "name=rope_bwd" in plain
    assert not any(word in plain
                   for word in ("norm_rope", "rsqrt", "reduce_sum"))
    assert all(word in normed
               for word in ("name=norm_rope_fwd", "rsqrt", "reduce_sum"))
    if jax.__version__ == "0.9.0":
        assert hashlib.sha256(plain.encode()).hexdigest()[:16] \
            == "74d82f0c63338039"


@pytest.mark.parametrize("implementation,shape", [
    ("xla", (1, 512, 4, 128)),          # no kernel asked for
    ("interpret", (2, 1, 4, 128)),      # a decode step's one position
    ("interpret", (1, 512, 4, 64)),     # a head that is not whole lane tiles
])
def test_normed_fallback_is_rmsnorm_and_the_plain_form(implementation, shape):
    rope = NORMED_ROPES["sdar"]
    x = 3.0 * _x(shape, seed=6)
    scale = _scale()[:shape[-1]]
    before = _calls()
    got = jax.jit(lambda x, scale: rope_to_heads(
        x, rope, implementation, norm=(scale, EPS)))(x, scale)
    assert _gained(before) == {"kernel": 0, "xla": 1}
    norm = RMSNorm(EPS, x.dtype)
    want = jax.jit(lambda x, scale: apply_rope(
        norm.apply({"params": {"scale": scale}}, x), rope)
        .transpose(0, 2, 1, 3))(x, scale)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _norm_calls():
    counter = get_tracer().registry.counter("attention.qk_norm.calls")
    return {path: counter.value(path=path) for path in ("fused", "xla")}


def _normed_attention(implementation, rope, head_dim=128):
    return MultiHeadAttention(
        num_heads=4, dtype=jnp.bfloat16, attention_impl=implementation,
        style=BlockStyle(num_kv_heads=2, head_dim=head_dim, rope=rope,
                         qk_norm=True, rms_eps=EPS))


@pytest.mark.parametrize("rope", ["sdar", "sections"])
def test_attention_with_qk_norm_norms_in_the_kernel(rope):
    """A styled block with ``qk_norm`` and a 128-lane head: the parameter
    tree is the one it had (``query_norm/scale``, ``key_norm/scale``), a norm
    pair counts ``fused`` once under ``interpret`` and ``xla`` under
    ``reference``, the rotary calls count as they did, and outputs and
    gradients agree to bf16 rounding."""
    rope = NORMED_ROPES[rope]
    x = _x((1, 512, 64), seed=3, dtype=jnp.float32)
    trees = []
    for implementation in ("interpret", "reference"):
        before = _norm_calls()
        trees.append(jax.eval_shape(
            lambda key: _normed_attention(implementation, rope).init(
                key, x, causal=True), jax.random.PRNGKey(4)))
        gained = {p: n - before[p] for p, n in _norm_calls().items()}
        assert gained == ({"fused": 1, "xla": 0}
                          if implementation == "interpret"
                          else {"fused": 0, "xla": 1})
    assert trees[0] == trees[1]
    params = trees[0]["params"]
    assert {name: (params[name]["scale"].shape, params[name]["scale"].dtype)
            for name in ("query_norm", "key_norm")} == {
        "query_norm": ((128,), jnp.float32), "key_norm": ((128,), jnp.float32)}
    params = jax.jit(lambda key: _normed_attention("reference", rope).init(
        key, x, causal=True))(jax.random.PRNGKey(4))
    # Scales other than the seed's ones, so their gradients are told apart.
    params = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf * _scale(8) if "norm" in str(path) else leaf,
        params)

    def loss(params, implementation):
        out = _normed_attention(implementation, rope).apply(
            params, x, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    value_and_grad = jax.jit(jax.value_and_grad(loss, has_aux=True),
                             static_argnums=1)
    before, before_norm = _calls(), _norm_calls()
    (_, got), got_grad = value_and_grad(params, "interpret")
    assert _gained(before) == {"kernel": 2, "xla": 0}
    assert _norm_calls()["fused"] - before_norm["fused"] == 1
    (_, want), want_grad = value_and_grad(params, "reference")
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-2)
    assert jax.tree_util.tree_structure(got_grad) \
        == jax.tree_util.tree_structure(want_grad)
    for a, b in zip(jax.tree_util.tree_leaves(got_grad),
                    jax.tree_util.tree_leaves(want_grad)):
        assert float(jnp.linalg.norm(a - b)) \
            < 2e-2 * float(jnp.linalg.norm(b)), (a.shape,)


@pytest.mark.parametrize("rope,head_dim,scope", [
    ("sdar", 16, "qk_norm/rope"),    # a head the kernel cannot tile
    (None, 128, "qk_norm"),          # a norm and no rotary positions
])
def test_attention_with_qk_norm_off_the_kernel(rope, head_dim, scope):
    """Where the kernel does not run the norm is ``RMSNorm``'s by XLA, counted
    ``xla``, under the scope the block gives it."""
    rope = rope and NORMED_ROPES[rope]
    x = _x((1, 512, 64), seed=3, dtype=jnp.float32)
    layer = _normed_attention("interpret", rope, head_dim)
    before = _norm_calls()
    params = jax.jit(lambda key: layer.init(key, x, causal=True))(
        jax.random.PRNGKey(4))
    assert _norm_calls() == {**before, "xla": before["xla"] + 1}
    assert params["params"]["key_norm"]["scale"].shape == (head_dim,)
    text = jax.jit(lambda p: layer.apply(p, x, causal=True)).lower(params) \
        .as_text(debug_info=True)
    assert f"{scope}/rsqrt" in text
