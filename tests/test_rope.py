"""The rotary-position kernel (ops/rope.py) in Pallas interpreter mode against
the plain form (models/transformer.py:apply_rope and the transpose), at both
of Laguna's rope settings; when it engages; and what it counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import build_model
from deeplearning_cfn_tpu.models.lm import _LAGUNA_TINY, _LAGUNA_XS2
from deeplearning_cfn_tpu.models.transformer import (
    BlockStyle, MultiHeadAttention, Rope, apply_rope, rope_to_heads)
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
