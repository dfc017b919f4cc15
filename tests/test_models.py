"""Model zoo: shapes, param counts, dtype policy."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import build_model


def _param_count(params):
    return sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params))


def _init(model, x):
    """The model's variables from key 0, as a function to compile
    (``jax.jit``) or only to trace (``jax.eval_shape``): called as it is,
    every operation of it is a program of its own to compile."""
    return lambda: model.init(jax.random.PRNGKey(0), x, train=False)


def test_resnet20_shapes_and_params():
    model = build_model("resnet20", num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = jax.jit(_init(model, x))()
    logits = jax.jit(lambda v: model.apply(v, x, train=False))(variables)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32
    # He et al. ResNet-20 is ~0.27M params.
    n = _param_count(variables["params"])
    assert 0.2e6 < n < 0.35e6, n


def test_resnet50_shapes_and_params():
    model = build_model("resnet50", num_classes=1000, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 64, 64, 3))  # small spatial for test speed
    # Shapes and dtypes are all this asks, so nothing is computed.
    variables = jax.eval_shape(_init(model, x))
    n = _param_count(variables["params"])
    # Canonical ResNet-50 ≈ 25.6M params.
    assert 24e6 < n < 27e6, n
    logits = jax.eval_shape(lambda v: model.apply(v, x, train=False),
                            variables)
    assert logits.shape == (1, 1000)
    assert logits.dtype == jnp.float32  # head forced to f32


def test_space_to_depth_exact():
    from deeplearning_cfn_tpu.models.resnet import space_to_depth

    x = jnp.arange(2 * 8 * 8 * 3, dtype=jnp.float32).reshape(2, 8, 8, 3)
    y = space_to_depth(x, 2)
    assert y.shape == (2, 4, 4, 12)
    # Block (i,j) of the output must hold the 2×2 input block row-major:
    # channels [0:3]=(2i,2j), [3:6]=(2i,2j+1), [6:9]=(2i+1,2j), [9:12]=(2i+1,2j+1).
    np.testing.assert_array_equal(y[0, 1, 2, 0:3], x[0, 2, 4, :])
    np.testing.assert_array_equal(y[0, 1, 2, 3:6], x[0, 2, 5, :])
    np.testing.assert_array_equal(y[0, 1, 2, 6:9], x[0, 3, 4, :])
    np.testing.assert_array_equal(y[0, 1, 2, 9:12], x[0, 3, 5, :])


def test_resnet50_s2d_stem():
    # The s2d variant must produce the same output shape as the classic
    # stem (downstream stages are identical) with a 4×4×12 stem kernel.
    model = build_model("resnet50_s2d", num_classes=1000, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 64, 64, 3))
    variables = jax.eval_shape(_init(model, x))
    stem_kernel = variables["params"]["conv_init_s2d"]["kernel"]
    assert stem_kernel.shape == (4, 4, 12, 64), stem_kernel.shape
    logits = jax.eval_shape(lambda v: model.apply(v, x, train=False),
                            variables)
    assert logits.shape == (1, 1000)
    n = _param_count(variables["params"])
    assert 24e6 < n < 27e6, n  # same ballpark as classic resnet50


def test_batchnorm_stats_update():
    model = build_model("resnet20", num_classes=10, dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    variables = jax.jit(_init(model, x))()
    _, mutated = jax.jit(lambda v: model.apply(
        v, x, train=True, mutable=["batch_stats"]))(variables)
    before = jax.tree_util.tree_leaves(variables["batch_stats"])
    after = jax.tree_util.tree_leaves(mutated["batch_stats"])
    assert any(not np.allclose(np.asarray(b), np.asarray(a))
               for b, a in zip(before, after))


def test_bn_params_stay_f32_under_bf16():
    model = build_model("resnet50", num_classes=10, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 32, 32, 3))
    variables = jax.eval_shape(_init(model, x))
    flat = jax.tree_util.tree_leaves_with_path(variables["params"])
    for path, leaf in flat:
        assert leaf.dtype == jnp.float32, path


def test_unknown_model_raises():
    with pytest.raises(KeyError):
        build_model("nonexistent", num_classes=2, dtype=jnp.float32)
