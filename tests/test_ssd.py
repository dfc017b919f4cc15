"""The chunked state-space scan (``ops/ssd.py``) against the recurrence it
computes, a token at a time, in float32: values and every gradient; and the
pieces of Mamba-2's mixer around it (``models/ssm.py``): the causal depthwise
convolution against explicit shifts, the gate before the norm, the constants
a head; and that convolution with its bias and silu as the two kernels
of ``ops/conv.py``, in interpreter mode, against the shifted form."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models.ssm import (CausalConv, Mamba2Mixer,
                                             conv_gain, head_constants)
from deeplearning_cfn_tpu.ops.conv import (causal_conv_silu, conv_path,
                                          token_block)
from deeplearning_cfn_tpu.ops.ssd import (head_block, scan_path,
                                         ssd_recurrence, ssd_scan)

HEADS, HEAD_DIM, STATE = 4, 8, 16


def _inputs(seq, groups, seed=0, head_dim=HEAD_DIM, state=STATE):
    """Step sizes and rates whose product ``dt * a`` runs from 0.001 (a head
    that remembers a thousand tokens) to 2 (one that forgets inside one)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (2, seq, HEADS, head_dim))
    a = -jnp.asarray([0.1, 1.0, 4.0, 16.0])
    dt = jnp.exp(jax.random.uniform(ks[1], (2, seq, HEADS),
                                    minval=jnp.log(0.01),
                                    maxval=jnp.log(0.125)))
    dt = dt.at[0, 0].set(0.01).at[0, 1].set(0.125)      # both ends, surely
    b = jax.random.normal(ks[2], (2, seq, groups, state))
    c = jax.random.normal(ks[3], (2, seq, groups, state))
    return x, dt, a, b, c


# Both sides are float32 on the CPU and differ in the order of their sums
# (a running product of decays against one exponential of a running sum): a
# few float32 roundings of sums over up to 40 tokens.
TOL = 5e-6


def _close(got, want, what, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape, what
    assert np.max(np.abs(got - want)) <= tol * max(np.max(np.abs(want)), 1.0), \
        (what, np.max(np.abs(got - want)), np.max(np.abs(want)))


# The Pallas kernels in interpreter mode, at the smallest shapes they tile:
# a state of 128 and heads of 64 (two to a lane tile) or 128, chunks of whole
# 128-square sub-tiles. Sums over up to 256 tokens of 128 states.
KERNEL = dict(implementation="interpret", head_dim=64, state=128, tol=2e-5)


@pytest.mark.parametrize("seq,groups,how", [
    (24, 1, {}),    # 3 chunks
    (40, 1, {}),    # 5 chunks
    (40, 2, {}),    # 5 chunks, two groups of B and C
    (8, 1, {}),     # one chunk: nothing is handed on
    (5, 1, {}),     # shorter than a chunk
    # 3 chunks, two groups, a head block a group: two a grid's chunk step.
    (384, 2, dict(KERNEL, chunk=128, block_heads=2)),
    # A chunk of four sub-tiles: the one above the diagonal skipped, the one
    # left of it C B^T between two scalings; two head blocks of one group
    # add into its dB and dC.
    (512, 1, dict(KERNEL, chunk=256, block_heads=2)),
    # The same with all heads a block: two units of its loop.
    (512, 1, dict(KERNEL, chunk=256, block_heads=4)),
    # Three sub-tiles: two rows of them left of the diagonal.
    (384, 1, dict(KERNEL, chunk=384, block_heads=4)),
    # A head of a whole lane tile, and of two.
    (256, 2, dict(KERNEL, chunk=128, block_heads=1, head_dim=128)),
    (256, 1, dict(KERNEL, chunk=128, block_heads=2, head_dim=256)),
    # Asked for by name and shorter than a chunk: the einsums.
    (100, 1, dict(KERNEL, chunk=128)),
])
def test_chunked_scan_is_the_recurrence_with_every_gradient(seq, groups,
                                                            how):
    how = dict(dict(chunk=8, head_dim=HEAD_DIM, state=STATE, tol=TOL), **how)
    tol, sizes = how.pop("tol"), (how.pop("head_dim"), how.pop("state"))
    args = _inputs(seq, groups, 0, *sizes)
    span = np.asarray(args[1])[..., None, :] * -np.asarray(args[2])
    assert span.min() < 0.0011 and span.max() > 1.99
    path, _ = scan_path(how.get("implementation", "auto"), args[0].shape,
                        sizes[1], groups, how["chunk"])
    assert path == ("kernel" if "block_heads" in how else "xla")
    scan = lambda *t: ssd_scan(*t, **how)
    _close(jax.jit(scan)(*args), ssd_recurrence(*args), "y", tol)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    grads = lambda f: jax.jit(jax.grad(
        lambda *t: jnp.sum(f(*t) * w), argnums=(0, 1, 2, 3, 4)))(*args)
    for name, got, want in zip(("x", "dt", "a", "b", "c"), grads(scan),
                               grads(ssd_recurrence)):
        assert np.any(np.asarray(want)), name
        _close(got, want, f"d{name}", tol)


@pytest.mark.parametrize("shape,state,groups,chunk,path", [
    ((1, 8192, 64, 64), 128, 1, 256, "kernel"),   # granite-4.0-h's
    ((2, 512, 8, 128), 256, 2, 128, "kernel"),
    ((1, 8192, 64, 64), 128, 1, 64, "xla"),       # no whole sub-tile
    ((1, 8192, 64, 64), 16, 1, 256, "xla"),       # a state short of a tile
    ((1, 8192, 64, 48), 128, 1, 256, "xla"),      # heads that split a tile
    ((1, 8192, 3, 64), 128, 1, 256, "xla"),       # a tile with half a head
    ((1, 200, 64, 64), 128, 1, 256, "xla"),       # shorter than a chunk
    ((1, 8192 + 128, 64, 64), 128, 1, 256, "xla"),
])
def test_the_kernels_take_the_shapes_they_tile(shape, state, groups, chunk,
                                               path):
    """Which carrier runs is read from the call alone: on this CPU ``auto``
    is the einsums whatever the shape, and the kernels asked for by name
    engage only where the shape tiles."""
    assert scan_path("pallas", shape, state, groups, chunk) == (path, False)
    assert scan_path("interpret", shape, state, groups, chunk) \
        == (path, True)
    assert scan_path("auto", shape, state, groups, chunk) == ("xla", False)
    assert scan_path("reference", shape, state, groups, chunk) \
        == ("xla", False)
    with pytest.raises(ValueError, match="unknown implementation"):
        scan_path("mosaic", shape, state, groups, chunk)


def test_head_block_is_whole_tiles_of_a_group():
    assert head_block(64, 1, 64) == 8           # granite-4.0-h's
    assert head_block(64, 1, 64, wanted=64) == 64
    assert head_block(64, 4, 64, wanted=32) == 16   # a group's 16 heads
    assert head_block(64, 16, 64) == 4
    assert head_block(4, 1, 64) == 4
    assert head_block(24, 1, 128) == 8
    assert head_block(20, 1, 128) == 5
    assert head_block(6, 1, 64) == 6            # pairs: never 3
    assert head_block(2, 1, 64, wanted=1) == 2  # a lane tile at the least


def test_the_state_a_chunk_enters_with_is_most_of_a_slow_heads_result():
    """What the benchmark's control (a) leaves out: past the first chunk a
    head that forgets slowly owes most of its result to earlier chunks, so
    the scan of the last chunk alone is far from the scan of the whole."""
    x, dt, a, b, c = _inputs(24, 1)
    whole = ssd_scan(x, dt, a, b, c, chunk=8)
    alone = ssd_scan(x[:, 16:], dt[:, 16:], a, b[:, 16:], c[:, 16:], chunk=8)
    gap = jnp.max(jnp.abs(whole[:, 16:] - alone), axis=(0, 1, 3))
    assert float(gap[0]) > 0.5          # a = 0.1: remembers every token
    assert float(gap[3]) < float(gap[0])


def test_bad_shapes_are_refused():
    x, dt, a, b, c = _inputs(24, 1)
    with pytest.raises(ValueError, match="whole chunks"):
        ssd_scan(x[:, :20], dt[:, :20], a, b[:, :20], c[:, :20], chunk=8)
    with pytest.raises(ValueError, match="cannot share"):
        ssd_scan(x, dt, a, jnp.tile(b, (1, 1, 3, 1)),
                 jnp.tile(c, (1, 1, 3, 1)), chunk=8)


@pytest.mark.parametrize("seq,sizes,how", [
    (40, (HEAD_DIM, STATE), dict(chunk=8)),
    (512, (64, 128), dict(chunk=256, implementation="interpret",
                          block_heads=2)),
])
def test_bfloat16_operands_keep_the_state_and_the_sums_in_float32(seq, sizes,
                                                                  how):
    """The training dtype: products with bfloat16 operands, float32 sums.
    Against the float32 recurrence of the same rounded inputs the result is
    right to bfloat16's 3 digits, far from what a bfloat16 running sum over
    40 tokens would leave; so is every gradient, the kernels' as the
    einsums', whose cotangents come back in the inputs' dtypes."""
    x, dt, a, b, c = _inputs(seq, 1, 0, *sizes)
    half = lambda t: t.astype(jnp.bfloat16)
    args = (half(x), dt, a, half(b), half(c))
    got = ssd_scan(*args, **how)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(jnp.float32), ssd_recurrence(*args), "y", tol=2e-2)
    w = jax.random.normal(jax.random.PRNGKey(9), x.shape)
    grads = lambda f: jax.grad(lambda *t: jnp.sum(
        f(*t).astype(jnp.float32) * w), argnums=(0, 1, 2, 3, 4))(*args)
    for name, got, want in zip(("x", "dt", "a", "b", "c"),
                               grads(lambda *t: ssd_scan(*t, **how)),
                               grads(ssd_recurrence)):
        assert got.dtype == want.dtype, name
        _close(got.astype(jnp.float32), want.astype(jnp.float32),
               f"d{name}", tol=2e-2)


def test_causal_convolution_is_four_shifted_multiplies():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 6))
    conv = CausalConv(4)
    params = conv.init(jax.random.PRNGKey(1), x)["params"]
    assert params["kernel"].shape == (4, 6) and params["bias"].shape == (6,)
    params = dict(params, bias=jnp.arange(6.0))
    got = conv.apply({"params": params}, x)
    w = conv_gain(4, 6) * np.asarray(params["kernel"])
    want = np.zeros((2, 11, 6))
    for t in range(11):
        for j in range(4):
            if t - (3 - j) >= 0:
                want[:, t] += w[j] * np.asarray(x)[:, t - (3 - j)]
    _close(got, want + np.arange(6.0), "conv")
    # Causal: a later token changes no earlier output.
    moved = conv.apply({"params": params}, x.at[:, 7].add(1.0))
    assert np.array_equal(np.asarray(moved[:, :7]), np.asarray(got[:, :7]))
    assert not np.array_equal(np.asarray(moved[:, 7]), np.asarray(got[:, 7]))


def _shifted_form(x, kernel, bias, splits, taps):
    """``CausalConv``'s own arithmetic: the shifted multiply-adds in float32,
    silu, one cast, the split."""
    return CausalConv(taps).apply(
        {"params": {"kernel": kernel, "bias": bias}}, x, splits, "reference")


def _conv_inputs(shape, taps, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], shape).astype(dtype),
            jax.nn.initializers.xavier_uniform()(ks[1], (taps, shape[2])),
            0.3 * jax.random.normal(ks[2], shape[2:]))


def _within_an_ulp(got, want, what, dtype):
    """Within one unit in the last place of ``dtype`` (8 bits of bfloat16,
    24 of float32) of ``want``, and a few float32 roundings of the largest
    value where the terms of a sum nearly cancel."""
    assert got.dtype == want.dtype == dtype and got.shape == want.shape, what
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    bits = 8 if dtype == jnp.bfloat16 else 24
    ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30)))
                  - (bits - 1))
    ulp = ulp + 1e-6 * max(np.max(np.abs(want)), 1.0)
    assert np.all(np.abs(got - want) <= ulp), \
        (what, np.max(np.abs(got - want) / ulp))


@pytest.mark.parametrize("shape,splits,block,taps,dtype", [
    # Three token blocks of two chunks, four lane chunks over three arrays:
    # the rows before a chunk from the chunk above and, at a block's first,
    # from the block before; a batch of 2.
    ((2, 96, 512), (256, 384), (32, 16, 128), 4, jnp.bfloat16),
    # The block the rule gives (the whole sequence, two chunks of it), one
    # array, a width no 256 lanes divide: three lane chunks.
    ((2, 64, 384), (), None, 4, jnp.bfloat16),
    # A block of one chunk: every chunk's rows before come by the edge
    # block, every chunk's cotangent after from the scratch.
    ((1, 128, 256), (128,), (16, 16, 256), 4, jnp.bfloat16),
    # Chunks of 16 rows, two to a block; three taps.
    ((2, 128, 256), (128,), (32, 16, 128), 3, jnp.bfloat16),
    # float32 through and through, as the CPU tests' mixers run it.
    ((1, 64, 256), (128,), (32, 16, 256), 4, jnp.float32),
])
def test_convolution_kernels_are_the_shifted_form_with_every_gradient(
        shape, splits, block, taps, dtype):
    """``ops/conv.py``'s two kernels in interpreter mode against
    ``CausalConv``'s shifted multiply-adds followed by silu, the cast and
    the split: each array and ``x``'s gradient to one unit in the last place
    of the dtype, the float32 gradients of ``kernel`` and ``bias`` (sums
    over every token, in another order) to 1e-4 of their largest."""
    assert conv_path("interpret", shape, taps, splits,
                     jnp.dtype(dtype).itemsize) == ("kernel", True)
    x, kernel, bias = _conv_inputs(shape, taps, dtype)
    kernels = lambda x, kernel, bias: causal_conv_silu(
        x, conv_gain(taps, shape[2]) * kernel, bias, splits, interpret=True,
        block=block)
    plain = lambda x, kernel, bias: _shifted_form(x, kernel, bias, splits,
                                                  taps)
    got, want = kernels(x, kernel, bias), plain(x, kernel, bias)
    assert len(got) == len(want) == len(splits) + 1
    for n, (g, t) in enumerate(zip(got, want)):
        _within_an_ulp(g, t, f"array {n}", dtype)
    cts = [jax.random.normal(jax.random.PRNGKey(7 + n), t.shape)
           .astype(dtype) for n, t in enumerate(want)]
    grads = lambda f: jax.jit(jax.grad(lambda *a: sum(
        jnp.sum(o.astype(jnp.float32) * ct.astype(jnp.float32))
        for o, ct in zip(f(*a), cts)), argnums=(0, 1, 2)))(x, kernel, bias)
    (dx, dk, db), (dx_w, dk_w, db_w) = grads(kernels), grads(plain)
    # The ends: the first taps - 1 positions' inputs feed fewer outputs
    # before them, the last ones fewer after; both sides agree there too.
    _within_an_ulp(dx, dx_w, "dx", dtype)
    assert np.any(np.asarray(dx_w[:, -1], np.float32))
    for name, g, t in (("dkernel", dk, dk_w), ("dbias", db, db_w)):
        assert g.dtype == t.dtype == jnp.float32 and np.any(np.asarray(t))
        _close(g, t, name, tol=1e-4)


@pytest.mark.parametrize("shape,splits,taps,why", [
    ((2, 100, 256), (128,), 4, "no token block divides the sequence"),
    ((2, 64, 192), (128,), 4, "channels that are no whole lane tiles"),
    ((2, 64, 256), (64,), 4, "a cut inside a lane tile"),
    ((1, 64, 256), (128,), 10, "more taps than a chunk keeps rows"),
])
def test_convolution_off_the_kernels_is_todays_shifted_form(shape, splits,
                                                            taps, why):
    """A shape the kernels do not tile takes the ``xla`` path whatever is
    asked for, and that path is today's code: ``CausalConv`` alone, then
    ``nn.silu``, the cast and ``jnp.split``, bit for bit."""
    for impl in ("auto", "pallas", "interpret", "reference"):
        assert conv_path(impl, shape, taps, splits) \
            == ("xla", impl == "interpret"), why
    x, kernel, bias = _conv_inputs(shape, taps, jnp.bfloat16)
    params = {"params": {"kernel": kernel, "bias": bias}}
    got = CausalConv(taps).apply(params, x, splits, "interpret")
    today = jnp.split(jax.nn.silu(CausalConv(taps).apply(params, x))
                      .astype(jnp.bfloat16), splits, axis=-1)
    assert len(got) == len(today) == len(splits) + 1
    for g, t in zip(got, today):
        assert g.dtype == jnp.bfloat16
        assert np.array_equal(np.asarray(g, np.float32),
                              np.asarray(t, np.float32))


@pytest.mark.parametrize("shape,taps,splits,itemsize,block", [
    ((1, 8192, 4352), 4, (4096, 4224), 2, 512),    # granite-4.0-h's
    ((1, 8192, 4352), 4, (4096, 4224), 4, 256),    # float32: half the rows
    ((4, 4096, 512), 4, (), 2, 512),
    ((2, 48, 128), 2, (), 2, 16),
    ((1, 8192, 4352), 4, (4096, 4200), 2, None),   # a cut inside a tile
    ((1, 8200, 4352), 4, (), 2, None),             # 8 rows over
    ((1, 8192, 4300), 4, (), 2, None),
])
def test_the_convolution_kernels_take_the_shapes_they_tile(shape, taps,
                                                           splits, itemsize,
                                                           block):
    """Which carrier runs is read from the call alone, as the scan's is: on
    this CPU ``auto`` is the shifted form whatever the shape."""
    path = "kernel" if block else "xla"
    assert conv_path("pallas", shape, taps, splits, itemsize) \
        == (path, False)
    assert conv_path("interpret", shape, taps, splits, itemsize) \
        == (path, True)
    assert conv_path("auto", shape, taps, splits, itemsize) == ("xla", False)
    if block:
        assert token_block(shape[1], shape[2], itemsize) == block
    with pytest.raises(ValueError, match="unknown implementation"):
        conv_path("mosaic", shape, taps, splits, itemsize)


def test_convolution_kernels_are_causal_through_the_halo():
    """Token blocks of 32: moving token 31, a block's last, changes outputs
    31 to 34 (three of them in the next block, through the rows the edge
    block brings) and none before; moving token 40 changes 40 to 43 and
    none before. The cotangent runs the other way: output 32's reaches
    inputs 29 to 32 and none after."""
    x, kernel, bias = _conv_inputs((2, 96, 256), 4, jnp.bfloat16)
    w = conv_gain(4, 256) * kernel
    conv = lambda x: causal_conv_silu(x, w, bias, (128,), interpret=True,
                                      block=(32, 16, 128))
    rows = lambda parts: np.concatenate(
        [np.asarray(p, np.float32) for p in parts], axis=-1)
    got = rows(conv(x))
    for at in (31, 40):
        moved = rows(conv(x.at[:, at].add(1.0)))
        assert np.array_equal(moved[:, :at], got[:, :at])
        changed = np.any(moved != got, axis=(0, 2))
        assert changed[at:at + 4].all() and not changed[at + 4:].any()
    dx = jax.grad(lambda x: sum(
        jnp.sum(p[:, 32].astype(jnp.float32)) for p in conv(x)))(x)
    reached = np.any(np.asarray(dx, np.float32) != 0, axis=(0, 2))
    assert reached[29:33].all() and not reached[:29].any() \
        and not reached[33:].any()


def test_conv_gain_makes_xavier_taps_conv1ds():
    """A Xavier-uniform ``[4, 4352]`` kernel is uniform over +-sqrt(6 /
    4356); times the gain it is uniform over +-1/2, ``nn.Conv1d``'s bound
    for 4 taps a channel."""
    assert conv_gain(4, 4352) * np.sqrt(6 / 4356) == pytest.approx(0.5)
    assert conv_gain(4, 4352) == pytest.approx(13.472, abs=1e-3)


def test_head_constants_are_the_grid():
    a, c = head_constants(64)
    dt = np.log1p(np.exp(c))
    assert a.shape == c.shape == (64,)
    assert sorted(set(np.round(a, 4))) == pytest.approx(
        16.0 ** np.linspace(-1, 1, 8), abs=1e-4)
    assert dt.min() == pytest.approx(1e-3, rel=1e-4)
    assert dt.max() == pytest.approx(1e-1, rel=1e-4)
    # Every rate meets every step size once.
    assert len({(round(float(x), 4), round(float(y), 6))
                for x, y in zip(a, dt)}) == 64
    assert (a * dt).min() == pytest.approx(1e-3 / 16, rel=1e-4)
    assert (a * dt).max() == pytest.approx(1.6, rel=1e-4)
    # A third of the heads remember past a chunk of 256 tokens.
    assert np.sum(a * dt < 1 / 256) == 23
    with pytest.raises(ValueError, match="square grid"):
        head_constants(48)


def _mixer(**kw):
    return Mamba2Mixer(**dict(dict(heads=HEADS, head_dim=HEAD_DIM,
                                   state=STATE, chunk=8, dtype=jnp.float32),
                              **kw))


def test_mixer_is_its_equations_written_out():
    """The module against the recurrence and plain numpy around it: the
    split of the projection, the convolution over x, B and C alike, the
    constants a head, D's skip, the gate before the norm."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32))
    params = _mixer().init(jax.random.PRNGKey(1), u)["params"]
    assert {k: tuple(v[next(iter(v))].shape) for k, v in params.items()
            if k not in ("conv",)} == {
        "in_proj": (32, 2 * 32 + 2 * 16 + 4), "out_proj": (32, 32),
        "a_log": (4,), "dt_bias": (4,), "d_skip": (4,),
        "gate_norm": (32,)}
    rng = np.random.RandomState(0)
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * rng.standard_normal(p.shape).astype(np.float32),
        params)
    got = _mixer().apply({"params": params}, u)
    p = jax.tree_util.tree_map(np.asarray, params)
    inner, bc = 32, 16
    zxbcdt = np.asarray(u) @ p["in_proj"]["kernel"]
    z, xbc, dt = np.split(zxbcdt, (inner, 2 * inner + 2 * bc), axis=-1)
    w = conv_gain(4, inner + 2 * bc) * p["conv"]["kernel"]
    pad = np.concatenate([np.zeros((2, 3, xbc.shape[-1])), xbc], axis=1)
    xbc = p["conv"]["bias"] + sum(w[j] * pad[:, j:j + 24] for j in range(4))
    xbc = xbc / (1 + np.exp(-xbc))
    x, b, c = np.split(xbc, (inner, inner + bc), axis=-1)
    a_h, c_h = head_constants(HEADS)
    a = -a_h * np.exp(p["a_log"]["bias"])
    dt = np.log1p(np.exp(dt + c_h + p["dt_bias"]["bias"]))
    x = x.reshape(2, 24, HEADS, HEAD_DIM)
    y = np.asarray(ssd_recurrence(
        jnp.asarray(x, jnp.float32), jnp.asarray(dt, jnp.float32),
        jnp.asarray(a, jnp.float32),
        jnp.asarray(b.reshape(2, 24, 1, STATE), jnp.float32),
        jnp.asarray(c.reshape(2, 24, 1, STATE), jnp.float32)))
    y = (y + p["d_skip"]["scale"][:, None] * x).reshape(2, 24, inner)
    gated = y * (z / (1 + np.exp(-z)))
    normed = gated / np.sqrt(np.mean(gated ** 2, -1, keepdims=True) + 1e-5) \
        * p["gate_norm"]["scale"]
    _close(got, normed @ p["out_proj"]["kernel"], "mixer", tol=2e-5)
    # Norm-then-gate is another function.
    other = y / np.sqrt(np.mean(y ** 2, -1, keepdims=True) + 1e-5) \
        * p["gate_norm"]["scale"] * (z / (1 + np.exp(-z)))
    assert np.max(np.abs(other @ p["out_proj"]["kernel"]
                         - np.asarray(got))) > 0.05


KERNEL_MIXER = dict(head_dim=64, state=128, chunk=128)


@pytest.mark.parametrize("seq,how,path", [
    (24, {}, "xla"),                            # off the TPU: the einsums
    (24, dict(scan_impl="interpret"), "xla"),   # a chunk of 8 tiles nothing
    (100, dict(KERNEL_MIXER, scan_impl="interpret"), "xla"),  # short
    (256, dict(KERNEL_MIXER), "xla"),           # it would tile: off the TPU
    (256, dict(KERNEL_MIXER, scan_impl="interpret"), "kernel"),
])
def test_mixer_counts_its_calls_when_traced(seq, how, path):
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    registry = get_tracer().registry
    calls = registry.counter("ssm.scan.calls")
    convs = registry.counter("ssm.conv.calls")
    mixer = _mixer(**how)
    chunk = str(mixer.chunk)
    before = {p: calls.value(path=p, chunk=chunk) for p in ("xla", "kernel")}
    convs_before = {p: convs.value(path=p) for p in ("xla", "kernel")}
    u = jnp.zeros((1, seq, 32))
    params = mixer.init(jax.random.PRNGKey(0), u)
    jax.jit(jax.grad(lambda p: jnp.sum(mixer.apply(p, u))))(params)
    # Once for the parameters, once for the step, under the path taken; the
    # backward pass traces nothing again.
    assert {p: calls.value(path=p, chunk=chunk) - n
            for p, n in before.items()} \
        == {path: 2, "kernel" if path == "xla" else "xla": 0}
    # The convolution's kernels tile where the scan's do in these five: 64
    # channels are no lane tile, 100 tokens no whole blocks of 16.
    assert {p: convs.value(path=p) - n for p, n in convs_before.items()} \
        == {path: 2, "kernel" if path == "xla" else "xla": 0}
    assert registry.gauge("ssm.scan.chunks").value() \
        == max(seq // mixer.chunk, 1)
    assert registry.gauge("ssm.state_bytes").value() \
        == 4 * HEADS * mixer.head_dim * mixer.state


def test_mixer_is_the_same_function_through_the_kernels():
    """The module with the kernels (interpreter mode) against itself with
    the einsums: the result and the gradient of every parameter."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 32))
    by = {impl: _mixer(**KERNEL_MIXER, scan_impl=impl)
          for impl in ("interpret", "reference")}
    params = jax.jit(by["reference"].init)(jax.random.PRNGKey(1), u)
    w = jax.random.normal(jax.random.PRNGKey(2), u.shape)
    loss = lambda impl: lambda p: jnp.sum(by[impl].apply(p, u) * w)
    _close(jax.jit(by["interpret"].apply)(params, u),
           jax.jit(by["reference"].apply)(params, u), "mixer", tol=2e-5)
    got, want = (jax.jit(jax.grad(loss(impl)))(params)
                 for impl in ("interpret", "reference"))
    for (path, g), t in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        _close(g, t, jax.tree_util.keystr(path), tol=5e-5)
