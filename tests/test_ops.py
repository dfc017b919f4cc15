"""Numerics tests for the kernel layer (ops/): flash attention vs the jnp
oracle (kernel run in Pallas interpreter mode — CPU-runnable), gradients
through the custom VJP, and ring attention vs full attention on the fake
8-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning_cfn_tpu.ops.ulysses import ulysses_attention_sharded
from deeplearning_cfn_tpu.ops import (
    attention_reference,
    fused_attention,
    ring_attention_sharded,
)


def _qkv(b=2, h=2, sq=64, sk=64, d=32, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda s: jnp.asarray(rng.normal(0, 1, (b, h, s, d)), dtype)
    return mk(sq), mk(sk), mk(d * 0 + sk)[:, :, :sk, :]


def test_reference_matches_naive_softmax():
    q, k, v = _qkv()
    out = attention_reference(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(32.0)
    naive = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
    np.testing.assert_allclose(out, naive, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(64, 64), (64, 128), (100, 100)])
def test_flash_kernel_matches_reference(causal, sq, sk):
    """The Pallas kernel (interpreter mode) must agree with the oracle,
    including non-block-multiple lengths (padding path) and causal masks."""
    if causal and sq != sk and sq == 64 and sk == 128:
        pass  # cross-length causal aligns ends — covered below too
    q, k, v = _qkv(sq=sq, sk=sk)
    ref = attention_reference(q, k, v, causal=causal)
    out = fused_attention(q, k, v, causal=causal,
                          implementation="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_kernel_with_padding_bias():
    """Additive -inf padding bias (BERT padding mask shape [B,1,1,Sk])."""
    q, k, v = _qkv(sq=64, sk=64)
    kv_len = 40
    bias = jnp.where(jnp.arange(64) < kv_len, 0.0, -1e30)
    bias = bias[None, None, None, :]
    ref = attention_reference(q, k, v, bias=bias)
    out = fused_attention(q, k, v, bias=bias, implementation="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # Masked-out keys truly don't contribute.
    v2 = v.at[:, :, kv_len:, :].set(999.0)
    out2 = fused_attention(q, k, v2, bias=bias, implementation="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-5)


def test_fused_attention_grads_match_reference():
    q, k, v = _qkv(sq=32, sk=32, d=16)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    def loss_fused(q, k, v):
        return jnp.sum(fused_attention(q, k, v, causal=True,
                                       implementation="interpret") ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fused):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_fused_attention_bfloat16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = fused_attention(q, k, v, implementation="interpret")
    ref = attention_reference(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


def test_auto_dispatch_short_seq_window():
    """The 'auto' dispatch (ops/attention._auto_use_pallas): flash kernel
    on TPU except the hardware-measured short-seq window (S<1024) where
    XLA's fused attention is faster — and only while its quadratic
    backward intermediate fits the cap (at big batch, flash's O(S)
    memory wins regardless)."""
    from deeplearning_cfn_tpu.ops.attention import _auto_use_pallas

    # Never pallas off-TPU.
    assert _auto_use_pallas("cpu", 8, 12, 512, 512) is False
    # Short seq on TPU within the memory cap -> XLA path.
    assert _auto_use_pallas("tpu", 32, 12, 512, 512) is False
    # Long seq -> flash (the measured 1.4x/35x regime).
    assert _auto_use_pallas("tpu", 8, 12, 2048, 2048) is True
    assert _auto_use_pallas("tpu", 2, 12, 8192, 8192) is True
    # Short seq but the f32 [B,H,Sq,Sk] backward intermediate exceeds
    # the 512 MiB cap -> flash for memory: 512*12*512*512*4 B = 6.0 GiB.
    assert _auto_use_pallas("tpu", 512, 12, 512, 512) is True
    # Near-cap case (60*16*512*512*4 B ≈ 0.94 GiB > 512 MiB): must stay
    # flash — the XLA backward holds 2-3 such buffers live at once.
    assert _auto_use_pallas("tpu", 60, 16, 512, 512) is True
    # The r03 bench shape (32*12*512*512*4 B ≈ 402 MiB) stays eligible.
    assert _auto_use_pallas("tpu", 32, 12, 512, 512) is False


def test_fused_attention_shape_validation():
    with pytest.raises(ValueError, match="B,H,S,D"):
        fused_attention(jnp.zeros((4, 8, 16)), jnp.zeros((4, 8, 16)),
                        jnp.zeros((4, 8, 16)))
    with pytest.raises(ValueError, match="implementation"):
        q, k, v = _qkv(sq=8, sk=8, d=8)
        fused_attention(q, k, v, implementation="cuda")


@pytest.mark.parametrize("sq,sk", [(192, 192), (300, 300), (40, 72)])
def test_flash_causal_with_block_padding(sq, sk):
    """Shapes where Q and K pad by DIFFERENT amounts: the causal diagonal
    must still align to the true lengths (regression: padded lengths used
    to shift the mask, leaking future positions)."""
    q, k, v = _qkv(b=1, h=1, sq=sq, sk=sk, d=16, seed=3)
    ref = attention_reference(q, k, v, causal=True)
    out = fused_attention(q, k, v, causal=True, implementation="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_bias_broadcast_k_dim():
    """Bias with K dim == 1 (broadcast over keys, e.g. a per-query additive
    term): the contract is 'broadcastable to [B,H,Sq,Sk]' and the reference
    path accepts it, so the kernel path must agree (regression: used to
    raise ValueError)."""
    q, k, v = _qkv(b=1, h=2, sq=64, sk=72, d=16, seed=5)
    bias = jnp.asarray(
        np.random.RandomState(6).normal(0, 1, (1, 1, 64, 1)), jnp.float32)
    ref = attention_reference(q, k, v, bias=bias)
    out = fused_attention(q, k, v, bias=bias, implementation="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # And combined with causal masking (kv block padding in play: sk=72).
    ref_c = attention_reference(q, k, v, bias=bias, causal=True)
    out_c = fused_attention(q, k, v, bias=bias, causal=True,
                            implementation="interpret")
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref_c),
                               atol=2e-5, rtol=2e-5)


def test_flash_bias_with_kv_padding():
    """User bias [B,1,1,sk] where sk needs block padding (regression: used
    to crash on shape mismatch when adding the pad bias)."""
    sk = 200
    q, k, v = _qkv(b=1, h=2, sq=64, sk=sk, d=16, seed=4)
    bias = jnp.where(jnp.arange(sk) < 150, 0.0, -1e30)[None, None, None, :]
    ref = attention_reference(q, k, v, bias=bias)
    out = fused_attention(q, k, v, bias=bias, implementation="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(64, 64), (100, 100), (64, 128),
                                   (40, 72)])
def test_flash_backward_matches_reference(causal, sq, sk):
    """The blocked flash backward (dq/dk/dv kernels, interpret mode) must
    agree with the reference VJP — including block-padded lengths where the
    causal diagonal and padded rows/columns need masking in the recompute."""
    q, k, v = _qkv(b=2, h=2, sq=sq, sk=sk, d=16, seed=8)
    g = jnp.asarray(
        np.random.RandomState(9).normal(0, 1, q.shape[:-1] + (16,)),
        jnp.float32)

    def loss_ref(q, k, v):
        return jnp.vdot(attention_reference(q, k, v, causal=causal), g)

    def loss_flash(q, k, v):
        return jnp.vdot(
            fused_attention(q, k, v, causal=causal,
                            implementation="interpret"), g)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_flash):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=5e-4, rtol=5e-4,
            err_msg=f"d{name} mismatch")


def test_flash_backward_bf16():
    q, k, v = _qkv(sq=128, sk=128, d=32, dtype=jnp.bfloat16, seed=10)

    def loss(impl):
        def f(q, k, v):
            return jnp.sum(fused_attention(q, k, v, causal=True,
                                           implementation=impl) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(loss("reference"), loss("interpret")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=5e-2)


def test_flash_backward_no_full_score_matrix(monkeypatch):
    """The point of the flash backward: no [Sq,Sk] intermediate anywhere in
    the grad computation (walk the jaxpr, including pallas kernel bodies —
    block tiles and sub-tiles are fine, full S×S is not)."""
    from deeplearning_cfn_tpu.ops import attention

    # Blocks and sub-tiles under S, as a long call has them: 512 would fit
    # the shipped 1024-square block whole.
    for name, size in (("_BLOCK_Q", 256), ("_BLOCK_K", 256),
                       ("_SUB_Q", 128), ("_SUB_K", 128)):
        monkeypatch.setattr(attention, name, size)
    sq = sk = 512
    q, k, v = _qkv(b=1, h=1, sq=sq, sk=sk, d=16, seed=11)

    def loss(q, k, v):
        return jnp.sum(fused_attention(q, k, v, causal=True,
                                       implementation="interpret") ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    offenders, tiles = [], set()

    def walk(jx, kernel=None):
        for eqn in jx.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(var, "aval", None)
                shape = getattr(aval, "shape", ())
                if len(shape) >= 2 and shape[-1] == sk and \
                        shape[-2] == sq:
                    offenders.append((eqn.primitive.name, shape))
                if kernel and len(shape) == 2 and shape[0] == shape[1]:
                    tiles.add((kernel, shape[0]))
            within = eqn.params["name"] \
                if eqn.primitive.name == "pallas_call" else kernel
            for param in eqn.params.values():
                for one in param if isinstance(param, (tuple, list)) \
                        else (param,):
                    inner = getattr(one, "jaxpr", one)
                    if hasattr(inner, "eqns"):
                        walk(inner, within)

    walk(jaxpr.jaxpr)
    assert not offenders, f"full score-matrix tensors found: {offenders}"
    # The walk did reach the three kernels' bodies: the forward forms its
    # block's score tile, the backward kernels their sub-tiles.
    assert tiles >= {("flash_fwd", 256), ("flash_bwd_dkdv", 128),
                     ("flash_bwd_dq", 128)}, tiles


# -- the tile plan and the sub-tiles inside a grid step ------------------------


def _counts(sq, sk, plan, causal, **schedule_kw):
    """(all, computed, masked) sub-tiles by the schedule a kernel runs."""
    from deeplearning_cfn_tpu.ops.attention import (
        _schedule, _subtile_counts)

    return _subtile_counts(sq, sk, plan,
                           _schedule(sq, sk, plan, causal, **schedule_kw))


def _brute_force_counts(sq, sk, plan):
    """(all, computed, masked) sub-tiles of a causal call, from positions."""
    block_q, block_k, sub_q, sub_k = plan
    sq_p = -(-sq // block_q) * block_q
    sk_p = -(-sk // block_k) * block_k
    q_pos = np.arange(sq_p)[:, None] + (sk - sq)
    k_pos = np.arange(sk_p)[None, :]
    below = k_pos <= q_pos
    total = live = masked = 0
    for r0 in range(0, sq_p, sub_q):
        for c0 in range(0, sk_p, sub_k):
            total += 1
            tile = (slice(r0, r0 + sub_q), slice(c0, c0 + sub_k))
            if not below[tile].any():
                continue  # above the diagonal
            live += 1
            masked += not below[tile].all()
    return total, live, masked


@pytest.mark.parametrize("sq,sk,d,causal,backward,plan,counts", [
    # The cell's shape. Backward: 256-square sub-tiles, 10 of 16 computed, 4
    # of them masked. Forward: one piece.
    (1024, 1024, 64, True, True, (1024, 1024, 256, 256), (16, 10, 4)),
    (1024, 1024, 64, True, False, (1024, 1024, 1024, 1024), (1, 1, 1)),
    (8192, 8192, 64, True, True, (1024, 1024, 256, 256), None),
    (8192, 8192, 64, True, False, (1024, 1024, 1024, 1024), (64, 36, 36)),
    (2048, 2048, 128, True, True, (1024, 1024, 256, 256), None),
    (1000, 1000, 64, True, True, (1024, 1024, 256, 256), None),
    (1024, 2048, 64, True, True, (1024, 1024, 256, 256), None),
    (1000, 3000, 64, True, True, (1024, 1024, 256, 256), None),
    (300, 300, 64, True, True, (512, 512, 256, 256), (4, 3, 2)),
    # Not causal: one sub-tile per block, all of it computed. (A call with a
    # bias has no backward kernel, and its forward is one piece like any.)
    (4096, 4096, 64, False, False, (1024, 1024, 1024, 1024), (16, 16, 0)),
    (4096, 4096, 64, False, True, (1024, 1024, 1024, 1024), (16, 16, 16)),
    # Short calls fit one sub-tile: ends aligned, and ragged.
    (64, 128, 64, True, True, (64, 128, 64, 128), (1, 1, 1)),
    (100, 100, 64, True, True, (104, 104, 104, 104), (1, 1, 1)),
    (40, 72, 64, True, True, (40, 72, 40, 72), (1, 1, 1)),
])
def test_tile_plan(sq, sk, d, causal, backward, plan, counts):
    """ops/attention._tile_plan, and what the kernels make of it
    (_subtile_counts, from the very schedule the kernels run)."""
    from deeplearning_cfn_tpu.ops.attention import _tile_plan

    assert _tile_plan(sq, sk, d, causal, backward) == plan
    got = _counts(sq, sk, plan, causal, mask_whole=backward)
    if counts is not None:
        assert got == counts
    if not causal:
        assert got[1] == got[0]  # a live share of 1.0
    elif plan[:2] == plan[2:]:
        # One piece per grid tile: every tile the grid does not skip, masked.
        assert got[1] == got[2] == _brute_force_counts(sq, sk, plan)[1]
    else:
        assert got == _brute_force_counts(sq, sk, plan)
        # The dK/dV kernel walks bands of columns: the same sub-tiles.
        assert got == _counts(sq, sk, plan, causal, by_columns=True)


@pytest.mark.parametrize("sq,sk,plan", [
    (64, 128, (64, 128, 32, 32)),      # sq < sk, ends aligned
    (100, 100, (128, 128, 32, 64)),    # ragged: padded rows and columns
    (100, 100, (64, 64, 32, 32)),      # the same over a 2 x 2 grid
    (40, 72, (48, 96, 16, 32)),
    (300, 300, (128, 128, 64, 64)),    # 3 x 3 grid, 84 padded
])
def test_subtile_counts_forced_plans(sq, sk, plan):
    want = _brute_force_counts(sq, sk, plan)
    assert _counts(sq, sk, plan, True) == want
    assert _counts(sq, sk, plan, True, by_columns=True) == want


@pytest.mark.parametrize("sq,sk,plan,causal,dtype", [
    # One grid tile (static loops): square and oblong sub-tiles.
    (256, 256, (256, 256, 64, 64), True, jnp.float32),
    (256, 256, (256, 256, 64, 128), True, jnp.float32),
    (256, 256, (256, 256, 64, 64), False, jnp.float32),
    # Several grid tiles (bounds from program_id).
    (256, 256, (128, 128, 64, 64), True, jnp.float32),
    (256, 256, (128, 128, 32, 64), False, jnp.float32),
    # sq < sk (ends aligned), one tile and several.
    (128, 256, (128, 256, 64, 64), True, jnp.float32),
    (128, 256, (64, 128, 32, 64), True, jnp.float32),
    # Padded lengths.
    (200, 200, (256, 256, 64, 64), True, jnp.float32),
    (200, 200, (128, 128, 64, 64), True, jnp.float32),
    (40, 72, (48, 96, 16, 32), True, jnp.float32),
    # bfloat16, at test_flash_backward_bf16's tolerances.
    (256, 256, (256, 256, 64, 64), True, jnp.bfloat16),
    (256, 256, (128, 128, 64, 128), True, jnp.bfloat16),
])
def test_flash_subtiles_match_reference(sq, sk, plan, causal, dtype):
    """Forward and gradients with sub-tiles forced smaller than the block,
    so that all three cases of a sub-tile (skipped, masked, unmasked) and
    the boundaries between them run on the CPU."""
    from deeplearning_cfn_tpu.ops.attention import (
        _flash_backward, _flash_forward)

    if causal:  # the plan does exercise every case
        total, live, masked = _counts(sq, sk, plan, True)
        assert 0 < masked < live < total
    d = 16
    q, k, v = _qkv(b=1, h=2, sq=sq, sk=sk, d=d, seed=12, dtype=dtype)
    g = jnp.asarray(np.random.RandomState(13).normal(0, 1, q.shape), dtype)
    scale = d ** -0.5
    out, lse = _flash_forward(q, k, v, None, causal, scale, interpret=True,
                              return_stats=True, plan=plan)
    grads = _flash_backward(q, k, v, out, lse, g, causal, scale, True,
                            plan=plan)
    ref, vjp = jax.vjp(
        lambda q, k, v: attention_reference(q, k, v, causal=causal,
                                            sm_scale=scale), q, k, v)
    tol = dict(atol=5e-4, rtol=5e-4) if dtype == jnp.float32 \
        else dict(atol=5e-2, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)
    for name, a, b in zip("qkv", vjp(g), grads):
        np.testing.assert_allclose(
            np.asarray(b, np.float32), np.asarray(a, np.float32), **tol,
            err_msg=f"d{name} mismatch")


def test_flash_subtile_gauges():
    """The two gauges (docs/OBSERVABILITY.md), set when a kernel is traced:
    the plan's shares at the benchmark cell's shape, 1.0 for a non-causal
    call."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    def shares(shape, causal):
        arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        jax.eval_shape(jax.grad(
            lambda q, k, v: fused_attention(
                q, k, v, causal=causal, implementation="interpret"
            ).astype(jnp.float32).sum(), argnums=(0, 1, 2)), arg, arg, arg)
        registry = get_tracer().registry
        live = registry.gauge("attention.flash.live_subtile_share")
        masked = registry.gauge("attention.flash.masked_subtile_share")
        return {kernel: (live.value(kernel=kernel),
                         masked.value(kernel=kernel))
                for kernel in ("flash_fwd", "flash_bwd_dkdv",
                               "flash_bwd_dq")}

    assert shares((16, 12, 1024, 64), True) == {
        "flash_fwd": (1.0, 1.0), "flash_bwd_dkdv": (0.625, 0.25),
        "flash_bwd_dq": (0.625, 0.25)}
    assert shares((2, 12, 4096, 64), False) == {
        "flash_fwd": (1.0, 0.0), "flash_bwd_dkdv": (1.0, 1.0),
        "flash_bwd_dq": (1.0, 1.0)}


# -- grouped K/V heads and the sliding window ---------------------------------


def _grouped_qkv(h, hk, sq, sk, d=16, seed=21, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda heads, s: jnp.asarray(rng.normal(0, 1, (1, heads, s, d)),
                                      dtype)
    return mk(h, sq), mk(hk, sk), mk(hk, sk)


def _seen(sq, sk, plan, window):
    """Which column each row of the block-padded score matrix sees."""
    block_q, block_k = plan[:2]
    q_pos = np.arange(-(-sq // block_q) * block_q)[:, None] + (sk - sq)
    k_pos = np.arange(-(-sk // block_k) * block_k)[None, :]
    seen = k_pos <= q_pos
    return seen & (q_pos - k_pos < window) if window else seen


def _brute_force_window_counts(sq, sk, plan, window):
    sub_q, sub_k = plan[2:]
    seen = _seen(sq, sk, plan, window)
    sq_p, sk_p = seen.shape
    total = live = masked = 0
    for r0 in range(0, sq_p, sub_q):
        for c0 in range(0, sk_p, sub_k):
            tile = seen[r0:r0 + sub_q, c0:c0 + sub_k]
            total += 1
            live += bool(tile.any())
            masked += bool(tile.any() and not tile.all())
    return total, live, masked


@pytest.mark.parametrize("sq,sk,window,plan", [
    (4096, 4096, 512, (1024, 1024, 256, 256)),   # the Laguna cell's layers
    (1024, 2048, 512, (1024, 1024, 256, 256)),
    (256, 256, 40, (128, 128, 32, 32)),
    (100, 100, 24, (64, 64, 32, 16)),
    (128, 256, 300, (64, 128, 32, 64)),          # wider than the rows
    (32, 32, 8, (32, 32, 32, 32)),               # one sub-tile, both edges
])
def test_window_schedule_counts(sq, sk, window, plan):
    """Every sub-tile the window or the diagonal leaves out is left out, by
    all three kernels' schedules, and nothing else."""
    from deeplearning_cfn_tpu.ops.attention import _tile_plan

    want = _brute_force_window_counts(sq, sk, plan, window)
    assert _counts(sq, sk, plan, True, window=window) == want
    assert _counts(sq, sk, plan, True, by_columns=True, window=window) == want
    if plan == (1024, 1024, 256, 256):
        # the backward pair's plan; the forward's sub-tiles are 128-square
        assert _tile_plan(sq, sk, 128, True, True, window) == plan
        assert _tile_plan(sq, sk, 128, True, False, window) == (
            1024, 1024, 128, 128)
    assert want[1] < want[0] or want[0] == 1


_GRID_WALKS = [
    # (sq, sk, window, plan), grid steps / dead / wasted copies a query head
    # in each of the three kernels: test_window_schedule_counts' cases ...
    ((4096, 4096, 512, (1024, 1024, 256, 256)), (8, 1, 0)),  # parent: 16/9/9
    ((4096, 4096, 512, (1024, 1024, 128, 128)), (8, 1, 0)),  # its forward
    ((1024, 2048, 512, (1024, 1024, 256, 256)), None),
    ((256, 256, 40, (128, 128, 32, 32)), None),
    ((100, 100, 24, (64, 64, 32, 16)), None),
    ((128, 256, 300, (64, 128, 32, 64)), None),
    ((32, 32, 8, (32, 32, 32, 32)), (1, 0, 0)),
    # ... the plans of the PR 29 sweep at the Laguna cell's sliding layers ...
    ((4096, 4096, 512, (512, 512, 256, 256)), (16, 1, 0)),
    ((4096, 4096, 512, (1024, 512, 256, 128)), None),  # 12/1/0, dK/dV 16/5/0
    ((4096, 4096, 512, (512, 512, 128, 128)), (16, 1, 0)),
    # ... blocks that do not align, a window wider than the rows' block,
    # sq < sk under a window that leaves the first K/V blocks to no row ...
    ((96, 96, 21, (32, 16, 16, 16)), None),
    ((96, 96, 40, (16, 32, 16, 16)), None),
    ((48, 160, 24, (16, 32, 16, 16)), None),   # dK/dV: two blocks unseen
    # ... and no window: the grid stays, its dead steps name no new block.
    ((4096, 4096, 0, (1024, 1024, 256, 256)), (16, 6, 0)),   # parent: 16/6/6
    ((8192, 8192, 0, (1024, 1024, 1024, 1024)), (64, 28, 0)),
    ((1024, 2048, 0, (1024, 1024, 256, 256)), (2, 0, 0)),
    ((300, 300, 0, (128, 128, 64, 64)), (9, 3, 0)),
    ((128, 256, 0, (64, 128, 32, 64)), (4, 0, 0)),
    ((1024, 1024, 0, (1024, 1024, 256, 256)), (1, 0, 0)),    # gpt2_small_train
]


@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("kernel", ["flash_fwd", "flash_bwd_dkdv",
                                    "flash_bwd_dq"])
@pytest.mark.parametrize("call,counts", _GRID_WALKS)
def test_flash_grid_walk(call, counts, kernel, group):
    """Every kernel's grid, walked in Python through the index maps its
    pallas_call is given (_grid_steps): (a) the live steps compute exactly
    the sub-tiles a brute-force look at positions finds, each once, from the
    block the step's operands were named; (b) a dead step names the block a
    neighbouring step holds, so nothing is copied for it alone; (c) the three
    gauges read what the walk counts."""
    from collections import Counter

    from deeplearning_cfn_tpu.ops.attention import (
        _grid_gauges, _grid_steps, _record_grid, _schedule, _walk)

    sq, sk, window, plan = call
    block_q, block_k, sub_q, sub_k = plan
    by_columns = kernel == "flash_bwd_dkdv"
    cases = _schedule(sq, sk, plan, True, by_columns=by_columns,
                      window=window)
    walk = _walk(sq, sk, plan, True, window, by_columns)
    steps = _grid_steps(walk, cases, group)

    # (a) what is computed, in sub-tiles of the padded score matrix.
    seen = _seen(sq, sk, plan, window)
    want = Counter()
    for r0 in range(0, seen.shape[0], sub_q):
        for c0 in range(0, seen.shape[1], sub_k):
            if seen[r0:r0 + sub_q, c0:c0 + sub_k].any():
                want[r0, c0] = group if by_columns else 1
    got = Counter()
    heads = Counter()
    for at, (outer, block, named, bands) in enumerate(steps):
        iq, kb = (block, outer) if by_columns else (outer, block)
        if bands:
            # the operands in VMEM are the block the step computes on, of
            # one of the group's heads (dK/dV) or of the one K/V head
            assert named[::2] == (0, block) and named[3] == 0, (at, named)
            heads[named[1]] += 1
        for (o0, o1), pieces in bands:
            for i0, i1, _ in pieces:
                (r0, r1), (c0, c1) = ((i0, i1), (o0, o1)) if by_columns \
                    else ((o0, o1), (i0, i1))
                for r in range(iq * block_q + r0, iq * block_q + r1, sub_q):
                    for c in range(kb * block_k + c0, kb * block_k + c1,
                                   sub_k):
                        got[r, c] += 1
    assert got == want
    assert sorted(heads) == list(range(group if by_columns else 1))
    assert len(set(heads.values())) == 1

    # (b) dead steps, and the copies they start for nothing.
    dead = [at for at, step in enumerate(steps) if not step[3]]
    for at in dead:
        neighbours = [steps[n][2] for n in (at - 1, at + 1)
                      if 0 <= n < len(steps)]
        assert steps[at][2] in neighbours, (at, steps[at], neighbours)
    # K/V blocks that lie before every row's window (sq < sk): their dK/dV
    # are zeros, their steps all dead, and each head's q block is copied.
    unseen = [o for o in range(walk.outer_blocks)
              if walk.span(o)[0] > walk.span(o)[1]]
    if window:
        # a windowed call's other dead steps are those clamped at an edge
        assert all(steps[at][1] != steps[at][2][2] for at in dead
                   if steps[at][0] not in unseen)

    # (c) the gauges, a query head.
    _record_grid(kernel, walk, cases, group)
    read = _grid_gauges(kernel, window)
    per_head = group if by_columns else 1
    assert read[:2] == (len(steps) / per_head, len(dead) / per_head)
    # no dead step copies for nothing, but a head's at an unseen K/V block
    assert read[2] <= len(unseen)
    if counts is not None:
        assert read == counts


def test_flash_dead_step_not_taken_by_its_rel():
    """The step a windowed dK/dV walk takes past the last q block stands at
    a ``rel`` that a live tile has too (1024 at the Laguna cell's sliding
    layers: K/V block 3 against a q block 4 that is not there, and K/V
    block 0 against q block 1): it is dead by its place, not by its rel."""
    from deeplearning_cfn_tpu.ops.attention import (
        _bands_at, _schedule, _walk)

    plan = (1024, 1024, 256, 256)
    walk = _walk(4096, 4096, plan, True, 512, True)
    cases = _schedule(4096, 4096, plan, True, by_columns=True, window=512)
    assert walk.steps == 2 and walk.span(3) == (3, 3)
    block, live = walk.block(3, 1)
    assert (block, live, walk.named(3, 1)) == (4, False, 3)
    assert _bands_at(cases, block * 1024 - 3 * 1024)  # rel 1024 has a case
    assert walk.block(0, 1) == (1, True)              # and a live tile


@pytest.mark.parametrize("sq,sk", [(64, 64), (48, 96)])
@pytest.mark.parametrize("h,hk,window,plan", [
    (4, 2, 0, None), (4, 2, 24, None), (6, 2, 24, None),
    (4, 2, 0, (32, 32, 16, 16)), (6, 2, 0, (32, 32, 16, 16)),
    (6, 2, 24, (32, 32, 16, 16)),
    (4, 4, 24, None), (4, 4, 24, (32, 32, 16, 16)),   # a window alone
    # The band's grid (PR 29): a window wider than a block, one that is no
    # multiple of the sub-tile, blocks that do not align, one block of rows.
    (4, 2, 40, (32, 32, 16, 16)), (4, 2, 21, (32, 32, 16, 16)),
    (6, 2, 21, (32, 16, 16, 16)), (4, 2, 40, (16, 32, 16, 16)),
    (4, 4, 7, (16, 16, 16, 16)), (4, 2, 24, (64, 32, 16, 16)),
])
def test_flash_grouped_window_matches_reference(h, hk, window, sq, sk, plan):
    """Grouped K/V heads x {causal, causal + window} x {sq = sk, sq < sk}:
    the kernels (interpret mode; whole pieces, and 2 x 2 and 2 x 3 grids of
    sub-tiled blocks) against the oracle, forward and gradients, dK/dV
    summed over each group."""
    from deeplearning_cfn_tpu.ops.attention import (
        _flash_backward, _flash_forward)

    q, k, v = _grouped_qkv(h, hk, sq, sk)
    g = jnp.asarray(np.random.RandomState(22).normal(0, 1, q.shape),
                    jnp.float32)
    scale = q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, None, True, scale, interpret=True,
                              return_stats=True, plan=plan, window=window)
    grads = _flash_backward(q, k, v, out, lse, g, True, scale, True,
                            plan=plan, window=window)
    ref, vjp = jax.vjp(
        lambda q, k, v: attention_reference(q, k, v, causal=True,
                                            sm_scale=scale, window=window),
        q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=5e-4)
    for name, a, b in zip("qkv", vjp(g), grads):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_window_reference_is_the_band():
    q, k, v = _grouped_qkv(4, 2, 32, 32)
    out = attention_reference(q, k, v, causal=True, window=8)
    kk, vv = (jnp.repeat(t, 2, axis=1) for t in (k, v))
    i, j = np.arange(32)[:, None], np.arange(32)[None, :]
    bias = jnp.where((j <= i) & (i - j < 8), 0.0, -1e30)[None, None]
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(attention_reference(q, kk, vv, bias)),
        atol=1e-6)
    with pytest.raises(ValueError, match="window"):
        fused_attention(q, k, v, causal=False, window=8)
    with pytest.raises(ValueError, match="share"):
        fused_attention(q, k[:, :1], v, causal=True)


# The text of the gradient's jaxpr, kernels' bodies included, of calls that
# have neither grouped heads nor a window, as the kernels stood before either
# came (PR 25's tree, jax 0.9.0; addresses taken out). The benchmark's
# gpt2_small_train runs the first: what it is timed on did not change. After
# an upgrade of jax, record the digests again from the parent commit.
_UNCHANGED_JAXPRS = [
    ((16, 12, 1024, 64), None, True, "469739f2dd020e40"),
    ((1, 2, 2048, 128), None, True, "b3ec920f4baa0be0"),
    ((2, 2, 1024, 64), 2048, True, "e076445553d35398"),
    ((2, 2, 2048, 64), None, False, "d2c055c0e3cc2d29"),
    ((2, 2, 100, 64), None, True, "a29a5b615474f173"),
]


@pytest.mark.parametrize("shape,sk,causal,digest", _UNCHANGED_JAXPRS)
def test_ungrouped_unwindowed_jaxpr_unchanged(shape, sk, causal, digest,
                                              monkeypatch):
    import hashlib
    import re

    from deeplearning_cfn_tpu.ops import attention

    if jax.__version__ != "0.9.0":
        pytest.skip("the digests were recorded under jax 0.9.0")
    # The two names a recomputed block's policy reads (PR 44) are identity
    # equations after the forward kernel; the text is read without them.
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    b, h, s, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, h, sk or s, d), jnp.bfloat16)
    grad = jax.grad(
        lambda q, k, v: fused_attention(
            q, k, v, causal=causal, implementation="interpret"
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(grad)(q, kv, kv)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_window_subtile_gauges():
    """A windowed call's gauges are labelled mask="window" and say what the
    kernels leave out at the Laguna cell's sliding layers, of all sub-tiles
    of the score matrix: the backward pair computes 45 of 256 (256-square
    sub-tiles), the forward 150 of 1024 (128-square since PR 29: 37.5 of
    those 256)."""
    from deeplearning_cfn_tpu.obs.trace import get_tracer

    q = jax.ShapeDtypeStruct((1, 8, 4096, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 4096, 128), jnp.bfloat16)
    jax.eval_shape(jax.grad(
        lambda q, k, v: fused_attention(
            q, k, v, causal=True, window=512, implementation="interpret"
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)), q, kv, kv)
    live = get_tracer().registry.gauge("attention.flash.live_subtile_share")
    assert live.value(kernel="flash_fwd", mask="window") == 150 / 1024
    for kernel in ("flash_bwd_dkdv", "flash_bwd_dq"):
        assert live.value(kernel=kernel, mask="window") == 45 / 256


# -- ring attention ---------------------------------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(devices, causal):
    """Sequence sharded 8 ways over the mesh: the ring result must equal
    single-device full attention — it is exact, not approximate."""
    mesh = Mesh(np.asarray(devices), ("data",))
    q, k, v = _qkv(b=2, h=2, sq=128, sk=128, d=32)
    ref = attention_reference(q, k, v, causal=causal)
    out = ring_attention_sharded(q, k, v, mesh, axis_name="data",
                                 causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grads_flow(devices, causal):
    mesh = Mesh(np.asarray(devices), ("data",))
    q, k, v = _qkv(b=1, h=1, sq=64, sk=64, d=16)

    def loss(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh,
                                              axis_name="data",
                                              causal=causal) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_attention_composed_data_seq_shard(devices):
    """Composed parallelism on a (data=2, seq=4) mesh: batch sharded over
    'data', sequence ring over 'seq' — forward and backward both match the
    single-device oracle."""
    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("data", "seq"))
    q, k, v = _qkv(b=4, h=2, sq=128, sk=128, d=16, seed=12)

    out = ring_attention_sharded(q, k, v, mesh, axis_name="seq",
                                 causal=True, batch_axis="data")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss(q, k, v):
        return jnp.sum(ring_attention_sharded(
            q, k, v, mesh, axis_name="seq", causal=True,
            batch_axis="data") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ring_attention_backward_no_stacked_rotations(devices):
    """The custom VJP must not save every K/V rotation as scan residuals —
    that per-device memory would grow with the axis size, defeating
    sequence parallelism. Walk the grad jaxpr for stacked [axis_size-1,...]
    K/V-shaped tensors."""
    mesh = Mesh(np.asarray(devices), ("data",))
    b, h, s, d = 1, 1, 64, 16
    q, k, v = _qkv(b=b, h=h, sq=s, sk=s, d=d)

    def loss(q, k, v):
        return jnp.sum(ring_attention_sharded(q, k, v, mesh,
                                              axis_name="data") ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    n_rot = 7  # axis_size - 1
    s_local = s // 8
    offenders = []

    def walk(jx):
        for eqn in jx.eqns:
            for var in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(getattr(var, "aval", None), "shape", ())
                if len(shape) == 5 and shape[0] == n_rot and \
                        shape[-2:] == (s_local, d):
                    offenders.append((eqn.primitive.name, shape))
            for param in eqn.params.values():
                inner = getattr(param, "jaxpr", param)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jaxpr.jaxpr)
    assert not offenders, f"stacked per-rotation residuals: {offenders}"


# -- ulysses (all-to-all) sequence parallelism ------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(devices, causal):
    """Sequence sharded 8 ways, heads reswizzled via all_to_all: the result
    must equal single-device full attention — exact, like the ring."""
    mesh = Mesh(np.asarray(devices), ("data",))
    q, k, v = _qkv(b=2, h=8, sq=128, sk=128, d=32)
    ref = attention_reference(q, k, v, causal=causal)
    out = ulysses_attention_sharded(q, k, v, mesh, axis_name="data",
                                    causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_grads_match(devices, causal):
    mesh = Mesh(np.asarray(devices), ("data",))
    q, k, v = _qkv(b=1, h=8, sq=64, sk=64, d=16)

    def loss(q, k, v):
        return jnp.sum(ulysses_attention_sharded(
            q, k, v, mesh, axis_name="data", causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ulysses_composed_data_seq_shard(devices):
    """Composed (data=2, seq=4) mesh: batch over 'data', sequence all-to-all
    over 'seq' — forward and backward match the single-device oracle."""
    mesh = Mesh(np.asarray(devices).reshape(2, 4), ("data", "seq"))
    q, k, v = _qkv(b=4, h=4, sq=128, sk=128, d=16, seed=12)

    out = ulysses_attention_sharded(q, k, v, mesh, axis_name="seq",
                                    causal=True, batch_axis="data")
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)

    def loss(q, k, v):
        return jnp.sum(ulysses_attention_sharded(
            q, k, v, mesh, axis_name="seq", causal=True,
            batch_axis="data") ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grads, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_ulysses_rejects_indivisible_heads(devices):
    mesh = Mesh(np.asarray(devices), ("data",))
    q, k, v = _qkv(b=1, h=6, sq=64, sk=64, d=16)  # 6 % 8 != 0
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention_sharded(q, k, v, mesh, axis_name="data")


def test_ulysses_agrees_with_ring(devices):
    """The two sequence-parallel strategies are interchangeable: same
    inputs, same mesh → same attention output."""
    mesh = Mesh(np.asarray(devices), ("data",))
    q, k, v = _qkv(b=2, h=8, sq=128, sk=128, d=16, seed=5)
    a = ulysses_attention_sharded(q, k, v, mesh, axis_name="data",
                                  causal=True)
    b = ring_attention_sharded(q, k, v, mesh, axis_name="data", causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-5, rtol=2e-5)
