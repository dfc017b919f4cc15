"""The row kernel (``ops/rows.py``) interpreted on the CPU against the sum it
is: ``out[i] = sum over j < count[i] of weight[i, j] * src[idx[i, j]]``, at
one and eight slots a row, at the edges of its tiles and of its SMEM blocks,
in bfloat16 (a row is half of a pair's words) and float32. What the chip's
compiler makes of it is ``tests/test_chip_compile*.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.ops.rows import _SLOTS, _SMEM, fits, \
    sum_live_rows


def _plain(src, idx, count, weight, dot_with, out_dtype):
    n, k = idx.shape
    live = jnp.arange(k)[None, :] < count[:, None]
    got = jnp.where(live[..., None],
                    src[jnp.where(live, idx, 0)].astype(jnp.float32), 0)
    total = jnp.zeros((n, src.shape[1]), jnp.float32)
    for j in range(k):
        total = total + got[:, j] * (1.0 if weight is None
                                     else weight[:, j, None])
    dots = None if dot_with is None else jnp.sum(
        got * dot_with.astype(jnp.float32)[:, None, :], axis=-1)
    return total.astype(out_dtype or src.dtype), dots


def _case(k, rows, width, dtype, live, seed=0):
    """``rows`` output rows of ``k`` slots over a source of 96 rows (an odd
    count in one case, so a bfloat16 source is padded to whole pairs);
    ``live`` is how many of the output rows have a live slot, the first
    ones, as a buffer's live rows are."""
    rng = np.random.RandomState(seed)
    m = 96 + (seed % 2)
    src = jnp.asarray(rng.randn(m, width), dtype)
    idx = jnp.asarray(rng.randint(0, m, (rows, k)), jnp.int32)
    count = rng.randint(1, k + 1, (rows,))
    count[live:] = 0
    weight = jnp.asarray(rng.rand(rows, k), jnp.float32)
    return src, idx, jnp.asarray(count, jnp.int32), weight


# A tile holds ``_SLOTS / k`` rows: 512 at one slot a row, 64 at eight.
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("k,width", [(1, 256), (8, 384)])
@pytest.mark.parametrize("live", ["none", "one_row", "a_tile_less_one",
                                  "a_tile", "a_tile_and_one", "every_row"])
def test_live_rows_are_summed_and_dead_ones_read_zero(live, k, width, dtype):
    tile = _SLOTS // k
    rows = 2 * tile + 24
    n_live = {"none": 0, "one_row": 1, "a_tile_less_one": tile - 1,
              "a_tile": tile, "a_tile_and_one": tile + 1,
              "every_row": rows}[live]
    src, idx, count, weight = _case(k, rows, width, dtype, n_live, seed=k)
    got = sum_live_rows(src, idx, count, weight, interpret=True)
    want, _ = _plain(src, idx, count, weight, None, None)
    assert got.shape == (rows, width) and got.dtype == dtype
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=2e-6,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6)
    assert not np.any(np.asarray(got[n_live:], np.float32))


@pytest.mark.parametrize("k", [1, 8])
def test_rows_past_an_smem_block_and_the_dots(k):
    """More rows than one SMEM block of indices holds (the next tile's DMAs
    are not started across a block's edge), no weights, a float32 result
    from bfloat16 rows, and the rows' dots with ``dot_with`` from the same
    pass."""
    rows = _SMEM // k + _SLOTS // k + 8
    src, idx, count, _ = _case(k, rows, 128, jnp.bfloat16, rows - 40)
    dot_with = jnp.asarray(np.random.RandomState(1).randn(rows, 128),
                           jnp.bfloat16)
    got, dots = sum_live_rows(src, idx, count, None, dot_with,
                              out_dtype=jnp.float32, interpret=True)
    want, want_dots = _plain(src, idx, count, None, dot_with, jnp.float32)
    assert got.dtype == jnp.float32 and dots.shape == (rows, k)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-6)
    np.testing.assert_allclose(dots, want_dots, atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("n_live", [0, 1, 511, 512, 513, 1100])
def test_one_count_is_a_buffers_live_rows(n_live):
    """``count`` one number: the first ``count`` rows' one slot is live, and
    the lists the scalar side walks are made without a sort."""
    src, idx, _, weight = _case(1, 1100, 128, jnp.bfloat16, 0)
    got = sum_live_rows(src, idx, jnp.int32(n_live), weight, interpret=True)
    count = (jnp.arange(1100) < n_live).astype(jnp.int32)
    want, _ = _plain(src, idx, count, weight, None, None)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    with pytest.raises(ValueError):
        sum_live_rows(src, jnp.zeros((8, 2), jnp.int32), jnp.int32(3),
                      interpret=True)


def test_kernel_takes_whole_lane_tiles_of_two_dtypes():
    assert fits(2304, jnp.bfloat16) and fits(2048, jnp.float32)
    assert not fits(2304 + 64, jnp.bfloat16) and not fits(2048, jnp.float16)
    with pytest.raises(ValueError):
        sum_live_rows(jnp.zeros((8, 48)), jnp.zeros((4, 1), jnp.int32),
                      jnp.ones((4,), jnp.int32), interpret=True)
