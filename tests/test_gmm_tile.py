"""The tile of a grouped product (``models/moe.py:gmm_tile``): a rule on the
shape a kernel is asked, a tile a kernel, under a VMEM budget; the row buffer
follows the longest row tile; megablox's kernels interpreted on the CPU give
``ragged_dot``'s gradients through the layer's own VJP."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning_cfn_tpu.models import moe
from deeplearning_cfn_tpu.obs.trace import get_tracer

KERNELS = ("gmm", "gmm_t", "tgmm")
# cell -> (a rank's usual row buffer, groups, d_model, expert width): the
# three expert cells of BENCHMARK.json (PERF.md section 4).
CELLS = {
    "mellum2_12b_train_8k_ep4": (131072, 16, 2304, 896),
    "laguna_xs2_train_4k": (16384, 32, 2048, 512),
    "zaya1_8b_train_4k": (8192, 8, 2048, 2048),
}
PRODUCTS = [(cell, product, kernel) for cell in CELLS
            for product in ("in", "out") for kernel in KERNELS]


def _asked(cell, product, kernel):
    """``(m, k, n, groups)`` as ``kernel`` of the cell's product sees it."""
    m, groups, d, w = CELLS[cell]
    k, n = (d, 2 * w) if product == "in" else (w, d)
    return ((m, n, k) if kernel == "gmm_t" else (m, k, n)) + (groups,)


@pytest.mark.parametrize("cell,product,kernel", PRODUCTS)
def test_tile_divides_its_product_and_fits(cell, product, kernel):
    m, k, n, groups = _asked(cell, product, kernel)
    tm, tk, tn = moe.gmm_tile(kernel, m, k, n, groups)
    # No last tile is padded and masked in any product of the three cells.
    assert k % tk == 0 and n % tn == 0, (tm, tk, tn)
    assert tk % 128 == 0 and tn % 128 == 0
    assert moe._whole_tiles(m) == m and m % tm == 0
    assert moe.gmm_tile_vmem(kernel, tm, tk, tn) <= moe._GMM_VMEM


@functools.lru_cache(maxsize=None)
def _swept():
    """The committed lines of the chip sweep the rule's constants were read
    from: ``{(shape, product, kernel): {tile: ms}}``, groups as the cells'
    routers leave them, and the same for the one tile there was."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "gmm_tile_sweep_pr38.jsonl")
    times, was = {}, {}
    with open(path) as fh:
        for line in map(json.loads, fh):
            if line.get("sizes") != "uneven":
                continue
            key = (line["shape"], line["product"], line["kernel"])
            times.setdefault(key, {})[tuple(line["tile"])] = line["ms"]
            if line["was"]:
                was[key] = tuple(line["tile"])
    return times, was


@pytest.mark.parametrize("cell,product,kernel", PRODUCTS)
def test_tile_is_what_the_sweep_allows(cell, product, kernel):
    """At every cell's shape the rule's tile was timed on the chip and read
    within 4 % of the fastest tile of its kernel; where it is not the one
    tile of PRs 26-37, (256, 1024, 512) clipped (swept for Laguna at PR 26),
    it read faster than that one by 3 % or more."""
    times, was = _swept()
    key = (cell.split("_")[0], product, kernel)
    tile = moe.gmm_tile(kernel, *_asked(cell, product, kernel))
    assert tile in times[key], (tile, sorted(times[key]))
    assert times[key][tile] <= 1.04 * min(times[key].values())
    assert tile == was[key] or times[key][tile] <= 0.97 * times[key][was[key]]


@pytest.mark.parametrize("x,cap,tile", [
    (2304, 1024, 768), (2304, 1152, 1152), (1792, 1024, 896),
    (896, 1024, 896), (2048, 1024, 1024), (512, 1024, 512),
    (4096, 896, 512), (2304, 896, 768),
    # 17 x 128: nothing over half the cap divides, so the last tile pads.
    (2176, 1024, 1024),
    # Not whole lane tiles: whole under the cap, padded over it.
    (1000, 1024, 1000), (3000, 1024, 1024)])
def test_dividing(x, cap, tile):
    assert moe._dividing(x, cap) == tile


@pytest.mark.parametrize("budget_mib", [3, 4, 8, 13])
def test_a_tile_over_the_budget_cannot_be_chosen(monkeypatch, budget_mib):
    """(256, 1152, 1792) ran out of VMEM in ``tgmm`` on the chip (PR 35): by
    the reckoning it is over the 16 MiB a kernel has. Whatever the budget,
    the rule's tile is under it (a narrow budget may pad the columns)."""
    assert moe.gmm_tile_vmem("tgmm", 256, 1152, 1792) > 16 * 2 ** 20
    monkeypatch.setattr(moe, "_GMM_VMEM", budget_mib * 2 ** 20)
    for kernel in KERNELS:
        for m, k, n, groups in ((131072, 2304, 1792, 16),
                                (8192, 4096, 2048, 8), (65536, 8192, 28672, 8)):
            tile = moe.gmm_tile(kernel, m, k, n, groups)
            assert moe.gmm_tile_vmem(kernel, *tile) <= moe._GMM_VMEM
            assert m % tile[0] == 0 and tile[1] % 128 == 0 \
                and tile[2] % 128 == 0


@pytest.mark.parametrize("rows", [1, 8, 100, 256, 300, 512, 513, 1000, 4096,
                                  8192, 16384, 20480, 131072])
def test_row_tile_divides_the_buffer(rows):
    """The row buffer is rounded to what the longest row tile divides, and
    whatever row tile the rule takes divides the buffer (megablox raises
    where it does not)."""
    buffer = moe._whole_tiles(rows)
    assert buffer >= rows and buffer - rows < moe._ROW_TILE
    for kernel in KERNELS:
        for groups in (1, 8, 64):
            assert buffer % moe.gmm_tile(kernel, buffer, 2304, 1792,
                                         groups)[0] == 0


@pytest.mark.parametrize("cell,pairs,count,experts", [
    ("mellum2_12b_train_8k_ep4", 4 * 8192 * 8, 16, 64),
    ("laguna_xs2_train_4k", 8192 * 8, 32, 256),
    ("zaya1_8b_train_4k", 8192, 8, 16)])
def test_the_cells_buffers_keep_their_size(cell, pairs, count, experts):
    """``_rank_part``'s usual buffer in the three cells: what it was under
    the 256-row rounding, so ``_in_passes``' windows and the compiled
    footprints do not move."""
    usual = moe._whole_tiles(int(moe._BUFFER_SHARE * pairs * count / experts))
    assert usual == CELLS[cell][0]
    assert min(usual, pairs) % 512 == 0


def _calls():
    return get_tracer().registry.counter("moe.gmm.calls").series()


def _new_calls(before):
    return {dict(key)["kernel"]: (dict(key)["tile"], dict(key)["divides"])
            for key, n in _calls().items() if n > before.get(key, 0)}


def _problem(m, k, n, sizes, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    lhs = jax.random.normal(keys[0], (m, k), jnp.bfloat16)
    rhs = jax.random.normal(keys[1], (len(sizes), k, n), jnp.bfloat16) * 0.1
    sizes = jnp.asarray(sizes, jnp.int32)
    live = (jnp.arange(m) < jnp.sum(sizes))[:, None]
    # A cotangent that is zero on the rows past the last group, as the
    # layer's mask makes it.
    ct = jnp.where(live, jax.random.normal(keys[2], (m, n), jnp.float32), 0)
    return lhs, rhs, sizes, live, ct


@pytest.mark.parametrize("m,k,n,sizes", [
    (512, 256, 384, [100, 0, 211, 137]),          # an empty group, dead rows
    (1024, 128, 256, [256, 256, 256, 256]),       # even, every row live
    (1024, 384, 128, [1, 700, 3]),                # one group over many tiles
])
def test_gradients_match_ragged_dot(m, k, n, sizes):
    """Forward, the rows' gradient (``gmm`` with the weights transposed) and
    the weights' (``tgmm``), each at its own tile, megablox interpreted."""
    lhs, rhs, sizes, live, ct = _problem(m, k, n, sizes)

    def loss(product):
        return lambda a, b: jnp.sum(jnp.where(
            live, product(a, b), 0).astype(jnp.float32) * ct)

    before = _calls()
    ours = loss(lambda a, b: moe.megablox_gmm(a, b, sizes, True))
    theirs = loss(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=a.dtype))
    np.testing.assert_allclose(float(ours(lhs, rhs)), float(theirs(lhs, rhs)),
                               rtol=2e-3)
    got = jax.grad(ours, argnums=(0, 1))(lhs, rhs)
    want = jax.grad(theirs, argnums=(0, 1))(lhs, rhs)
    # The rows' gradient past the last group is undefined, as the forward's
    # rows there are: the layer reads neither.
    for g, w in ((jnp.where(live, got[0], 0), want[0]), (got[1], want[1])):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.isfinite(g).all()
        np.testing.assert_allclose(g, w, atol=2e-2 * np.abs(w).max())
    assert set(_new_calls(before)) == set(KERNELS)
    assert all(divides == "yes" for _, divides in _new_calls(before).values())


def test_a_padded_product_says_so():
    """``divides=no``: a contraction of 33 x 128 is over the cap and nothing
    over half the cap divides it, so it is two tiles of 4096, the second
    padded and masked; the result is right all the same."""
    m, k, n = 256, 4224, 128
    lhs, rhs, sizes, live, _ = _problem(m, k, n, [100, 156])
    before = _calls()
    got = moe.megablox_gmm(lhs, rhs, sizes, True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=lhs.dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.25)
    tile = moe.gmm_tile("gmm", m, k, n, 2)
    assert k % tile[1] and _new_calls(before) == {
        "gmm": ("x".join(map(str, tile)), "no")}


def test_off_the_tpu_ragged_dot_is_untouched():
    lhs, rhs, sizes, _, _ = _problem(64, 32, 48, [10, 54])
    before = _calls()
    got = moe.grouped_matmul(lhs, rhs, sizes)
    assert got.shape == (64, 48) and _new_calls(before) == {}
