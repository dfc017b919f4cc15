"""Rows fetched one by one, the live ones alone: one Pallas kernel.

    out[i] = sum over j < count[i] of  weight[i, j] * src[idx[i, j]]

``src [M, F]`` stays in HBM; every live slot ``(i, j)`` is one DMA of its row
into VMEM, the products and the sum are float32 and the result is cast once.
A slot past ``count[i]`` costs nothing: no DMA, no arithmetic for a group of
rows whose slots ``j`` are all dead, and a tile without a live slot writes
zeros. XLA's gather has a static shape and pays for every slot, dead or live
(``tools/moe_rows_sweep.py``; PERF.md, PR 36); an expert layer that holds 16
of 64 experts has three dead slots in four (``models/moe.py:take_rows``,
``sum_rows``, the kernel's four uses).

**What a DMA can fetch.** An array ``[M, F]`` lies in HBM in tiles of 8
sublanes by 128 lanes of 32-bit words (a bfloat16 row shares its words with
its neighbour), and Mosaic takes no slice of a tiled dimension that is not
whole tiles: a row cannot be named. So the source is handed over as ``[M / 2,
2, F]`` in bfloat16 (``[M, 1, F]`` in float32), whose tile is one pair of
rows: the DMA fetches the pair ``idx >> 1``, the kernel reads the buffer as
32-bit words and takes the half ``idx & 1`` (the even row is the low half).
XLA makes that view by one copy of the source, which is part of the kernel's
price (0.67 ms for 151 MB, 2.66 ms for 604 MB: ``view`` in the sweep).

**The grid** is over tiles of 512 slots (``512 / K`` output rows, 4.7 MB of
VMEM at rows of 2304), sequential. What costs is starting a DMA (40-60 ns
each from a loop that tests every row's count, ``tools/moe_rows_sweep.py``,
PR 36, call 1: the scalar core, not the bytes), so the scalar side walks two
lists made outside, a tile's live slots first: the source's row and the place
in the tile's buffer, eight DMAs between two tests of the loop's bound. The
lists come to SMEM eight tiles at a time (XLA tiles a 1-D int32 array by
1,024, so a smaller block is refused); inside such a block the next tile's
DMAs are started before this tile's are waited for (two buffers, a semaphore
each). The tile's rows are summed in groups of 8 (16 where the result is
16-bit: a packed tile), slot by slot, a slot skipped where no row of the
group has it.

With ``dot_with [N, F]`` a second result ``dots[i, j] = sum over the row of
src[idx[i, j]] * dot_with[i]`` (float32, no weight; 0 for a dead slot) comes
out of the same pass: ``sum_rows``' cotangent of the weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANES = 128
# Entries of a list one SMEM block holds (XLA tiles a 1-D int32 array by
# 1,024 and a smaller block is refused): eight tiles' slots.
_SMEM = 4096
# (row, slot) pairs one of the two VMEM buffers holds: a tile.
_SLOTS = 512
# DMAs started, or waited for, between two tests of a loop's bound.
_UNROLL = 8


def fits(width: int, dtype) -> bool:
    """Whether the kernel can move rows of ``width`` elements of ``dtype``:
    whole lane tiles of bfloat16 or float32."""
    return width % _LANES == 0 and jnp.dtype(dtype) in (
        jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


def _kernel(live_ref, deep_ref, from_ref, to_ref, sel_ref, weight_ref,
            src_ref, *rest, tm: int, k: int, group: int, packed: bool,
            with_dot: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if with_dot:
        dot_ref, out_ref, dots_ref, buf, acc, sems = rest
    else:
        out_ref, buf, acc, sems = rest
    i = pl.program_id(0)
    per_block = _SMEM // _SLOTS
    words = buf.bitcast(jnp.uint32) if packed else buf
    width = out_ref.shape[-1]

    def in_eights(n, body):
        """``body(u)`` for ``u < n``, ``_UNROLL`` between two tests."""
        def eight(e, carry):
            for v in range(_UNROLL):
                body(e * _UNROLL + v)
            return carry

        def one(u, carry):
            body(u)
            return carry

        whole = n // _UNROLL
        jax.lax.fori_loop(0, whole, eight, None)
        jax.lax.fori_loop(whole * _UNROLL, n, one, None)

    def start(tile, slot):
        """Every live slot of ``tile`` on its way to buffer ``slot``."""
        base = (tile % per_block) * _SLOTS

        def fetch(u):
            at = from_ref[base + u]
            pltpu.make_async_copy(
                src_ref.at[at >> 1 if packed else at],
                buf.at[slot, to_ref[base + u]], sems.at[slot]).start()

        in_eights(live_ref[tile], fetch)

    slot = i % 2

    @pl.when(i % per_block == 0)
    def _():
        start(i, slot)

    @pl.when(jnp.logical_and((i + 1) % per_block != 0,
                             i + 1 < pl.num_programs(0)))
    def _():
        start(i + 1, 1 - slot)

    in_eights(live_ref[i], lambda u: pltpu.make_async_copy(
        src_ref.at[0], buf.at[slot, 0], sems.at[slot]).wait())

    groups = tm // group

    def rows(g, carry):
        at = pl.ds(pl.multiple_of(g * group, group), group)
        deepest = deep_ref[(i % (_SMEM // groups)) * groups + g]
        sel = sel_ref[at, :]
        weight = weight_ref[at, :]
        acc[...] = jnp.zeros_like(acc)
        if with_dot:
            dots_ref[at, :] = jnp.zeros((group, k), jnp.float32)
        for j in range(k):
            @pl.when(j < deepest)
            def _(j=j):
                x = words[slot, pl.ds(j * tm + g * group, group)].reshape(
                    group, width)
                which = sel[:, j:j + 1]
                if packed:
                    # The odd row of a pair is the high half of its words.
                    x = pltpu.bitcast(
                        jnp.where((which & 1) == 1,
                                  x & jnp.uint32(0xFFFF0000), x << 16),
                        jnp.float32)
                x = jnp.where(which >= 0, x, 0.0)
                acc[...] += x * weight[:, j:j + 1]
                if with_dot:
                    dots_ref[at, j:j + 1] = jnp.sum(
                        x * dot_ref[at, :].astype(jnp.float32), axis=1,
                        keepdims=True)
        out_ref[at, :] = acc[...].astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, groups, rows, None)


def sort_with(key, value):
    """``value`` (32-bit) in the order that sorts the int32 ``key``, with the
    sorted keys: ``jnp.argsort``'s own sort of two int32 operands, so that a
    step that already sorts as many integers compiles no second sort (20 s
    each for 262,144 by the chip's compiler)."""
    key, moved = jax.lax.sort(
        (key, jax.lax.bitcast_convert_type(value, jnp.int32)), num_keys=1,
        is_stable=True)
    return key, jax.lax.bitcast_convert_type(moved, value.dtype)


def _lists(sel, tm: int, tiles: int):
    """What the kernel's scalar side walks: for every tile the rows of the
    source its live slots read and where in the tile's buffer each lands
    (slot-major, ``j * tm + r``), the live ones first, and how many they
    are. One sort of the slots by (tile, dead, place): a stable partition of
    each tile's 512."""
    k = sel.shape[1]
    place = jnp.arange(k, dtype=jnp.int32)[None, :] * tm \
        + (jnp.arange(tiles * tm, dtype=jnp.int32) % tm)[:, None]
    tile = (jnp.arange(tiles * tm, dtype=jnp.int32) // tm)[:, None]
    key = (2 * tile + (sel < 0)) * _SLOTS + place
    key, source = sort_with(key.reshape(-1), sel.reshape(-1))
    return jnp.sum((sel >= 0).reshape(tiles, -1), axis=1, dtype=jnp.int32), \
        source, key % _SLOTS


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def sum_live_rows(src, idx, count, weight=None, dot_with=None, *,
                  out_dtype=None, interpret: bool = False):
    """``out [N, F]`` as the module's first line has it, in ``out_dtype``
    (``src``'s where none is given): ``src [M, F]`` bfloat16 or float32 with
    ``F`` whole lane tiles (:func:`fits`), ``idx [N, K]`` int32 with each
    row's live slots first, ``count [N]`` how many they are, ``weight [N, K]``
    float32 (1 where none is given). A ``count`` that is one number says that
    the first ``count`` rows have their one slot live (``K`` = 1: a buffer's
    live rows), and the lists need no sort. With ``dot_with [N, F]`` it
    returns ``(out, dots [N, K])``.

    Jitted, so that the layers of a step and the backward pass's
    recomputation trace and lower each of the kernel's uses once: a
    ``pallas_call`` traced anew at each of 40 places (both buffers' branches
    of four layers) made Mellum2's step 35 s to trace and lower on this
    sandbox's CPU where it was 17 s so (every process start pays it, compile
    cache or none)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k = idx.shape
    m, width = src.shape
    if not fits(width, src.dtype):
        raise ValueError(f"rows of {width} {src.dtype} are not whole tiles")
    if k > _SLOTS // 16 or _SLOTS % k:
        raise ValueError(f"{k} slots a row")
    out_dtype = jnp.dtype(out_dtype or src.dtype)
    packed = src.dtype == jnp.bfloat16
    per = 2 if packed else 1
    if m % per:
        src = jnp.pad(src, ((0, 1), (0, 0)))
    group = 16 if out_dtype.itemsize == 2 else 8
    tm = _SLOTS // k
    tiles = -(-n // tm)
    more = lambda a, fill=0: jnp.pad(
        a, ((0, tiles * tm - n),) + ((0, 0),) * (a.ndim - 1),
        constant_values=fill)
    count = jnp.asarray(count, jnp.int32)
    if count.ndim == 0:
        if k != 1:
            raise ValueError("one count for rows of several slots")
        count = (jnp.arange(n) < count).astype(jnp.int32)
        sel = more(jnp.where(count[:, None] > 0, idx, -1), -1)
        live = jnp.clip(jnp.sum(count) - jnp.arange(tiles) * tm, 0, tm)
        source = sel.reshape(-1)
        place = jnp.arange(tiles * tm, dtype=jnp.int32) % tm
    else:
        sel = more(jnp.where(jnp.arange(k)[None, :] < count[:, None], idx,
                             -1), -1)
        live, source, place = _lists(sel, tm, tiles)
    deepest = jnp.max(more(count).reshape(-1, group), axis=1)
    whole = lambda a: jnp.pad(a, (0, -a.shape[0] % _SMEM))
    weight = jnp.ones((n, k), jnp.float32) if weight is None \
        else weight.astype(jnp.float32)
    groups = tm // group
    smem = lambda per_tile: pl.BlockSpec(
        (_SMEM,), lambda i, live: (i // (_SMEM // per_tile),),
        memory_space=pltpu.SMEM)
    by_tile = lambda cols: pl.BlockSpec((tm, cols), lambda i, live: (i, 0))
    operands = [live, whole(deepest), whole(source),
                whole(place), sel, more(weight), src.reshape(-1, per, width)]
    in_specs = [smem(groups), smem(_SLOTS), smem(_SLOTS), by_tile(k),
                by_tile(k), pl.BlockSpec(memory_space=pl.ANY)]
    out_shape = [jax.ShapeDtypeStruct((tiles * tm, width), out_dtype)]
    out_specs = [by_tile(width)]
    if dot_with is not None:
        operands.append(more(dot_with))
        in_specs.append(by_tile(width))
        out_shape.append(jax.ShapeDtypeStruct((tiles * tm, k), jnp.float32))
        out_specs.append(by_tile(k))
    got = pl.pallas_call(
        functools.partial(_kernel, tm=tm, k=k, group=group, packed=packed,
                          with_dot=dot_with is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(tiles,), in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((2, _SLOTS, per, width), src.dtype),
                            pltpu.VMEM((group, width), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=out_shape,
        # Sequential: a tile starts the next one's DMAs.
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=48 * 2 ** 20),
        interpret=interpret, name="live_rows",
    )(*operands)
    got = [a[:n] for a in got] if tiles * tm != n else got
    return got[0] if dot_with is None else tuple(got)
