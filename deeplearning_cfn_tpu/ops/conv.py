"""Mamba-2's short causal convolution with its bias and silu, as one Pallas
kernel forward and one backward.

A channel ``c`` of ``x [B, S, C]`` is convolved along the sequence with its
own ``taps`` numbers, zeros before position 0:

    pre[t] = bias + sum_j w[j] * x[t - (taps - 1 - j)]
    y[t]   = pre[t] * sigmoid(pre[t])

in float32 from ``x`` as it arrives (bfloat16 in training), rounded once to
``x``'s dtype at the end. ``models/ssm.py:CausalConv`` writes the same as
``taps`` shifted multiply-adds of ``jnp``, each shift (and in the backward
each shift's transpose and each tap's sum over the tokens) a pass of XLA's
over a float32 ``[B, S, C]``; that stays the carrier off the TPU and for the
shapes the kernels do not tile (:func:`conv_path` says which, from what the
call can see).

**The kernels** (``causal_conv_fwd``, ``causal_conv_bwd`` under a
``jax.custom_vjp`` whose residuals are the inputs: no float32 ``[B, S, C]``
reaches HBM in either pass). The grid runs over (batch, token block); a step
holds its ``[token block, C]`` tile of ``x``, every channel, and walks it in
chunks of ``_ROWS`` rows by up to ``_CHUNK_LANES`` lanes that live in
registers: widen, form the shifted terms by sublane rolls of the chunk with
the 8 rows before it on top, multiply-add, silu, cast, store. The rows before
a chunk are the chunk above's last 8, handed down the loop; before a tile's
first chunk they come through a second ``BlockSpec`` on ``x`` that fetches
the 16 rows (bfloat16's sublane group) before the tile, zeroed at the
sequence's first. The result is written as the arrays its reader wants: the
channels cut at ``splits`` (whole lane tiles), one output each, so that
Mamba-2's ``x | B | C`` need no slice after.

The backward walks the token blocks, and a tile's chunks, from the last to
the first. It computes ``pre`` again from ``x`` (the same terms), ``dpre = dy
* silu'(pre)`` and ``dx[t] = sum_j w[j] * dpre[t + (taps - 1 - j)]``; the
``dpre`` after a chunk is the chunk below's first 8 rows, handed up the loop
and from tile to tile in a VMEM scratch (zeros past the last position).
``dw[j] = sum_t dpre[t] * x[t - (taps - 1 - j)]`` and ``dbias = sum_t
dpre[t]`` are added up in float32 a sublane (``[8, C]`` each, vector adds
alone) into an output block the token axis revisits; the 8 sublanes and the
batch are summed outside, over ``[B, 8 * (taps + 1), C]``.

``tools/conv_block_sweep.py`` times the kernels by token block and chunk.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

_LANES = 128
# Rows of float32 a register holds: what a chunk keeps of its neighbour, so
# ``taps - 1`` may not pass it.
_HALO = 8
# Rows of the block that brings the rows before a tile: a sublane group of
# bfloat16 (two of float32).
_EDGE = 16
# A chunk of the walk: ``_ROWS`` by up to ``_CHUNK_LANES`` float32 numbers
# are ``_ROWS / 8 * _CHUNK_LANES / 128`` registers a live array, and the
# backward holds a dozen arrays of the 64 registers there are; both kernels
# are bound by the vector unit's slots (about 20 operations a register of
# 1,024 numbers forward, 50 backward), and a larger chunk gives the
# scheduler more to fill them with until the spills cost more. Swept on a
# v5e chip with the token block (tools/conv_block_sweep.py and its .jsonl).
_ROWS = 32
_CHUNK_LANES = 256
_TOKEN_BLOCKS = (512, 256, 128, 64, 32, 16)
_VMEM_LIMIT = 64 * 2 ** 20


def token_block(seq: int, channels: int, itemsize: int) -> Optional[int]:
    """Tokens a grid step holds: the largest of ``_TOKEN_BLOCKS`` that
    divides the sequence and keeps the backward's six tiles (``x``, ``dy``,
    ``dx``, each in two buffers) inside half the VMEM limit; ``None`` where
    none does."""
    for ts in _TOKEN_BLOCKS:
        if seq % ts == 0 and 6 * ts * channels * itemsize <= _VMEM_LIMIT // 2:
            return ts
    return None


def conv_path(implementation: str, shape, taps: int,
              splits: Sequence[int] = (), itemsize: int = 2
              ) -> Tuple[str, bool]:
    """``(path, interpret)`` for ``silu(conv(x) + bias)`` at ``shape = (B,
    S, C)``: ``"kernel"`` where the channels and every cut of ``splits`` are
    whole lane tiles, the sequence is whole token blocks, ``taps - 1`` fits
    the rows a chunk keeps of its neighbour and ``implementation`` is
    ``auto`` on a TPU, ``pallas`` or ``interpret`` (the kernels in
    interpreter mode: the tests' way in); else ``"xla"``."""
    if implementation not in ("auto", "pallas", "interpret", "reference"):
        raise ValueError(f"unknown implementation {implementation!r}")
    _, seq, channels = shape
    fits = (1 <= taps <= _HALO + 1
            and all(c % _LANES == 0 for c in (channels, *splits))
            and token_block(seq, channels, itemsize) is not None)
    if implementation == "auto":
        wanted = jax.default_backend() == "tpu"
    else:
        wanted = implementation in ("pallas", "interpret")
    return ("kernel" if fits and wanted else "xla",
            implementation == "interpret")


def _segments(channels: int, splits: Sequence[int]):
    """``(start, width)`` of each array the channels are cut into."""
    cuts = (0, *splits, channels)
    return tuple((a, b - a) for a, b in zip(cuts, cuts[1:]))


def _chunk_lanes(width: int, wanted: int) -> int:
    """Lanes a chunk spans in a segment of ``width``: the most whole lane
    tiles up to ``wanted`` that divide it."""
    return max(n for n in range(_LANES, max(wanted, _LANES) + 1, _LANES)
               if width % n == 0)


def _lanes(start, lanes):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(start, _LANES), lanes)


def _rows(start, rows):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(start, rows), rows)


def _shifted(before, cur, taps):
    """``[x[t - (taps - 1 - j)] for j]`` over a chunk's rows: ``cur`` with
    the ``_HALO`` rows before it on top, rolled down the sublanes."""
    from jax.experimental.pallas import tpu as pltpu

    ext = jnp.concatenate([before, cur], axis=0)
    return [cur if j == taps - 1 else
            pltpu.roll(ext, taps - 1 - j, 0)[_HALO:] for j in range(taps)]


def _sigmoid(t):
    """``1 / (1 + exp(-t))`` as ``(1 + tanh(t / 2)) / 2``: one operation of
    the transcendental unit and two of the vector unit, in float32, where
    the quotient is a reciprocal, its refinement and the handling of its
    odd arguments, a dozen."""
    return 0.5 * jnp.tanh(0.5 * t) + 0.5


def _pre(w, bias, terms):
    """``bias + sum_j w[j] * terms[j]``, summed in ``CausalConv``'s order."""
    acc = w[0] * terms[0]
    for wj, term in zip(w[1:], terms[1:]):
        acc = acc + wj * term
    return bias + acc


def _of_chunk(il, start, lanes, edge_ref, w_ref, bias_ref, first):
    """What a lane chunk's walk holds: its lanes in ``x`` (``src``) and in
    its segment's own array (``dst``), the taps' rows, the bias, and the
    ``_HALO`` rows before the tile, zeros at the sequence's first."""
    src, dst = _lanes(start + il * lanes, lanes), _lanes(il * lanes, lanes)
    w = [w_ref[j:j + 1, src] for j in range(w_ref.shape[0])]
    edge = edge_ref[0, :, src].astype(jnp.float32)[_EDGE - _HALO:]
    return src, dst, w, bias_ref[:, src], \
        jnp.where(first, jnp.zeros_like(edge), edge)


def _fwd_kernel(x_ref, edge_ref, w_ref, bias_ref, *out_refs, segments, block):
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    (ts, rows, chunk_lanes), taps = block, w_ref.shape[0]
    first = pl.program_id(1) == 0
    for out_ref, (start, width) in zip(out_refs, segments):
        lanes = _chunk_lanes(width, chunk_lanes)

        def of_lanes(il, _):
            src, dst, w, bias, edge = _of_chunk(
                il, start, lanes, edge_ref, w_ref, bias_ref, first)

            def of_rows(ir, before):
                at = _rows(ir * rows, rows)
                cur = x_ref[0, at, src].astype(f32)
                pre = _pre(w, bias, _shifted(before, cur, taps))
                out_ref[0, at, dst] = (pre * _sigmoid(pre)).astype(
                    out_ref.dtype)
                return cur[rows - _HALO:]

            jax.lax.fori_loop(0, ts // rows, of_rows, edge)

        jax.lax.fori_loop(0, width // lanes, of_lanes, None)


def _bwd_kernel(x_ref, edge_ref, w_ref, bias_ref, *rest, segments, block):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    (ts, rows, chunk_lanes), taps = block, w_ref.shape[0]
    dy_refs, (dx_ref, sums_ref, after_scr) = rest[:len(segments)], \
        rest[len(segments):]
    it = pl.program_id(1)           # the last token block is the grid's first
    first = it == pl.num_programs(1) - 1
    steps = ts // rows

    @pl.when(it == 0)
    def _last_block():
        after_scr[...] = jnp.zeros(after_scr.shape, f32)
        sums_ref[...] = jnp.zeros(sums_ref.shape, f32)

    def fold(t):
        """``[_HALO, L]``: the rows of ``t`` added up a sublane."""
        return sum(t[k:k + _HALO] for k in range(0, rows, _HALO))

    for dy_ref, (start, width) in zip(dy_refs, segments):
        lanes = _chunk_lanes(width, chunk_lanes)

        def of_lanes(il, _):
            src, dst, w, bias, edge = _of_chunk(
                il, start, lanes, edge_ref, w_ref, bias_ref, first)

            def of_rows(k, carried):
                after, sums = carried
                ir = steps - 1 - k
                at = _rows(ir * rows, rows)
                cur = x_ref[0, at, src].astype(f32)
                above = x_ref[0, _rows(jnp.maximum(ir * rows - _EDGE, 0),
                                       _EDGE), src].astype(f32)
                terms = _shifted(
                    jnp.where(ir == 0, edge, above[_EDGE - _HALO:]), cur,
                    taps)
                pre = _pre(w, bias, terms)
                s = _sigmoid(pre)
                dpre = dy_ref[0, at, dst].astype(f32) \
                    * (s * (1.0 + pre * (1.0 - s)))
                ext = jnp.concatenate([dpre, after], axis=0)
                dx = w[taps - 1] * dpre
                for j in range(taps - 1):
                    dx = dx + w[j] * pltpu.roll(
                        ext, rows + _HALO - (taps - 1 - j), 0)[:rows]
                dx_ref[0, at, src] = dx.astype(dx_ref.dtype)
                sums = [acc + fold(dpre * term)
                        for acc, term in zip(sums, terms)] \
                    + [sums[taps] + fold(dpre)]
                return dpre[:_HALO], sums

            nothing = jnp.zeros((_HALO, lanes), f32)
            after, sums = jax.lax.fori_loop(
                0, steps, of_rows, (after_scr[:, src], [nothing] * (taps + 1)))
            after_scr[:, src] = after
            for j, acc in enumerate(sums):
                sums_ref[0, j * _HALO:(j + 1) * _HALO, src] += acc

        jax.lax.fori_loop(0, width // lanes, of_lanes, None)


def _specs(ts, channels, segments, block_of):
    """The block of each array a grid step ``(batch, token step)`` holds;
    ``block_of`` gives the token block of a token step."""
    from jax.experimental import pallas as pl

    per = ts // _EDGE
    tile = lambda width: pl.BlockSpec(
        (1, ts, width), lambda ib, it: (ib, block_of(it), 0))
    return dict(
        x=tile(channels), parts=[tile(width) for _, width in segments],
        edge=pl.BlockSpec(
            (1, _EDGE, channels),
            lambda ib, it: (ib, jnp.maximum(block_of(it) * per - 1, 0), 0)),
        taps=lambda n: pl.BlockSpec((n, channels), lambda ib, it: (0, 0)))


def _params(interpret):
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=("splits", "block", "interpret"))
def _conv_forward(x, w, bias, *, splits, block, interpret):
    """``causal_conv_fwd``: ``silu(conv(x) + bias)`` cut at ``splits``. One
    jitted function: every layer's call of a shape shares its trace."""
    from jax.experimental import pallas as pl

    bsz, seq, channels = x.shape
    taps, ts = w.shape[0], block[0]
    segments = _segments(channels, splits)
    spec = _specs(ts, channels, segments, lambda it: it)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, segments=segments, block=block),
        grid=(bsz, seq // ts),
        in_specs=[spec["x"], spec["edge"], spec["taps"](taps),
                  spec["taps"](1)],
        out_specs=spec["parts"],
        out_shape=[jax.ShapeDtypeStruct((bsz, seq, width), x.dtype)
                   for _, width in segments],
        compiler_params=_params(interpret), interpret=interpret,
        name="causal_conv_fwd",
    )(x, x, w, bias.reshape(1, channels))


@functools.partial(jax.jit, static_argnames=("splits", "block", "interpret"))
def _conv_backward(x, w, bias, dys, *, splits, block, interpret):
    """``causal_conv_bwd``, the token blocks in reverse: the cotangents of
    ``x``, ``w`` and ``bias`` from those of the forward's arrays."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, seq, channels = x.shape
    ts = block[0]
    taps, blocks = w.shape[0], seq // ts
    segments = _segments(channels, splits)
    spec = _specs(ts, channels, segments, lambda it: blocks - 1 - it)
    held = (taps + 1) * _HALO
    dx, sums = pl.pallas_call(
        functools.partial(_bwd_kernel, segments=segments, block=block),
        grid=(bsz, blocks),
        in_specs=[spec["x"], spec["edge"], spec["taps"](taps),
                  spec["taps"](1), *spec["parts"]],
        out_specs=[spec["x"], pl.BlockSpec((1, held, channels),
                                           lambda ib, it: (ib, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz, held, channels), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_HALO, channels), jnp.float32)],
        compiler_params=_params(interpret), interpret=interpret,
        name="causal_conv_bwd",
    )(x, x, w, bias.reshape(1, channels), *dys)
    sums = sums.reshape(bsz, taps + 1, _HALO, channels).sum((0, 2))
    return dx, sums[:taps], sums[taps]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_kernels(x, w, bias, splits, block, interpret):
    return tuple(_conv_forward(x, w, bias, splits=splits, block=block,
                               interpret=interpret))


def _conv_kernels_fwd(x, w, bias, splits, block, interpret):
    return _conv_kernels(x, w, bias, splits, block, interpret), (x, w, bias)


def _conv_kernels_bwd(splits, block, interpret, kept, dys):
    return _conv_backward(*kept, tuple(dys), splits=splits, block=block,
                          interpret=interpret)


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


def causal_conv_silu(x, w, bias, splits: Sequence[int] = (),
                     interpret: bool = False, mesh=None,
                     block: Optional[Tuple[int, int, int]] = None):
    """The kernels' way of ``silu(conv(x) + bias)``: a tuple of ``len(splits)
    + 1`` arrays ``[B, S, width]`` in ``x``'s dtype, the channels cut at
    ``splits``. ``x [B, S, C]``; ``w [taps, C]`` and ``bias [C]`` float32.
    For shapes :func:`conv_path` gives the kernels; ``block`` (token block,
    a chunk's rows and lanes) is for the sweep and the tests. On a ``mesh``
    whose batch axes hold more than one device each device runs its own rows
    of the batch (``parallel/kernels.py``)."""
    from ..parallel.kernels import batch_axes_of, rows_spec, shard_rows

    splits = tuple(int(s) for s in splits)
    ts, rows, chunk_lanes = block or (
        token_block(x.shape[1], x.shape[2], x.dtype.itemsize), _ROWS,
        _CHUNK_LANES)
    block = (ts, min(rows, ts), chunk_lanes)

    def conv(x, w, bias):
        return _conv_kernels(x, w.astype(jnp.float32),
                             bias.astype(jnp.float32), splits, block,
                             interpret)

    axes = batch_axes_of(mesh)
    return shard_rows(
        conv, mesh, "conv",
        (rows_spec(axes, 3), rows_spec((), 2), rows_spec((), 1)),
        (rows_spec(axes, 3),) * (len(splits) + 1))(x, w, bias)
