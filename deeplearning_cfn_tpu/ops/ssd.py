"""The selective state-space recurrence of Mamba-2 as the chunked (SSD)
algorithm (Dao & Gu 2024, "Transformers are SSMs", section 6).

A head ``h`` of size ``P`` carries a state ``S [P, N]`` along the sequence:

    S_t = exp(dt_t * a_h) * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t

with ``a_h < 0`` a number a head, ``dt_t > 0`` a step size a token and head,
and ``B_t``, ``C_t`` of size ``N`` shared by the heads of a group. Written
out, ``y_t = sum_{s <= t} exp(sum_{s < r <= t} dt_r a) dt_s (C_t . B_s)
x_s``: a masked, decayed attention. The chunked algorithm cuts the sequence
into chunks of ``chunk`` tokens and computes

1. inside a chunk, that sum as two products: the decay matrix ``L[i, j] =
   exp(cum_i - cum_j)`` (``i >= j``; ``cum`` the running sum of ``dt * a``
   from the chunk's start) times ``C B^T``, times ``dt * x``;
2. a chunk's closing state from ``B^T (decay to the chunk's end * dt * x)``;
3. the states handed from chunk to chunk, each decayed by its chunk's whole
   ``exp(cum_last)``: the one sequential part, ``S / chunk`` steps;
4. ``C`` times the state a chunk entered with, decayed to each position.

The running sums, the exponentials and the carried state are float32; the
four products take operands of ``x``'s dtype (bfloat16 in training) and
accumulate in float32.

**Two carriers of the one algorithm** (:func:`scan_path` says which, from
what the call can see):

- ``kernel``: two Pallas kernels, ``ssd_fwd`` and ``ssd_bwd`` under a
  ``jax.custom_vjp``, on a TPU where the shapes tile (whole chunks, a chunk
  of whole 128-square sub-tiles, ``N`` whole lane tiles, a head of whole
  lane tiles or a whole number of a group's heads to one). The grid runs
  over (batch, chunk, head block), the chunks in order (in reverse in
  ``ssd_bwd``); a step reads its ``(chunk, heads * P)`` block of ``x``
  straight from ``[B, S, H * P]`` and holds in VMEM ``C B^T`` (once a chunk
  and group), a sub-tile of ``L`` at a time, its product with ``C B^T`` and
  every head's carried state ``[N, H * P]``. Of a chunk's sub-tiles those
  above the diagonal are all mask and are skipped; those on it are formed a
  head (subtract, mask, exp, times ``C B^T``, cast: what bounds the kernels,
  on the vector unit); those left of it never are: there ``exp(cum_i -
  cum_j)`` is ``exp(cum_i - cum_before) exp(cum_before - cum_j)`` for the
  position before the sub-tile's row, both factors at most 1 because ``cum``
  falls, so the sub-tile is ``C B^T`` between two scalings of its operands
  and one product serves the heads that share a lane tile. No ``[.., chunk,
  chunk]`` array reaches HBM in either pass; what the forward keeps for the
  backward is the state each chunk entered with (``[B, S / chunk, N, H * P]``
  float32, 67 MB a layer of ``granite4_h_micro_train_8k``). Outside the
  kernels stay the running sum and the exponentials a token and head
  (``[B, S, H]`` arrays, handed over with the tokens along the lanes:
  :func:`_layouts`), whose derivatives autodiff takes. The backward forms no
  ``y``: the running sum's cotangent is the row sums less the column sums of
  ``dM * M`` (:func:`_ssd_bwd_kernel`).
- ``xla``: the same four steps as ``jnp`` einsums and one ``lax.scan`` over
  the chunks, autodiff's backward: off the TPU, for a sequence shorter than
  a chunk and for shapes the kernels do not tile. Its decay matrices are
  ``[B, H, S / chunk, chunk, chunk]`` float32 in HBM.

``tools/ssd_block_sweep.py`` times the kernels by head block.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_LANES = 128
# Masked entries of a decay sub-tile: exp of it is 0 in float32.
_MASKED = -1e30
# Heads a grid step holds; the loop over their lane tiles is unrolled (static
# slices: a rolled loop, or one that runs four tiles a trip, is 20-30 %
# slower). Swept on a v5e chip (tools/ssd_block_sweep.py and its .jsonl;
# PERF.md, PR 42) at granite-4.0-h's shape, bfloat16 [1, 8192, 64, 64] with a
# state of 128 in chunks of 256: the forward kernel takes 0.56 / 0.53 / 0.51
# / 0.50 ms and a recomputed block's two 1.60 / 1.58 / 1.49 / 1.49 at 8 / 16
# / 32 / 64 heads (what is once a chunk and group is done less often), but
# the unrolled text is loaded with the step's executable, 27 kernels of it
# in granite4_h_micro_train_8k: a process's first step takes 17 s from the
# compile cache at 8 heads and 28 at 32 (21 with the einsums).
_BLOCK_HEADS = 8
# The side of a sub-tile of a chunk's triangle: the XLU's transposes, which
# turn a row of a token's numbers into a column of them, work on squares of
# 128.
_TILE = 128
_VMEM_LIMIT = 64 * 2 ** 20


def _unit(p: int) -> int:
    """Heads that share a tile of 128 lanes (1 where a head is whole
    tiles)."""
    return _LANES // p if p < _LANES else 1


def _tiles(p: int, n: int, q: int, seq: int, heads: int, groups: int) -> bool:
    """Whether the kernels take the shape: whole chunks of whole sub-tiles,
    ``N`` whole lane tiles, heads that fill lane tiles within a group."""
    if seq % q or q % _TILE or n % _LANES or heads % groups:
        return False
    if p % _LANES and _LANES % p:
        return False
    return (heads // groups) % _unit(p) == 0


def scan_path(implementation: str, x_shape, state: int, groups: int,
              chunk: int) -> Tuple[str, bool]:
    """``(path, interpret)`` for :func:`ssd_scan` at ``x_shape = (B, S, H,
    P)``: ``"kernel"`` where the shapes tile and ``implementation`` is
    ``auto`` on a TPU, ``pallas`` or ``interpret`` (the kernels in
    interpreter mode: the tests' way in); else ``"xla"``."""
    if implementation not in ("auto", "pallas", "interpret", "reference"):
        raise ValueError(f"unknown implementation {implementation!r}")
    _, seq, heads, p = x_shape
    fits = seq >= chunk and _tiles(p, state, chunk, seq, heads, groups)
    if implementation == "auto":
        wanted = jax.default_backend() == "tpu"
    else:
        wanted = implementation in ("pallas", "interpret")
    return ("kernel" if fits and wanted else "xla",
            implementation == "interpret")


def head_block(heads: int, groups: int, p: int,
               wanted: Optional[int] = None) -> int:
    """Heads a grid step holds: the largest divisor of a group's heads up to
    ``wanted`` (``_BLOCK_HEADS``) that fills whole lane tiles."""
    per_group, unit = heads // groups, _unit(p)
    wanted = wanted or _BLOCK_HEADS
    return max(hb for hb in range(unit, min(max(wanted, unit), per_group) + 1,
                                  unit) if per_group % hb == 0)


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _quantities(tiles: int) -> int:
    """Rows a head has in :func:`_layouts`' array."""
    return 4 if tiles == 1 else 4 + tiles


def _row(rows_ref, which, un, j, unit, span):
    """``[1, T]``: quantity ``which`` of head ``j`` of the block's unit
    ``un`` at the tokens ``span``, along the lanes."""
    at = which * unit + j
    return rows_ref[0, un, at:at + 1, span]


def _down(row, width):
    """``[T, width]`` from ``[1, T]``: the row's numbers down the rows, the
    same in every lane. A broadcast along the sublanes and a transpose of a
    128-square on the XLU (a column broadcast along the lanes is an XLU
    operation every eight rows)."""
    square = jnp.broadcast_to(row, (min(width, _LANES), row.shape[1])).T
    if width <= _LANES:
        return square
    return jnp.concatenate([square] * (width // _LANES), axis=1)


def _spread(rows_ref, which, un, unit, p, span):
    """``[T, unit * p]``: quantity ``which`` at the tokens ``span`` down the
    rows, each head of unit ``un`` (those that share a lane tile) over its
    own ``p`` lanes."""
    if unit == 1:
        return _down(_row(rows_ref, which, un, 0, 1, span), p)
    # Across first (a head's row over its p rows of the square), then turned.
    row = lambda j: jnp.broadcast_to(
        _row(rows_ref, which, un, j, unit, span),
        (unit * p, span.stop - span.start))
    out = row(unit - 1)
    at = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
    for j in reversed(range(unit - 1)):
        out = jnp.where(at < (j + 1) * p, row(j), out)
    return out.T


def _own_lanes(t, j, unit, p):
    """``t`` with the lanes of the unit's other heads zeroed: a product with
    it lands in, or sums over, head ``j``'s lanes alone."""
    if unit == 1:
        return t
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    return jnp.where((lane >= j * p) & (lane < (j + 1) * p), t,
                     jnp.zeros_like(t))


def _head_sums(term, unit, p):
    """``[1, T]`` a head of the unit: its sum of ``term [T, unit * p]`` over
    its lanes, the tokens along the lanes: transposes of 128-squares and
    sums down the rows."""
    across = [term[:, k:k + _LANES].T
              for k in range(0, term.shape[1], _LANES)]
    if unit == 1:
        return [sum(jnp.sum(a, axis=0, keepdims=True) for a in across)]
    return [jnp.sum(across[0][j * p:(j + 1) * p], axis=0, keepdims=True)
            for j in range(unit)]


def _decay_tile(rows_ref, un, j, unit, span):
    """``L[i, k] = exp(cum_i - cum_k)``, ``i >= k``, of head ``j`` of unit
    ``un`` on the diagonal sub-tile ``span``."""
    cum = _row(rows_ref, 1, un, j, unit, span)
    diff = _down(cum, cum.shape[1]) - cum
    i = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 0)
    k = jax.lax.broadcasted_iota(jnp.int32, diff.shape, 1)
    return jnp.exp(jnp.where(i >= k, diff, _MASKED))


def _sub_tiles(q: int):
    """The token spans of a chunk's sub-tiles."""
    return [slice(t * _TILE, (t + 1) * _TILE) for t in range(q // _TILE)]


def _unit_lanes(un, lanes):
    from jax.experimental import pallas as pl

    return pl.ds(pl.multiple_of(un * lanes, lanes), lanes)


def _ssd_fwd_kernel(x_ref, rows_ref, whole_ref, b_ref, c_ref, y_ref, *rest,
                    hb, p, per_group, save):
    from jax.experimental import pallas as pl

    if save:
        entering_ref, *rest = rest
    state_scr, cb_scr, cbb_scr, cs_scr, z_scr = rest
    ic, ih = pl.program_id(1), pl.program_id(2)
    dtype = x_ref.dtype
    unit = _unit(p)
    lanes = unit * p
    spans = _sub_tiles(x_ref.shape[1])
    bc, cc = b_ref[0], c_ref[0]

    @pl.when(ih % per_group == 0)
    def _group():
        cb_scr[...] = _dot(cc, bc, _NT)
        cbb_scr[...] = cb_scr[...].astype(dtype)

    @pl.when(ic == 0)
    def _first():
        state_scr[ih] = jnp.zeros(state_scr.shape[1:], jnp.float32)

    state = state_scr[ih]                                 # [N, hb * p]
    if save:
        entering_ref[0, 0] = state
    cs_scr[...] = _dot(cc, state.astype(dtype), _NN)

    def of_unit(un, _):
        sl = _unit_lanes(un, lanes)
        spread = lambda which, span: _spread(rows_ref, which, un, unit, p,
                                             span)
        xdt = [x_ref[0, rows, sl].astype(jnp.float32) * spread(0, rows)
               for rows in spans]
        for it, rows in enumerate(spans):
            acc = spread(2, rows) * cs_scr[rows, sl]
            if it:
                # Left of the diagonal sub-tile the decay is two scalings
                # round C B^T (see _layouts): one product for the unit.
                acc = acc + spread(4, rows) * sum(
                    _dot(cbb_scr[rows, cols],
                         (xdt[jt] * spread(4 + it, cols)).astype(dtype), _NN)
                    for jt, cols in enumerate(spans[:it]))
            own = xdt[it].astype(dtype)
            for j in range(unit):
                decay = _decay_tile(rows_ref, un, j, unit, rows)
                acc = acc + _dot((cb_scr[rows, rows] * decay).astype(dtype),
                                 _own_lanes(own, j, unit, p), _NN)
            y_ref[0, rows, sl] = acc.astype(y_ref.dtype)
            z_scr[rows, sl] = (xdt[it] * spread(3, rows)).astype(dtype)

    jax.lax.fori_loop(0, hb // unit, of_unit, None, unroll=True)
    state_scr[ih] = state * whole_ref[0, 0] + _dot(bc, z_scr[...], _TN)


def _ssd_bwd_kernel(x_ref, dy_ref, rows_ref, whole_ref, b_ref, c_ref,
                    entering_ref, dx_ref, sums_ref, edge_ref, db_ref, dc_ref,
                    g_scr, cb_scr, cbb_scr, dcb_scr, db_scr, dc_scr, cs_scr,
                    bg_scr, dye_scr, z_scr, dyu_scr, xv_scr, *, hb, p,
                    per_group):
    from jax.experimental import pallas as pl

    ic, ih = pl.program_id(1), pl.program_id(2)
    dtype = x_ref.dtype
    unit = _unit(p)
    lanes = unit * p
    spans = _sub_tiles(x_ref.shape[1])
    bc, cc = b_ref[0], c_ref[0]
    f32 = lambda v: v.astype(jnp.float32)

    @pl.when(ih % per_group == 0)
    def _group():
        cb_scr[...] = _dot(cc, bc, _NT)
        cbb_scr[...] = cb_scr[...].astype(dtype)
        dcb_scr[...] = jnp.zeros(dcb_scr.shape, jnp.float32)
        db_scr[...] = jnp.zeros(db_scr.shape, jnp.float32)
        dc_scr[...] = jnp.zeros(dc_scr.shape, jnp.float32)

    @pl.when(ic == 0)                       # the last chunk: the grid's first
    def _first():
        g_scr[ih] = jnp.zeros(g_scr.shape[1:], jnp.float32)

    entering = entering_ref[0, 0]                         # [N, hb * p]
    leaving_ct = g_scr[ih]          # the cotangent of the state handed on
    cs_scr[...] = _dot(cc, entering.astype(dtype), _NN)
    bg_scr[...] = _dot(bc, leaving_ct.astype(dtype), _NN)

    def of_unit(un, _):
        sl = _unit_lanes(un, lanes)
        spread = lambda which, span: _spread(rows_ref, which, un, unit, p,
                                             span)
        x = [f32(x_ref[0, rows, sl]) for rows in spans]
        dy = [dy_ref[0, rows, sl] for rows in spans]
        step = [spread(0, rows) for rows in spans]
        xdt = [x[t] * step[t] for t in range(len(spans))]
        xdt_b = [v.astype(dtype) for v in xdt]
        # ``cum``'s cotangent is sum_k W[i, k] - sum_k W[k, i] over W = dM
        # * M, every product the scan makes of ``dt x`` and its cotangent.
        # On the diagonal sub-tiles W is formed (float32 ``C B^T * L``, as
        # autodiff of the einsums has it). For the rest the row sums
        # ("given") are sum_p of ``dy`` scaled times what it multiplies
        # (``C`` by the state; ``C B^T`` by the rows left of the diagonal)
        # and the column sums ("taken") sum_p of ``dt x`` scaled times its
        # cotangent; at the chunk's last position all that the closing
        # state takes is given back, from the same array, so that the two
        # cancel as the products they stand for do.
        dxdt, given, taken = [], [], []
        edge = None
        for t, rows in enumerate(spans):
            to_end = spread(3, rows)
            dxdt.append(to_end * bg_scr[rows, sl])
            taken.append(xdt[t] * dxdt[t])
            part = jnp.sum(taken[t], axis=0, keepdims=True)
            edge = part if edge is None else edge + part
            z_scr[rows, sl] = (xdt[t] * to_end).astype(dtype)
            grown = f32(dy[t]) * spread(2, rows)
            given.append(grown * cs_scr[rows, sl])
            dye_scr[rows, sl] = grown.astype(dtype)
        edge_ref[0, 0, :, sl] = edge
        for it, rows in enumerate(spans[1:], 1):
            # Left of the diagonal sub-tile: C B^T between two scalings.
            dyu = (f32(dy[it]) * spread(4, rows)).astype(dtype)
            dyu_scr[rows, sl] = dyu
            below = None
            for jt, cols in enumerate(spans[:it]):
                before = spread(4 + it, cols)
                xv = (xdt[jt] * before).astype(dtype)
                xv_scr[it - 1, cols, sl] = xv
                part = _dot(cbb_scr[rows, cols], xv, _NN)
                below = part if below is None else below + part
                dxv = _dot(cbb_scr[rows, cols], dyu, _TN)
                dxdt[jt] = dxdt[jt] + before * dxv
                taken[jt] = taken[jt] + f32(xv) * dxv
            given[it] = given[it] + f32(dyu) * below
        for t, rows in enumerate(spans):
            w_sums = []
            for j in range(unit):
                dy_own = _own_lanes(dy[t], j, unit, p)
                decay = _decay_tile(rows_ref, un, j, unit, rows)
                m = cb_scr[rows, rows] * decay
                dxdt[t] = dxdt[t] + _dot(m.astype(dtype), dy_own, _TN)
                dm = _dot(dy_own, xdt_b[t], _NT)
                dcb_scr[rows, rows] += decay * dm
                w = m * dm
                w_sums.append(jnp.sum(w.T, axis=0, keepdims=True)
                              - jnp.sum(w, axis=0, keepdims=True))
            dx_ref[0, rows, sl] = (dxdt[t] * step[t]).astype(dx_ref.dtype)
            # A head's sums over its lanes, the tokens along the lanes:
            # ``cum``'s cotangent but for the chunk's last position's term,
            # and sum_p x d(dt x), the step size's.
            dcum = _head_sums(given[t] - taken[t], unit, p)
            ddt = _head_sums(x[t] * dxdt[t], unit, p)
            for j in range(unit):
                sums_ref[0, un, j:j + 1, rows] = dcum[j] + w_sums[j]
                sums_ref[0, un, unit + j:unit + j + 1, rows] = ddt[j]

    jax.lax.fori_loop(0, hb // unit, of_unit, None, unroll=True)
    # Over the whole head block: C B^T's cotangent left of the diagonal, the
    # states' parts of dB and dC, the cotangent of the entering state.
    for it, rows in enumerate(spans[1:], 1):
        for cols in spans[:it]:
            dcb_scr[rows, cols] += _dot(dyu_scr[rows, :],
                                        xv_scr[it - 1, cols, :], _NT)
    dc_scr[...] += _dot(dye_scr[...], entering.astype(dtype), _NT)
    db_scr[...] += _dot(z_scr[...], leaving_ct.astype(dtype), _NT)
    carried = leaving_ct * whole_ref[0, 0]
    edge_ref[0, 0] += jnp.sum(carried * entering, axis=0, keepdims=True)
    g_scr[ih] = carried + _dot(cc, dye_scr[...], _TN)

    @pl.when(ih % per_group == per_group - 1)
    def _group_done():
        dcb = dcb_scr[...].astype(dtype)
        dc_ref[0] = (dc_scr[...] + _dot(dcb, bc, _NN)).astype(dc_ref.dtype)
        db_ref[0] = (db_scr[...] + _dot(dcb, cc, _TN)).astype(db_ref.dtype)


def _layouts(dt, cum, q: int, p: int):
    """What the kernels read of a token and head, from ``dt`` and the
    running sum ``cum`` (``[B, S, H]`` float32): ``rows [B, H / unit,
    quantities * unit, S]``, the tokens along the lanes and the ``unit``
    heads that share a lane tile together, and a chunk's whole decay over
    each head's lanes ``[B, S / q, 1, H * p]``. The quantities: ``dt``,
    ``cum``, ``exp(cum)``, the decay to the chunk's end ``exp(cum_last -
    cum)`` and, where a chunk is more than one sub-tile, the decay since the
    position before the token's sub-tile ``exp(cum - cum_before)`` and, for
    each sub-tile ``t`` after the first, the decay up to it
    ``exp(cum_before(t) - cum)`` of the tokens left of it. Their product is
    ``exp(cum_i - cum_j)`` for ``i`` in sub-tile ``t`` and ``j`` before it,
    and neither factor passes 1 because ``cum`` falls (``dt > 0``, ``a <
    0``)."""
    bsz, seq, heads = dt.shape
    by_chunk = cum.reshape(bsz, seq // q, q, heads)
    last = by_chunk[:, :, -1:]
    rows = [dt, cum, jnp.exp(cum), jnp.exp(last - by_chunk)]
    tiles = q // _TILE
    if tiles > 1:
        by_tile = by_chunk.reshape(bsz, seq // q, tiles, _TILE, heads)
        before = jnp.concatenate([jnp.zeros_like(by_chunk[:, :, :1]),
                                  by_tile[:, :, :-1, -1]], axis=2)
        rows.append(jnp.exp(by_tile - before[:, :, :, None]))
        at = jnp.arange(q)[:, None]
        rows += [jnp.where(at < t * _TILE,
                           jnp.exp(jnp.minimum(before[:, :, t:t + 1]
                                               - by_chunk, 0.0)), 0.0)
                 for t in range(1, tiles)]
    unit = _unit(p)
    rows = jnp.stack([r.reshape(cum.shape) for r in rows], axis=1)
    rows = rows.reshape(bsz, -1, seq, heads // unit, unit) \
        .transpose(0, 3, 1, 4, 2).reshape(bsz, heads // unit, -1, seq)
    return rows, jnp.repeat(jnp.exp(last), p, axis=-1)


def _specs(q, n, hb, p, per_group, chunk_of):
    """The block of each array a grid step ``(batch, chunk step, head
    block)`` holds; ``chunk_of`` gives the chunk of a chunk step."""
    from jax.experimental import pallas as pl

    width, units, unit = hb * p, hb // _unit(p), _unit(p)
    at = lambda f: (lambda ib, ic, ih: f(ib, chunk_of(ic), ih))
    rows = lambda k: pl.BlockSpec((1, units, k * unit, q),
                                  at(lambda b, c, h: (b, h, 0, c)))
    return dict(
        tokens=pl.BlockSpec((1, q, width), at(lambda b, c, h: (b, c, h))),
        rows=rows(_quantities(q // _TILE)), sums=rows(2),
        lanes=pl.BlockSpec((1, 1, 1, width),
                           at(lambda b, c, h: (b, c, 0, h))),
        group=pl.BlockSpec((1, q, n),
                           at(lambda b, c, h: (b, c, h // per_group))),
        state=pl.BlockSpec((1, 1, n, width),
                           at(lambda b, c, h: (b, c, 0, h))))


def _params(interpret):
    from jax.experimental.pallas import tpu as pltpu

    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "q", "hb", "interpret", "save"))
def _forward(x2, dt, cum, b2, c2, *, heads, groups, q, hb, interpret, save):
    """``ssd_fwd``: ``y [B, S, H * P]`` and, where ``save``, the state each
    chunk entered with ``[B, S / q, N, H * P]``. One jitted function: every
    layer's call of a shape shares its trace."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, seq, inner = x2.shape
    p, n = inner // heads, b2.shape[2] // groups
    chunks, per_group = seq // q, heads // groups // hb
    spec = _specs(q, n, hb, p, per_group, lambda ic: ic)
    out_specs, out_shape = [spec["tokens"]], [
        jax.ShapeDtypeStruct(x2.shape, x2.dtype)]
    if save:
        out_specs.append(spec["state"])
        out_shape.append(jax.ShapeDtypeStruct((bsz, chunks, n, inner),
                                              jnp.float32))
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, hb=hb, p=p, per_group=per_group,
                          save=save),
        grid=(bsz, chunks, heads // hb),
        in_specs=[spec["tokens"], spec["rows"], spec["lanes"], spec["group"],
                  spec["group"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((heads // hb, n, hb * p), jnp.float32),  # the states
            pltpu.VMEM((q, q), jnp.float32),                    # C B^T
            pltpu.VMEM((q, q), x2.dtype),               # and as an operand
            pltpu.VMEM((q, hb * p), jnp.float32),       # C entering state
            pltpu.VMEM((q, hb * p), x2.dtype),          # dt x to the end
        ],
        compiler_params=_params(interpret), interpret=interpret,
        name="ssd_fwd",
    )(x2, *_layouts(dt, cum, q, p), b2, c2)


@functools.partial(jax.jit, static_argnames=(
    "heads", "groups", "q", "hb", "interpret"))
def _backward(x2, dt, cum, b2, c2, entering, dy2, *, heads, groups, q, hb,
              interpret):
    """``ssd_bwd``, the chunks in reverse: the cotangents of ``x2``, ``dt``
    (through ``dt x`` alone), ``cum``, ``b2`` and ``c2``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, seq, inner = x2.shape
    p, n, unit = inner // heads, b2.shape[2] // groups, _unit(inner // heads)
    chunks, per_group, blocks = seq // q, heads // groups // hb, heads // hb
    spec = _specs(q, n, hb, p, per_group, lambda ic: chunks - 1 - ic)
    f32 = jnp.float32
    dx2, sums, edge, db2, dc2 = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, hb=hb, p=p, per_group=per_group),
        grid=(bsz, chunks, blocks),
        in_specs=[spec["tokens"], spec["tokens"], spec["rows"],
                  spec["lanes"], spec["group"], spec["group"],
                  spec["state"]],
        out_specs=[spec["tokens"], spec["sums"], spec["lanes"],
                   spec["group"], spec["group"]],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x2.dtype),
                   jax.ShapeDtypeStruct((bsz, heads // unit, 2 * unit, seq),
                                        f32),
                   jax.ShapeDtypeStruct((bsz, chunks, 1, inner), f32),
                   jax.ShapeDtypeStruct(b2.shape, b2.dtype),
                   jax.ShapeDtypeStruct(c2.shape, c2.dtype)],
        scratch_shapes=[
            pltpu.VMEM((blocks, n, hb * p), f32),     # the states' cotangents
            pltpu.VMEM((q, q), f32),                  # C B^T
            pltpu.VMEM((q, q), x2.dtype),             # and as an operand
            pltpu.VMEM((q, q), f32),                  # its cotangent
            pltpu.VMEM((q, n), f32),                  # dB but for C B^T's
            pltpu.VMEM((q, n), f32),                  # dC but for C B^T's
            pltpu.VMEM((q, hb * p), f32),             # C entering state
            pltpu.VMEM((q, hb * p), f32),             # B leaving cotangent
            pltpu.VMEM((q, hb * p), x2.dtype),        # exp(cum) dy
            pltpu.VMEM((q, hb * p), x2.dtype),        # dt x to the end
            pltpu.VMEM((q, hb * p), x2.dtype),        # dy since its sub-tile
            pltpu.VMEM((max(q // _TILE - 1, 1), q, hb * p), x2.dtype),
        ],                              # dt x up to each later sub-tile
        compiler_params=_params(interpret), interpret=interpret,
        name="ssd_bwd",
    )(x2, dy2, *_layouts(dt, cum, q, p), b2, c2, entering)
    sums = sums.reshape(bsz, heads // unit, 2, unit, seq) \
        .transpose(0, 4, 2, 1, 3).reshape(bsz, seq, 2, heads)
    # What the closing state takes of cum_last, at the chunk's last position.
    edge = edge.reshape(bsz, chunks, heads, p).sum(-1)
    dcum = sums[:, :, 0].reshape(bsz, chunks, q, heads) + jnp.where(
        (jnp.arange(q) == q - 1)[:, None], edge[:, :, None], 0.0)
    return dx2, sums[:, :, 1], dcum.reshape(bsz, seq, heads), db2, dc2


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _scan_kernels(x2, dt, cum, b2, c2, heads, groups, q, hb, interpret):
    return _forward(x2, dt, cum, b2, c2, heads=heads, groups=groups, q=q,
                    hb=hb, interpret=interpret, save=False)[0]


def _scan_kernels_fwd(x2, dt, cum, b2, c2, heads, groups, q, hb, interpret):
    y2, entering = _forward(x2, dt, cum, b2, c2, heads=heads, groups=groups,
                            q=q, hb=hb, interpret=interpret, save=True)
    return y2, (x2, dt, cum, b2, c2, entering)


def _scan_kernels_bwd(heads, groups, q, hb, interpret, kept, dy2):
    return _backward(*kept, dy2, heads=heads, groups=groups, q=q, hb=hb,
                     interpret=interpret)


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def ssd_scan(x, dt, a, b, c, chunk: int, implementation: str = "auto",
             block_heads: Optional[int] = None, mesh=None):
    """``y [B, S, H, P]`` of the recurrence above, in ``x``'s dtype.

    ``x [B, S, H, P]``; ``dt [B, S, H]`` float32, positive; ``a [H]``
    float32, negative; ``b``, ``c`` ``[B, S, G, N]`` with ``H`` a multiple of
    ``G`` (head ``h`` reads group ``h // (H / G)``). ``S`` is a multiple of
    ``chunk`` or shorter than it (then it is one chunk). ``implementation``
    and the shapes choose the carrier (:func:`scan_path`); ``block_heads``
    (heads a grid step) is for the sweep and the tests. On a
    ``mesh`` whose batch axes hold more than one device each device scans
    its own rows of the batch (``parallel/kernels.py``)."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads cannot share {groups} groups")
    q = min(chunk, seq)
    if seq % q:
        raise ValueError(f"{seq} positions are not whole chunks of {q}")
    path, interpret = scan_path(implementation, x.shape, n, groups, chunk)
    if path == "xla":
        return _scan_einsums(x, dt, a, b, c, q)
    from ..parallel.kernels import batch_axes_of, rows_spec, shard_rows

    hb = block_heads or head_block(heads, groups, p)

    def scan(x, dt, a, b, c):
        rows = x.shape[0]
        dt = dt.astype(jnp.float32)
        cum = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(
            rows, seq // q, q, heads), axis=2).reshape(dt.shape)
        return _scan_kernels(
            x.reshape(rows, seq, heads * p), dt, cum,
            b.reshape(rows, seq, groups * n), c.reshape(rows, seq, groups * n),
            heads, groups, q, hb, interpret).reshape(x.shape)

    axes = batch_axes_of(mesh)
    return shard_rows(
        scan, mesh, "ssd",
        (rows_spec(axes, 4), rows_spec(axes, 3), rows_spec((), 1),
         rows_spec(axes, 4), rows_spec(axes, 4)), rows_spec(axes, 4),
    )(x, dt, a, b, c)


def _scan_einsums(x, dt, a, b, c, q: int):
    """The ``xla`` carrier: chunks of ``q`` positions."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    r, chunks = heads // groups, seq // q
    # Heads of a group side by side, chunks apart: [B, G, R, C, Q, ...].
    by_head = lambda t: t.reshape(bsz, chunks, q, groups, r, *t.shape[3:]) \
        .transpose(0, 3, 4, 1, 2, *range(5, t.ndim + 2))
    by_group = lambda t: t.reshape(bsz, chunks, q, groups, n) \
        .transpose(0, 3, 1, 2, 4)                       # [B, G, C, Q, N]
    # The barrier keeps the transposition where it is written, on ``x`` as it
    # arrives: XLA otherwise widens ``x`` to float32 first and then moves
    # twice the bytes, in a copy of its own that no scope names.
    xh = jax.lax.optimization_barrier(by_head(x))
    dth = by_head(dt.astype(jnp.float32))
    da = dth * a.astype(jnp.float32).reshape(groups, r)[None, :, :, None,
                                                          None]
    bg, cg = by_group(b), by_group(c)
    # Step 1's C B^T is a group's, whatever the head.
    cb = jnp.einsum("bgcin,bgcjn->bgcij", cg, bg,
                    preferred_element_type=jnp.float32)

    cum = jnp.cumsum(da, axis=-1)                        # [B, G, R, C, Q]
    xdt = xh.astype(jnp.float32) * dth[..., None]
    lower = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    y = jnp.einsum("bgrcij,bgrcjp->bgrcip",
                   (cb[:, :, None] * decay).astype(x.dtype),
                   xdt.astype(x.dtype), preferred_element_type=jnp.float32)
    if chunks > 1:
        to_end = jnp.exp(cum[..., -1:] - cum)
        closing = jnp.einsum("bgcjn,bgrcjp->bgrcpn", bg,
                             (xdt * to_end[..., None]).astype(x.dtype),
                             preferred_element_type=jnp.float32)
        whole = jnp.exp(cum[..., -1])                    # [B, G, R, C]

        def hand_on(state, chunk_):
            decay_c, closing_c = chunk_
            return state * decay_c[..., None, None] + closing_c, state

        _, entering = jax.lax.scan(
            hand_on, jnp.zeros_like(closing[:, :, :, 0]),
            (jnp.moveaxis(whole, 3, 0), jnp.moveaxis(closing, 3, 0)))
        entering = jnp.moveaxis(entering, 0, 3)          # [B, G, R, C, P, N]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bgcin,bgrcpn->bgrcip", cg, entering.astype(x.dtype),
            preferred_element_type=jnp.float32)
    # [B, G, R, C, Q, P] -> [B, S, H, P]
    return y.astype(x.dtype).transpose(0, 3, 4, 1, 2, 5).reshape(
        bsz, seq, heads, p)


def ssd_recurrence(x, dt, a, b, c):
    """The recurrence itself, a token at a time under ``lax.scan``, in
    float32: what :func:`ssd_scan` is tested against. Same arguments and
    result (float32), no chunks."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    of_head = lambda t: jnp.repeat(t.astype(jnp.float32), heads // groups,
                                   axis=2)              # [B, S, H, N]
    f32 = lambda t: t.astype(jnp.float32)

    def token(state, t):
        x_t, dt_t, b_t, c_t = t
        state = state * jnp.exp(dt_t * f32(a))[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    along = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(token, jnp.zeros((bsz, heads, p, n), jnp.float32),
                        (along(f32(x)), along(f32(dt)), along(of_head(b)),
                         along(of_head(c))))
    return jnp.moveaxis(y, 0, 1)
