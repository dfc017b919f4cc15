"""Rotary positions as one Pallas kernel that also moves heads before rows.

``models/transformer.py:apply_rope`` slices a head into its turning halves,
multiplies them apart and joins them; at a 128-lane head every one of those is
a relayout across lanes that XLA does through HBM in float32 (PERF.md, PR 27:
30 ms of the Laguna cell's 281 ms step for 3.4 ms of bytes). Here the turn is
``x * cos_full + swap(x) * sin_signed`` over whole heads, ``swap`` a lane
rotate on the XLU, and the ``[B,S,H,D] -> [B,H,S,D]`` move that the flash
kernels want is the two ``BlockSpec``s' index maps: it costs nothing.

- ``spread_tables``: ``Rope.tables``' ``[S, rot/2]`` cos and sin spread to
  whole heads ``[S, D]``: ``cos, cos, 1...`` and ``-sin, +sin, 0...``, so a
  lane that does not turn is ``x * 1 + r * 0``.
- ``rotate_to_heads``: ``[B,S,H*D] -> [B,H,S,D]``, turned. Its transpose is
  the rotation by the negative angle, so the backward pass is the same kernel
  with ``sin_signed`` subtracted, reading ``[B,H,S,D]`` (what the flash backward
  kernels give) and writing ``[B,S,H*D]`` (what the projections' backward
  matmuls read). No residual is kept. With ``norm`` (a block with
  ``qk_norm``) the same two kernels also norm each head before it turns:
  XLA's norm between the projection and this kernel read and wrote the very
  blocks this kernel reads, a lane reduction a vreg at a time, at a sixth
  of the bandwidth (PERF.md, PR 49); in here it adds no traffic forward, and
  backward reads the projection's output beside the cotangent.
- ``kernel_engages``: decided from what the call can see, as
  ``fused_attention`` does: a head of whole lane tiles, rows that the row
  block divides, and a TPU (or the implementation asked for by name).

Arithmetic is ``apply_rope``'s: bf16 -> float32, multiply-add, -> bf16.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.kernels import batch_axes_of, rows_spec, shard_rows

_LANES = 128
# Rows and heads one grid step holds. Swept on a v5e chip on 2026-09-28
# (tools/rope_sweep.py; the table is in PERF.md, PR 27), bf16 [2,4096,H,128]:
# every block of 2 K rows x heads or more reads 0.40-0.41 ms a call for q of a
# sliding layer (H = 64; 0.33 ms at the chip's bandwidth) and 0.31 for q of a
# full one (H = 48, two rotates and a select: no slower); 256 rows x 1 head is
# 2.5 times that. 512 x 8 is the smallest on the plateau, so the kernel takes
# every S that 512 divides; 2048 x 8 no longer fits the kernel's VMEM.
# With a block's q/k norm inside (PR 49; tools/rope_sweep_pr49.jsonl, bf16
# [1,16384,32|4,128]) 512 x 8 is still the plateau's edge (q and k together
# 0.534 ms forward, 0.779 backward; 1024 x 4 0.529 / 0.777, 256 x 8 0.591 /
# 0.849), and the backward's three blocks no longer fit at 1024 x 8.
_BLOCK_ROWS = 512
_BLOCK_HEADS = 8


def spread_tables(cos: np.ndarray, sin: np.ndarray,
                  head_dim: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(cos_full, sin_signed)``, each float32 ``[S, head_dim]``, from the
    ``[S, rot/2]`` tables of the two-halves layout."""
    rest = head_dim - 2 * cos.shape[1]
    pad = lambda fill: np.full((cos.shape[0], rest), fill, np.float32)
    return (np.concatenate([cos, cos, pad(1.0)], axis=1),
            np.concatenate([-sin, sin, pad(0.0)], axis=1))


def kernel_engages(implementation: str, seq_len: int,
                   head_dim: int) -> Tuple[bool, bool]:
    """``(use_kernel, interpret)`` for ``MultiHeadAttention``'s
    ``attention_impl``: ``auto`` takes the kernel on a TPU, ``pallas`` and
    ``interpret`` wherever they are named, and only at a shape it tiles."""
    fits = head_dim % _LANES == 0 and seq_len % _BLOCK_ROWS == 0
    if implementation == "auto":
        return fits and jax.default_backend() == "tpu", False
    return fits and implementation in ("pallas", "interpret"), \
        implementation == "interpret"


def _swap(x, half: int):
    """Exchange the two turning halves ``[0, half)`` and ``[half, 2 half)`` of
    each row's ``D`` lanes; the lanes past them hold whatever the rotates
    bring (their sine is 0)."""
    from jax.experimental.pallas import tpu as pltpu

    d = x.shape[-1]
    up = pltpu.roll(x, half, 1)          # lane i takes lane i - half
    if 2 * half == d:
        return up
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane < half, pltpu.roll(x, d - half, 1), up)


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, half: int,
                 to_heads: bool):
    cos, sin = cos_ref[...], sin_ref[...]
    d = cos.shape[1]
    for h in range((o_ref if to_heads else x_ref).shape[1]):
        flat = (0, slice(None), slice(h * d, (h + 1) * d))
        src, dst = (flat, (0, h)) if to_heads else ((0, h), flat)
        x = x_ref[src].astype(jnp.float32)
        turn = _swap(x, half) * sin
        # The transpose is the turn by the negative angle.
        o_ref[dst] = (x * cos + turn if to_heads
                      else x * cos - turn).astype(o_ref.dtype)


def _lane_mean(x):
    """The mean over each row's lanes, ``[rows, 1]`` float32."""
    return jnp.sum(x, axis=-1, keepdims=True) / x.shape[-1]


def _norm_rope_kernel(x_ref, scale_ref, cos_ref, sin_ref, o_ref, *,
                      half: int, eps: float):
    """``_rope_kernel`` to heads, each head first normed as ``RMSNorm`` norms
    it: statistics in float32, the result rounded to the tensor's type (the
    rounding point the two have apart) and taken back to registers."""
    cos, sin, scale = cos_ref[...], sin_ref[...], scale_ref[...]
    d = cos.shape[1]
    for h in range(o_ref.shape[1]):
        x = x_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
        r = jax.lax.rsqrt(_lane_mean(x * x) + eps)
        y = (x * r * scale).astype(o_ref.dtype).astype(jnp.float32)
        o_ref[0, h] = (y * cos + _swap(y, half) * sin).astype(o_ref.dtype)


def _norm_rope_bwd_kernel(g_ref, x_ref, scale_ref, cos_ref, sin_ref, dx_ref,
                          dscale_ref, *, half: int, eps: float):
    """The transpose of ``_norm_rope_kernel``: ``g [1,heads,rows,D]`` turned
    back to the norm's cotangent ``dy`` (rounded as the two kernels apart
    round it), the norm's backward from the projection's output ``x``, and
    the block's part of the scale's gradient, ``[8, D]``: its rows summed
    eight apart, which are vector adds; XLA adds the parts."""
    cos, sin, scale = cos_ref[...], sin_ref[...], scale_ref[...]
    d = cos.shape[1]
    dscale = jnp.zeros(dscale_ref.shape[-2:], jnp.float32)
    for h in range(g_ref.shape[1]):
        flat = (0, slice(None), slice(h * d, (h + 1) * d))
        g = g_ref[0, h].astype(jnp.float32)
        dy = (g * cos - _swap(g, half) * sin).astype(dx_ref.dtype) \
            .astype(jnp.float32)
        x = x_ref[flat].astype(jnp.float32)
        r = jax.lax.rsqrt(_lane_mean(x * x) + eps)
        n, dn = x * r, dy * scale
        dx_ref[flat] = (r * (dn - n * _lane_mean(dn * n))) \
            .astype(dx_ref.dtype)
        dscale += (dy * n).reshape(-1, *dscale.shape).sum(axis=0)
    dscale_ref[0, 0, 0] = dscale


def _turn(x, cos_full, sin_signed, half, to_heads, interpret, blocks=None,
          norm=None):
    """The kernel in either direction. ``to_heads``: ``x [B,S,H*D]`` to
    ``[B,H,S,D]``; else ``x [B,H,S,D]`` to ``[B,S,H*D]``. ``blocks`` (rows,
    heads) is for the sweep and the tests. ``norm``: ``(scale [D], eps)`` to
    heads, each head normed before it turns; ``(scale, eps, the forward's
    x)`` back, which gives ``(dx, the scale's gradient by grid step
    [..., 8, D])``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, d = cos_full.shape
    if to_heads:
        b, h = x.shape[0], x.shape[2] // d
    else:
        b, h = x.shape[:2]
    rows, heads = blocks or (_BLOCK_ROWS, _BLOCK_HEADS)
    heads = max(n for n in range(1, min(heads, h) + 1) if h % n == 0)
    grid = (s // rows, b, h // heads)
    # Heads innermost, then the batch: a table block's index follows the row
    # block alone, so it is fetched once for all B * H heads it turns (a
    # float32 table block is four times the bytes of the bf16 rows under it).
    flat = pl.BlockSpec((1, rows, heads * d), lambda ir, ib, ih: (ib, ir, ih))
    by_head = pl.BlockSpec((1, heads, rows, d),
                           lambda ir, ib, ih: (ib, ih, ir, 0))
    table = pl.BlockSpec((rows, d), lambda ir, ib, ih: (ir, 0))
    operands, in_specs = [x], [flat if to_heads else by_head]
    out_specs = by_head if to_heads else flat
    out_shape = jax.ShapeDtypeStruct(
        (b, h, s, d) if to_heads else (b, s, h * d), x.dtype)
    name, cost = "rope_fwd" if to_heads else "rope_bwd", None
    if norm is None:
        kernel = functools.partial(_rope_kernel, half=half, to_heads=to_heads)
    else:
        scale, eps, *residual = norm
        operands += [*residual, scale.reshape(1, d)]
        in_specs += [flat] * len(residual) \
            + [pl.BlockSpec((1, d), lambda ir, ib, ih: (0, 0))]
        kernel = functools.partial(
            _norm_rope_kernel if to_heads else _norm_rope_bwd_kernel,
            half=half, eps=eps)
        name = "norm_" + name
        if not to_heads:
            out_specs = [out_specs, pl.BlockSpec(
                (1, 1, 1, 8, d), lambda ir, ib, ih: (ir, ib, ih, 0, 0))]
            out_shape = [out_shape, jax.ShapeDtypeStruct(
                grid + (8, d), jnp.float32)]
        # To XLA's scheduler a custom call weighs nothing unless it is told.
        # The norm's fusions, whose cost it knew, stood where it hid the copy
        # of K and V into VMEM before the flash forward kernel; with them
        # gone and no estimate here it stopped prefetching (PERF.md, PR 49).
        cost = pl.CostEstimate(
            flops=(12 if to_heads else 24) * x.size,
            transcendentals=b * s * h,
            bytes_accessed=(2 if to_heads else 3) * x.size * x.dtype.itemsize
            + 2 * cos_full.size * 4)
    return pl.pallas_call(
        kernel, grid=grid,
        in_specs=in_specs + [table, table],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ) if not interpret else None,
        interpret=interpret, name=name, cost_estimate=cost,
    )(*operands, cos_full, sin_signed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rotate(x, cos_full, sin_signed, half, interpret, blocks):
    return _turn(x, cos_full, sin_signed, half, True, interpret, blocks)


def _rotate_fwd(x, cos_full, sin_signed, half, interpret, blocks):
    return _turn(x, cos_full, sin_signed, half, True, interpret, blocks), \
        (cos_full, sin_signed)


def _rotate_bwd(half, interpret, blocks, tables, g):
    cos_full, sin_signed = tables
    return _turn(g, cos_full, sin_signed, half, False, interpret,
                 blocks), None, None


_rotate.defvjp(_rotate_fwd, _rotate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _norm_rotate(x, scale, cos_full, sin_signed, half, eps, interpret,
                 blocks):
    return _turn(x, cos_full, sin_signed, half, True, interpret, blocks,
                 (scale, eps))


def _norm_rotate_fwd(x, scale, cos_full, sin_signed, half, eps, interpret,
                     blocks):
    # No residual of the norm's own: the projection's output and the scale.
    return _turn(x, cos_full, sin_signed, half, True, interpret, blocks,
                 (scale, eps)), (x, scale, cos_full, sin_signed)


def _norm_rotate_bwd(half, eps, interpret, blocks, residuals, g):
    x, scale, cos_full, sin_signed = residuals
    dx, parts = _turn(g, cos_full, sin_signed, half, False, interpret,
                      blocks, (scale, eps, x))
    return dx, parts.sum(axis=(0, 1, 2, 3)), None, None


_norm_rotate.defvjp(_norm_rotate_fwd, _norm_rotate_bwd)


def rotate_to_heads(x: jnp.ndarray, cos: np.ndarray, sin: np.ndarray,
                    head_dim: int, interpret: bool = False,
                    blocks=None, mesh=None, norm=None) -> jnp.ndarray:
    """Turn ``x [B, S, H * head_dim]``, a projection's output as it lies, by
    the float32 tables ``cos``, ``sin`` ``[S, rot/2]`` and give it as
    ``[B, H, S, head_dim]``. For a shape ``kernel_engages`` accepts. On a
    ``mesh`` whose batch axes hold more than one device each device turns its
    own rows of the batch (``parallel/kernels.py``); the tables are whole on
    every one. ``norm``: ``(scale, eps)``, a float32 ``[head_dim]`` and a
    number: each head is first normed to ``x / rms(x) * scale`` in the same
    kernel, forward and backward (``models/transformer.py:rms_norm``'s
    arithmetic and rounding point)."""
    cos_full, sin_signed = spread_tables(cos, sin, head_dim)
    half = cos.shape[1]
    axes = batch_axes_of(mesh)
    if norm is None:
        turn, operands, more_specs = lambda x: _rotate(
            x, cos_full, sin_signed, half, interpret, blocks), (x,), ()
    else:
        # The scale is whole on every device; shard_map's transpose adds the
        # devices' gradients of it.
        scale, eps = norm
        turn, operands, more_specs = lambda x, scale: _norm_rotate(
            x, scale, cos_full, sin_signed, half, eps, interpret,
            blocks), (x, scale), (rows_spec((), 1),)
    return shard_rows(turn, mesh, "rope", (rows_spec(axes, 3), *more_specs),
                      rows_spec(axes, 4))(*operands)
