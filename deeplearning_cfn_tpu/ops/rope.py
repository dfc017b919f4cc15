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
  matmuls read). No residual is kept.
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


def _turn(x, cos_full, sin_signed, half, to_heads, interpret, blocks=None):
    """The kernel in either direction. ``to_heads``: ``x [B,S,H*D]`` to
    ``[B,H,S,D]``; else ``x [B,H,S,D]`` to ``[B,S,H*D]``. ``blocks`` (rows,
    heads) is for the sweep and the tests."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, d = cos_full.shape
    if to_heads:
        b, h = x.shape[0], x.shape[2] // d
    else:
        b, h = x.shape[:2]
    rows, heads = blocks or (_BLOCK_ROWS, _BLOCK_HEADS)
    heads = max(n for n in range(1, min(heads, h) + 1) if h % n == 0)
    # Heads innermost, then the batch: a table block's index follows the row
    # block alone, so it is fetched once for all B * H heads it turns (a
    # float32 table block is four times the bytes of the bf16 rows under it).
    flat = pl.BlockSpec((1, rows, heads * d), lambda ir, ib, ih: (ib, ir, ih))
    by_head = pl.BlockSpec((1, heads, rows, d),
                           lambda ir, ib, ih: (ib, ih, ir, 0))
    table = pl.BlockSpec((rows, d), lambda ir, ib, ih: (ir, 0))
    return pl.pallas_call(
        functools.partial(_rope_kernel, half=half, to_heads=to_heads),
        grid=(s // rows, b, h // heads),
        in_specs=[flat if to_heads else by_head, table, table],
        out_specs=by_head if to_heads else flat,
        out_shape=jax.ShapeDtypeStruct(
            (b, h, s, d) if to_heads else (b, s, h * d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
        ) if not interpret else None,
        interpret=interpret,
        name="rope_fwd" if to_heads else "rope_bwd",
    )(x, cos_full, sin_signed)


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


def rotate_to_heads(x: jnp.ndarray, cos: np.ndarray, sin: np.ndarray,
                    head_dim: int, interpret: bool = False,
                    blocks=None, mesh=None) -> jnp.ndarray:
    """Turn ``x [B, S, H * head_dim]``, a projection's output as it lies, by
    the float32 tables ``cos``, ``sin`` ``[S, rot/2]`` and give it as
    ``[B, H, S, head_dim]``. For a shape ``kernel_engages`` accepts. On a
    ``mesh`` whose batch axes hold more than one device each device turns its
    own rows of the batch (``parallel/kernels.py``); the tables are whole on
    every one."""
    cos_full, sin_signed = spread_tables(cos, sin, head_dim)
    axes = batch_axes_of(mesh)
    return shard_rows(
        lambda x: _rotate(x, cos_full, sin_signed, cos.shape[1], interpret,
                          blocks),
        mesh, "rope", (rows_spec(axes, 3),), rows_spec(axes, 4))(x)
