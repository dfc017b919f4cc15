"""Ulysses-style all-to-all sequence parallelism (exact attention).

The second long-context strategy next to ops/ring_attention.py (task
contract; the reference's max sequence was BERT's 512 — SURVEY.md §6).
Where ring attention KEEPS the sequence sharded and rotates K/V blocks
around the mesh axis, the all-to-all (DeepSpeed-Ulysses) form RESWIZZLES
the layout for the attention op itself:

    [B, H, S/N, D]  --all_to_all-->  [B, H/N, S, D]
        (sequence-sharded)             (head-sharded, full sequence)

Each device then runs ordinary full-sequence attention for its H/N head
group — the flash kernel applies unchanged, causal masking is local, no
online-softmax bookkeeping across devices — and a second all_to_all
restores sequence sharding. Communication is two all-to-alls of the
activation size per call (vs ring's N-1 K/V rotations), which on TPU rides
ICI as one fused collective each way.

Trade-off vs ring: Ulysses needs ``num_heads % axis_size == 0`` and moves
Q too; ring has no head-count constraint and overlaps transfers with
compute. Both are exact; both are differentiable (all_to_all's transpose
is all_to_all, so no custom VJP is needed here).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .attention import fused_attention


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    implementation: str = "auto",
) -> jnp.ndarray:
    """Per-shard all-to-all attention (use inside shard_map).

    q/k/v: this device's sequence shard, [B, H, S_local, D]; the global
    sequence is the concatenation over ``axis_name`` in axis-index order.
    Requires H divisible by the axis size.
    """
    n = jax.lax.psum(1, axis_name)
    h = q.shape[1]
    if h % n:
        raise ValueError(
            f"ulysses attention needs num_heads ({h}) divisible by the "
            f"sequence-parallel axis size ({n}); use ring_attention for "
            f"head counts that don't divide")
    swizzle = partial(jax.lax.all_to_all, axis_name=axis_name,
                      split_axis=1, concat_axis=2, tiled=True)
    unswizzle = partial(jax.lax.all_to_all, axis_name=axis_name,
                        split_axis=2, concat_axis=1, tiled=True)
    qh, kh, vh = swizzle(q), swizzle(k), swizzle(v)  # [B, H/N, S, D]
    out = fused_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale,
                          implementation=implementation)
    return unswizzle(out)  # [B, H, S_local, D]


def ulysses_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "data",
    causal: bool = False,
    sm_scale: Optional[float] = None,
    batch_axis: Optional[str] = None,
    implementation: str = "auto",
) -> jnp.ndarray:
    """Global-array wrapper: shards the sequence dim over ``axis_name`` and
    runs the all-to-all attention; ``batch_axis`` additionally shards the
    batch dim (composed data × sequence parallelism). Same signature as
    ``ring_attention_sharded`` so callers can switch strategy by name."""
    spec = P(batch_axis, None, axis_name, None)
    fn = partial(ulysses_attention, axis_name=axis_name, causal=causal,
                 sm_scale=sm_scale, implementation=implementation)
    mapped = shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return mapped(q, k, v)
