"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context support (task contract; absent from the reference, whose max
sequence was BERT's 512 — SURVEY.md §6). Sequences longer than one chip's
HBM shard across a mesh axis; each device holds a [S/N] slice of Q, K, V.
K/V blocks then rotate around the ring via ``lax.ppermute`` (XLA lowers it
to ICI neighbor transfers), and every device accumulates its Q block's
attention with the same online-softmax update the flash kernel uses — so
the result is *exact* attention, with compute and communication overlapped
by XLA's collective scheduler, not an approximation.

``ring_attention`` is the per-shard collective function (call inside
``shard_map``); ``ring_attention_sharded`` wraps it for a global array +
mesh. Causality is handled with global positions derived from the axis
index, so block (i, j) is skipped entirely when it lies above the diagonal.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attn(q, k, v, bias_blk, q_off, k_off, causal, scale):
    """One (local Q, rotating KV) block: returns (m, l-scaled) partials."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias_blk is not None:
        s = s + bias_blk.astype(jnp.float32)
    if causal:
        sq, sk = q.shape[-2], k.shape[-2]
        q_pos = jnp.arange(sq)[:, None] + q_off
        k_pos = jnp.arange(sk)[None, :] + k_off
        s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [b,h,q,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    return m, l, pv


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-shard ring attention (use inside shard_map).

    q/k/v: this device's sequence shard, [B, H, S_local, D]; the global
    sequence is the concatenation over ``axis_name`` in axis-index order.

    Differentiable with O(S_local) memory: a custom VJP re-rotates K/V in
    the backward instead of saving every rotation as scan residuals (which
    would grow per-device memory with the axis size — defeating sequence
    parallelism at exactly the scale it targets).
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    return _ring_attention(q, k, v, axis_name, causal, scale)


def _ring_perm(axis_size):
    return [(i, (i + 1) % axis_size) for i in range(axis_size)]


def _ring_forward_impl(q, k, v, axis_name, causal, scale):
    """Online-softmax ring pass; returns (out, lse[b,h,s_local,1])."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    q_off = my_idx * s_local

    def accumulate(carry, k_r, v_r, r):
        m_prev, l_prev, acc_prev = carry
        # After r rotations we hold the shard originally on (my_idx - r).
        src = (my_idx - r) % axis_size
        k_off = src * s_local
        m_cur, l_cur, pv = _block_attn(q, k_r, v_r, None, q_off, k_off,
                                       causal, scale)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha_prev = jnp.exp(m_prev - m_new)
        alpha_cur = jnp.exp(m_cur - m_new)
        l_new = l_prev * alpha_prev + l_cur * alpha_cur
        acc_new = acc_prev * alpha_prev + pv * alpha_cur
        return m_new, l_new, acc_new

    perm = _ring_perm(axis_size)

    def step(carry, r):
        stats, kv = carry
        # Rotate first, then accumulate — so the local (r=0) block is done
        # outside the loop and only axis_size-1 rotations happen in total.
        # XLA overlaps the ppermute with the einsums where the schedule
        # allows.
        k_r = jax.lax.ppermute(kv[0], axis_name, perm)
        v_r = jax.lax.ppermute(kv[1], axis_name, perm)
        stats = accumulate(stats, k_r, v_r, r)
        return (stats, (k_r, v_r)), None

    init_stats = (
        jnp.full((b, h, s_local, 1), _NEG_INF, jnp.float32),
        jnp.zeros((b, h, s_local, 1), jnp.float32),
        jnp.zeros((b, h, s_local, d), jnp.float32),
    )
    stats = accumulate(init_stats, k, v, 0)  # own shard, no comm
    if axis_size > 1:
        (stats, _), _ = jax.lax.scan(step, (stats, (k, v)),
                                     jnp.arange(1, axis_size))
    m, l, acc = stats
    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_attention(q, k, v, axis_name, causal, scale):
    return _ring_forward_impl(q, k, v, axis_name, causal, scale)[0]


def _ring_fwd(q, k, v, axis_name, causal, scale):
    out, lse = _ring_forward_impl(q, k, v, axis_name, causal, scale)
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, scale, res, g):
    """Backward ring: q/do/lse/delta stay home; (k, v, dk, dv) rotate.

    Each rotation recomputes P for one (local Q, visiting KV) block from
    the saved logsumexp (flash-style), adds this q-shard's contribution to
    the visiting block's dk/dv, and accumulates dq locally. After the full
    ring plus one final rotation the dk/dv partials arrive back on their
    home device — total memory stays O(S_local), independent of axis size.
    """
    q, k, v, out, lse = res
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, s_local, d = q.shape
    q_off = my_idx * s_local
    perm = _ring_perm(axis_size)

    do = g.astype(jnp.float32)
    qf = q.astype(jnp.float32)
    delta = jnp.sum(do * out.astype(jnp.float32), axis=-1, keepdims=True)

    def block_grads(k_r, v_r, r):
        src = (my_idx - r) % axis_size
        k_off = src * s_local
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_r.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = jnp.arange(s_local)[:, None] + q_off
            k_pos = jnp.arange(s_local)[None, :] + k_off
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)  # [b,h,q,k]; 0 where masked
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, do,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, v_r.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_c = scale * jnp.einsum("bhqk,bhkd->bhqd", ds,
                                  k_r.astype(jnp.float32),
                                  preferred_element_type=jnp.float32)
        dk_c = scale * jnp.einsum("bhqk,bhqd->bhkd", ds, qf,
                                  preferred_element_type=jnp.float32)
        return dq_c, dk_c, dv_c

    # r = 0: own block, no comm.
    dq, dk, dv = block_grads(k, v, 0)

    def step(carry, r):
        dq_acc, kvg = carry
        k_r = jax.lax.ppermute(kvg[0], axis_name, perm)
        v_r = jax.lax.ppermute(kvg[1], axis_name, perm)
        dk_r = jax.lax.ppermute(kvg[2], axis_name, perm)
        dv_r = jax.lax.ppermute(kvg[3], axis_name, perm)
        dq_c, dk_c, dv_c = block_grads(k_r, v_r, r)
        return (dq_acc + dq_c, (k_r, v_r, dk_r + dk_c, dv_r + dv_c)), None

    if axis_size > 1:
        (dq, (_, _, dk, dv)), _ = jax.lax.scan(
            step, (dq, (k, v, dk, dv)), jnp.arange(1, axis_size))
        # The visiting block is one final hop from home.
        dk = jax.lax.ppermute(dk, axis_name, perm)
        dv = jax.lax.ppermute(dv, axis_name, perm)

    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_attention_sharded(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    axis_name: str = "data",
    causal: bool = False,
    sm_scale: Optional[float] = None,
    batch_axis: Optional[str] = None,
) -> jnp.ndarray:
    """Global-array wrapper: shards the sequence dim over ``axis_name`` and
    runs the ring; ``batch_axis`` additionally shards the batch dim
    (composed data × sequence parallelism). For richer layouts call
    ``ring_attention`` directly inside your own shard_map."""
    spec = P(batch_axis, None, axis_name, None)
    fn = partial(ring_attention, axis_name=axis_name, causal=causal,
                 sm_scale=sm_scale)
    mapped = shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    return mapped(q, k, v)
