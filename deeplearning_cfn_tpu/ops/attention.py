"""Fused multi-head attention: Pallas flash kernel + jnp reference.

Replaces the reference workloads' cuDNN/fused-CUDA attention (BERT, NMT —
SURVEY.md §3.3 "cuDNN / framework kernels"). Design:

- ``attention_reference``: straight jnp softmax(QKᵀ/√d + bias)V — the
  numerics oracle and the CPU/GPU fallback. XLA fuses this well already;
  the flash kernel's win is avoiding the [S,S] materialization in HBM.
- ``_flash_forward``: Pallas TPU kernel, online-softmax blocked over the KV
  sequence (flash attention). Grid is (batch, heads, Q blocks, KV blocks)
  with the KV axis innermost: running (m, l, acc) stats live in VMEM
  scratch and every operand is block-mapped, so per-step VMEM is O(block)
  — sequence length is bounded by HBM, not VMEM (cross-host long-context
  is the ring-attention path in ring_attention.py).
- ``_flash_backward``: FlashAttention-2-style blocked dq/dk/dv kernels with
  the same grid-accumulation structure — the forward saves only O and the
  per-row logsumexp, the backward recomputes P per block, so training
  memory is O(S) too (bias-free path).
- ``fused_attention``: public entry — on TPU dispatches to the kernels,
  except the hardware-measured short-sequence window (Sk < 1024, backward
  intermediate under cap) where XLA's own fused attention is faster;
  reference elsewhere. With a bias, the backward falls back to the
  reference VJP (a trainable bias's cotangent is [Sq,Sk]-shaped anyway).

Shapes: q [B, H, Sq, D]; k/v [B, H, Sk, D]; optional additive bias
broadcastable to [B, H, Sq, Sk] (use -inf for padding); returns [B, H, Sq, D].
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free

# Flash kernel tiling. Swept on a real v5e chip (2026-07-31, BERT-shaped
# d=64 cases at S in {512, 1024, 2048, 8192}): 1024x1024 beat the initial
# 256x128 by 1.3-4.7x fwd+bwd — bigger tiles amortize the d=64 contraction
# (half the MXU's 128 depth) over more rows/columns and cut grid overhead.
# The f32 score tile (BQ x BK = 4 MB) plus operand blocks stays inside the
# 16 MB scoped-VMEM budget; short sequences clamp to ceil8(S) anyway.
_BLOCK_Q = 1024
_BLOCK_K = 1024
# Row statistics (logsumexp, delta) are stored lane-replicated with a
# trailing dim of 8: Mosaic requires a block's last two dims to be
# (divisible by 8, divisible by 128) or equal to the array's — a bare
# [..., block_q] row vector satisfies neither on real hardware (it only
# works in interpret mode, which skips the check).
_STAT_LANES = 8


def _ceil8(n: int) -> int:
    return max(8, -(-n // 8) * 8)


# ---------------------------------------------------------------------------
# Reference implementation (oracle + fallback + backward)
# ---------------------------------------------------------------------------


def attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    causal: bool = False,
    sm_scale: Optional[float] = None,
) -> jnp.ndarray:
    """Plain jnp attention; computes in f32 regardless of input dtype (the
    softmax accumulator precision the kernel also uses)."""
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends
        k_pos = jnp.arange(sk)[None, :]
        logits = jnp.where(k_pos <= q_pos, logits, _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas flash kernel (forward)
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *,
                  causal: bool, sm_scale: float, seq_k: int, seq_q: int):
    """One (batch, head, q-block, kv-block) grid step of the online softmax.

    The kv-block axis is the innermost ("arbitrary") grid dimension: the
    (m, l, acc) running statistics live in VMEM scratch that persists
    across those steps, and the output block (indexed by the q block only)
    is written once, on the last kv step. Every operand is block-mapped —
    per-step VMEM is O(block), independent of sequence length, which is
    what lets the same kernel serve seq-512 BERT and seq-32k long-context.
    (An earlier design held K/V whole in VMEM and looped inside the
    kernel; it hit Mosaic's scoped-vmem limit at long S.)

    ``seq_q``/``seq_k`` are the TRUE (unpadded) lengths — the causal
    diagonal aligns their ends; the refs hold block-padded arrays. The
    [S,S] score matrix never exists in HBM.
    """
    from jax.experimental import pallas as pl  # deferred: TPU-only path

    block_q = q_ref.shape[-2]
    block_k = k_ref.shape[-2]
    iq = pl.program_id(2)
    kb = pl.program_id(3)
    num_kb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _accumulate():
        q = q_ref[0, 0, :, :].astype(jnp.float32) * sm_scale
        k_blk = k_ref[0, 0, :, :]
        v_blk = v_ref[0, 0, :, :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [block_q, block_k]
        if bias_ref is not None:
            s = s + bias_ref[0, 0, :, :].astype(jnp.float32)
        if causal:
            q_pos = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + iq * block_q \
                + (seq_k - seq_q)
            k_pos = jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1) + kb * block_k
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [block_q, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[...] = acc_new

    if causal:
        # Whole kv block above the diagonal for every row of this q block
        # (true positions; padded k columns lie above it by construction):
        # skip the matmuls entirely — the DMA still happens, the FLOPs not.
        q_end = (iq + 1) * block_q + (seq_k - seq_q)
        pl.when(kb * block_k < q_end)(_accumulate)
    else:
        _accumulate()

    @pl.when(kb == num_kb - 1)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        # Guard divide-by-zero for rows that saw no KV block at all (only
        # the padded tail rows of the last q block, which the caller slices
        # off; -1e30-bias "masked" rows still have l > 0 and softmax
        # normally).
        o_ref[0, 0, :, :] = (acc_scr[...] / jnp.maximum(l, 1e-30)) \
            .astype(o_ref.dtype)
        if lse_ref is not None:
            # Per-row logsumexp of the SCALED logits — the statistic the
            # flash backward needs to rebuild P without a second online
            # softmax. Rows that saw nothing (padded tail) get +LARGE so
            # the backward's exp(s - lse) underflows to exactly 0 for
            # them. Stored lane-replicated (see _STAT_LANES).
            lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)),
                            -_NEG_INF)  # [block_q, 1]
            lse_ref[0, 0, :, :] = jnp.broadcast_to(
                lse, lse_ref.shape[2:]).astype(jnp.float32)


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _flash_forward(q, k, v, bias, causal, sm_scale, interpret=False,
                   return_stats=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[-2]
    # Multiples of 8 (the f32 sublane count) — Mosaic's block-shape rule.
    block_q = min(_BLOCK_Q, _ceil8(sq))
    block_k = min(_BLOCK_K, _ceil8(sk))

    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    if bias is not None and bias.shape[-1] == 1:
        # The contract is "broadcastable to [B,H,Sq,Sk]"; a bias constant
        # across the K (softmax) axis shifts every logit in a row equally,
        # and softmax is invariant to that — it contributes nothing to the
        # output. Drop it instead of materializing [...,Sk] (its gradient,
        # exactly zero, still flows via the custom VJP's reference
        # recompute, which sees the original bias).
        bias = None
    if bias is not None:
        # Align the user bias's K axis with the padded KV (zeros are fine:
        # the pad_bias below kills padded columns).
        if bias.shape[-1] not in (sk, sk_p):
            raise ValueError(
                f"bias K dim {bias.shape[-1]} incompatible with kv length "
                f"{sk}")
        bias = _pad_to(bias.astype(jnp.float32), 3, block_k) \
            if bias.shape[-1] == sk else bias.astype(jnp.float32)
    if sk_p != sk and not causal:
        # Padded KV columns must never win the softmax. (The causal mask
        # already excludes them: q_pos < sk for every real row.)
        pad_bias = jnp.where(
            jnp.arange(sk_p) < sk, 0.0, _NEG_INF)[None, None, None, :]
        bias = pad_bias if bias is None else bias + pad_bias

    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
        pl.BlockSpec((1, 1, block_k, d),
                     lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
    ]
    args = [qp, kp, vp]
    # The causal diagonal is defined by the TRUE lengths (ends aligned, as
    # in attention_reference); padded q rows are sliced off at the end and
    # padded k columns sit above the diagonal, so neither corrupts it.
    kernel_kw = dict(causal=causal, sm_scale=sm_scale, seq_k=sk, seq_q=sq)
    if bias is not None:
        # Keep broadcast dims at size 1 (indexed with block 0) instead of
        # materializing [B,H,Sq,Sk] in HBM.
        bb, bh, bq = bias.shape[0], bias.shape[1], bias.shape[2]
        if bq > 1:
            bias = _pad_to(bias, 2, block_q)
        block_bq = block_q if bq > 1 else 1
        in_specs.append(pl.BlockSpec(
            (1, 1, block_bq, block_k),
            lambda ib, ih, iq, ik: (ib if bb > 1 else 0,
                                    ih if bh > 1 else 0,
                                    iq if bq > 1 else 0, ik)))
        args.append(bias)

        def kernel(q_ref, k_ref, v_ref, b_ref, o_ref, *rest):
            # rest = (lse_ref if return_stats) + 3 scratch refs
            lse = rest[0] if return_stats else None
            _flash_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse,
                          *rest[-3:], **kernel_kw)
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
            lse = rest[0] if return_stats else None
            _flash_kernel(q_ref, k_ref, v_ref, None, o_ref, lse,
                          *rest[-3:], **kernel_kw)

    out_specs = pl.BlockSpec((1, 1, block_q, d),
                             lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    out_shape = jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype)
    if return_stats:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                                  lambda ib, ih, iq, ik: (ib, ih, iq, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, sq_p, _STAT_LANES),
                                          jnp.float32)]

    result = pl.pallas_call(
        kernel,
        grid=(b, h, sq_p // block_q, sk_p // block_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, d), jnp.float32),            # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    if return_stats:
        out, lse = result
        return out[:, :, :sq, :], lse[:, :, :sq, 0]
    return result[:, :, :sq, :]


# ---------------------------------------------------------------------------
# Pallas flash kernels (backward)
#
# FlashAttention-2-style: the forward saves only O and the per-row
# logsumexp; the backward recomputes P block-by-block from (q, k, lse) — so
# no [Sq,Sk] tensor ever reaches HBM in training either. Two kernels:
# dK/dV (grid over KV blocks, inner loop over Q blocks) and dQ (grid over Q
# blocks, inner loop over KV blocks). delta = rowsum(dO * O) is a cheap
# jnp precompute.
#
# Derivation (S = scale·QKᵀ, P = softmax(S), O = PV):
#   dV = Pᵀ dO
#   dP = dO Vᵀ ;  dS = P ∘ (dP - delta)
#   dQ = scale · dS K ;  dK = scale · dSᵀ Q
# ---------------------------------------------------------------------------


def _bwd_mask(s, iq_block, ik_block, block_q, block_k, causal, seq_q, seq_k):
    """Recreate the forward's masking (true-length causal diagonal + padded
    KV columns) on one [block_q, block_k] score tile."""
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
        + ik_block * block_k
    live = k_pos < seq_k
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            + iq_block * block_q + (seq_k - seq_q)
        live = live & (k_pos <= q_pos)
    return jnp.where(live, s, _NEG_INF)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr, *, causal,
                           sm_scale, seq_q, seq_k):
    """One (batch, head, kv-block, q-block) grid step: accumulate this q
    block's contribution to dK/dV of one kv block in VMEM scratch; write on
    the last q step. Same block-mapped structure as the forward kernel."""
    from jax.experimental import pallas as pl

    ik = pl.program_id(2)
    qi = pl.program_id(3)
    num_qb = pl.num_programs(3)
    block_q = q_ref.shape[-2]
    block_k = k_ref.shape[-2]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _accumulate():
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        q_blk = q_ref[0, 0, :, :].astype(jnp.float32)
        do_blk = do_ref[0, 0, :, :].astype(jnp.float32)
        # Stats are lane-replicated [rows, _STAT_LANES]; one column
        # suffices.
        lse = lse_ref[0, 0, :, :][:, :1]
        delta = delta_ref[0, 0, :, :][:, :1]
        s = jax.lax.dot_general(
            q_blk, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _bwd_mask(s, qi, ik, block_q, block_k, causal, seq_q, seq_k)
        p = jnp.exp(s - lse)  # [bq, bk]; 0 for masked/padded rows
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, do_blk, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_blk, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] = dk_scr[...] + sm_scale * jax.lax.dot_general(
            ds, q_blk, dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Live iff this q block's last row reaches this kv block's first
        # column (ends-aligned true positions) — else skip the matmuls.
        pl.when((qi + 1) * block_q + (seq_k - seq_q) > ik * block_k)(
            _accumulate)
    else:
        _accumulate()

    @pl.when(qi == num_qb - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal, sm_scale, seq_q,
                         seq_k):
    """One (batch, head, q-block, kv-block) grid step: accumulate one kv
    block's contribution to dQ of one q block; write on the last kv step."""
    from jax.experimental import pallas as pl

    iq = pl.program_id(2)
    kb = pl.program_id(3)
    num_kb = pl.num_programs(3)
    block_q = q_ref.shape[-2]
    block_k = k_ref.shape[-2]

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _accumulate():
        q_blk = q_ref[0, 0, :, :].astype(jnp.float32)
        do_blk = do_ref[0, 0, :, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, :][:, :1]
        delta = delta_ref[0, 0, :, :][:, :1]
        k_blk = k_ref[0, 0, :, :].astype(jnp.float32)
        v_blk = v_ref[0, 0, :, :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q_blk, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _bwd_mask(s, iq, kb, block_q, block_k, causal, seq_q, seq_k)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do_blk, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] = dq_scr[...] + sm_scale * jax.lax.dot_general(
            ds, k_blk, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(kb * block_k < (iq + 1) * block_q + (seq_k - seq_q))(
            _accumulate)
    else:
        _accumulate()

    @pl.when(kb == num_kb - 1)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, interpret):
    """dq, dk, dv via the blocked kernels (bias-free path)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, sq, d = q.shape
    sk = k.shape[-2]
    block_q = min(_BLOCK_Q, _ceil8(sq))
    block_k = min(_BLOCK_K, _ceil8(sk))

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qp = _pad_to(q, 2, block_q)
    dop = _pad_to(g.astype(q.dtype), 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    # Padded q rows: lse=+LARGE makes exp(s - lse) underflow to 0, delta=0.
    lse_p = _pad_to(lse, 2, block_q)
    if lse_p.shape[-1] != sq:
        pad_rows = jnp.arange(lse_p.shape[-1]) >= sq
        lse_p = jnp.where(pad_rows[None, None, :], -_NEG_INF, lse_p)
    delta_p = _pad_to(delta, 2, block_q)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    # Lane-replicate the row stats (see _STAT_LANES): a [..., rows] array
    # cannot be block-mapped on real hardware.
    lse_p = jnp.broadcast_to(lse_p[..., None], (b, h, sq_p, _STAT_LANES))
    delta_p = jnp.broadcast_to(delta_p[..., None], (b, h, sq_p, _STAT_LANES))

    common = dict(causal=causal, sm_scale=sm_scale, seq_q=sq, seq_k=sk)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary")) if not interpret else None

    # dK/dV: grid over kv blocks, q blocks innermost (accumulated).
    q_by_inner = pl.BlockSpec((1, 1, block_q, d),
                              lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    row_by_inner = pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                                lambda ib, ih, ik, iq: (ib, ih, iq, 0))
    kv_by_outer = pl.BlockSpec((1, 1, block_k, d),
                               lambda ib, ih, ik, iq: (ib, ih, ik, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, **common),
        grid=(b, h, sk_p // block_k, sq_p // block_q),
        in_specs=[q_by_inner, kv_by_outer, kv_by_outer, q_by_inner,
                  row_by_inner, row_by_inner],
        out_specs=[kv_by_outer, kv_by_outer],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b, h, sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(qp, kp, vp, dop, lse_p, delta_p)

    # dQ: grid over q blocks, kv blocks innermost (accumulated).
    q_by_outer = pl.BlockSpec((1, 1, block_q, d),
                              lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    row_by_outer = pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                                lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    kv_by_inner = pl.BlockSpec((1, 1, block_k, d),
                               lambda ib, ih, iq, ik: (ib, ih, ik, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(b, h, sq_p // block_q, sk_p // block_k),
        in_specs=[q_by_outer, kv_by_inner, kv_by_inner, q_by_outer,
                  row_by_outer, row_by_outer],
        out_specs=q_by_outer,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lse_p, delta_p)

    return dq[:, :, :sq, :], dk[:, :, :sk, :], dv[:, :, :sk, :]


# ---------------------------------------------------------------------------
# Public entry with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _fused_attention(q, k, v, bias, causal, sm_scale, use_pallas, interpret):
    if use_pallas:
        return _flash_forward(q, k, v, bias, causal, sm_scale,
                              interpret=interpret)
    return attention_reference(q, k, v, bias, causal, sm_scale)


def _fwd(q, k, v, bias, causal, sm_scale, use_pallas, interpret):
    if use_pallas and bias is None:
        # Full flash path: keep O + logsumexp so the backward kernels can
        # rebuild P per block — O(S) residual memory in training too.
        out, lse = _flash_forward(q, k, v, None, causal, sm_scale,
                                  interpret=interpret, return_stats=True)
        return out, (q, k, v, None, out, lse)
    out = _fused_attention(q, k, v, bias, causal, sm_scale, use_pallas,
                           interpret)
    return out, (q, k, v, bias, None, None)


def _bwd(causal, sm_scale, use_pallas, interpret, res, g):
    q, k, v, bias, out, lse = res
    if use_pallas and bias is None:
        dq, dk, dv = _flash_backward(q, k, v, out, lse, g, causal,
                                     sm_scale, interpret)
        return dq, dk, dv, None
    # Bias path (trainable biases must receive a cotangent, and dS would be
    # a full [Sq,Sk] output anyway): recompute through the reference
    # formulation — XLA fuses it. Costs O(S²) backward memory; bias-free
    # training (the long-context path) never lands here.
    def f(q, k, v, bias):
        return attention_reference(q, k, v, bias, causal, sm_scale)
    _, vjp = jax.vjp(f, q, k, v, bias)
    dq, dk, dv, dbias = vjp(g)
    return dq, dk, dv, None if bias is None else dbias


_fused_attention.defvjp(_fwd, _bwd)


# Auto-dispatch crossover, measured on hardware in r03 (BASELINE.md kernel
# table, v5e): XLA's own fused attention beat the flash kernel at S=512
# (9.0 ms vs 6.7 ms, 0.74×) while flash won 1.4× at S=2048 and 35× at
# S=8192 (where XLA spills the [S,S] matrix to HBM). Between the measured
# points the switch sits at 1024. The XLA path's backward holds 2-3
# O(B·H·Sq·Sk) f32 buffers live at once (softmax residual + dp/dlogits),
# so eligibility is capped on ONE such buffer at 512 MiB — ~1.5 GiB real
# peak, a safe transient on a 16 GB chip. Above it the flash kernel's
# O(S) memory wins regardless of speed.
_SHORT_SEQ_THRESHOLD = 1024
_REF_BWD_BYTES_CAP = 512 << 20


def _auto_use_pallas(backend: str, b: int, h: int, sq: int, sk: int) -> bool:
    """The 'auto' dispatch decision (pure, unit-tested): flash kernel on
    TPU except in the measured short-sequence window where XLA's fused
    attention is faster AND its quadratic backward intermediate fits."""
    if backend != "tpu":
        return False
    ref_bytes = b * h * sq * sk * 4
    return not (sk < _SHORT_SEQ_THRESHOLD and ref_bytes <= _REF_BWD_BYTES_CAP)


def fused_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    implementation: str = "auto",
) -> jnp.ndarray:
    """Multi-head attention, fused on TPU.

    implementation: 'auto' (on TPU: flash kernel, except the measured
    short-sequence window — Sk < 1024 with the quadratic backward
    intermediate under cap — where XLA's own fused attention is faster;
    off-TPU: reference), 'pallas', 'reference', or 'interpret' (pallas
    kernel in interpreter mode — CPU-runnable, used by tests to validate
    kernel numerics).
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected [B,H,S,D] inputs, got {q.shape}")
    if causal and q.shape[-2] > k.shape[-2]:
        # Ill-defined: ends are aligned, so the leading queries would
        # precede every key (and the kernel/reference paths would disagree
        # on what an all-masked softmax row means).
        raise ValueError(
            f"causal attention requires Sq <= Sk, got {q.shape[-2]} > "
            f"{k.shape[-2]}")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if implementation == "auto":
        b, h, sq, _ = q.shape
        use_pallas = _auto_use_pallas(jax.default_backend(), b, h, sq,
                                      k.shape[-2])
        interpret = False
    elif implementation == "pallas":
        use_pallas, interpret = True, False
    elif implementation == "interpret":
        use_pallas, interpret = True, True
    elif implementation == "reference":
        use_pallas, interpret = False, False
    else:
        raise ValueError(f"unknown implementation {implementation!r}")
    return _fused_attention(q, k, v, bias, causal, scale, use_pallas,
                            interpret)
