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
   ``exp(cum_last)``: the one sequential part, ``S / chunk`` steps of a
   ``lax.scan``;
4. ``C`` times the state a chunk entered with, decayed to each position.

The running sums, the exponentials and the carried state are float32; the
four products take operands of ``x``'s dtype (bfloat16 in training) and
accumulate in float32. Plain ``jnp``: the backward pass is autodiff's. The
decay matrices are ``[B, H, S / chunk, chunk, chunk]`` float32 (0.54 GB for
64 heads over 8,192 tokens at 256, which a recomputed block of
``granite4_h_micro_train_8k`` holds beside 12.4 GB of state: PERF.md
section 4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_scan(x, dt, a, b, c, chunk: int):
    """``y [B, S, H, P]`` of the recurrence above, in ``x``'s dtype.

    ``x [B, S, H, P]``; ``dt [B, S, H]`` float32, positive; ``a [H]``
    float32, negative; ``b``, ``c`` ``[B, S, G, N]`` with ``H`` a multiple of
    ``G`` (head ``h`` reads group ``h // (H / G)``). ``S`` is a multiple of
    ``chunk`` or shorter than it (then it is one chunk)."""
    bsz, seq, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if heads % groups:
        raise ValueError(f"{heads} heads cannot share {groups} groups")
    q = min(chunk, seq)
    if seq % q:
        raise ValueError(f"{seq} positions are not whole chunks of {q}")
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
