"""State-space token mixers: Mamba-2's (Dao & Gu 2024), the mixer of nine
layers in ten of IBM's Granite-4.0-H (``models/lm.py:gpt_granite4_h_micro``).

A block takes it in attention's place (``BlockStyle.mixer = "mamba2"``); it
carries a state along the sequence where attention looks back over it.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import get_tracer
from ..ops.conv import causal_conv_silu, conv_path
from ..ops.ssd import scan_path, ssd_scan
from .transformer import Leaf, RMSNorm, shift_later

Dtype = Any

# The ranges a head's decay rate and step size are laid over. The Mamba-2
# reference code draws ``dt`` log-uniform over [0.001, 0.1] and ``A`` uniform
# over [1, 16]; the rates here reach down to 1/16, because over [1, 16] only
# 4 heads of 64 remember past a chunk of 256 tokens, and those are the heads
# with the smallest steps, whose state adds a fiftieth of what ``D x`` adds:
# a program that handed no state from chunk to chunk read as a sound one
# (benchmark/configs/granite4_h_micro.json, `assumed.seeded_mixer`).
A_RANGE = (1.0 / 16.0, 16.0)
DT_RANGE = (1e-3, 1e-1)


def head_constants(heads: int):
    """``(a [heads], dt_bias [heads])``: initial decay rates and step-size
    biases laid as a grid over the heads instead of drawn: head ``i * side +
    j`` of ``side**2`` heads has the ``i``-th of ``side`` rates log-spaced
    over ``A_RANGE`` and the ``j``-th of ``side`` step sizes log-spaced over
    ``DT_RANGE`` (its bias the inverse softplus of it), so that ``dt * a``
    spans 0.00006 to 1.6: a head remembers between one token and more than
    a sequence, and 23 of 64 past a chunk of 256."""
    side = math.isqrt(heads)
    if side * side != heads:
        raise ValueError(f"{heads} heads are not a square grid")
    a = np.repeat(np.exp(np.linspace(*np.log(A_RANGE), side)), side)
    dt = np.tile(np.exp(np.linspace(*np.log(DT_RANGE), side)), side)
    return a.astype(np.float32), np.log(np.expm1(dt)).astype(np.float32)


def conv_gain(taps: int, channels: int) -> float:
    """What a Xavier-uniform ``[taps, channels]`` matrix is multiplied by to
    be distributed as ``nn.Conv1d``'s depthwise taps are (uniform over
    +-1 / sqrt(taps))."""
    return math.sqrt((taps + channels) / (6.0 * taps))


class CausalConv(nn.Module):
    """``y[t] = bias + sum_j w[j] * x[t - (taps - 1 - j)]`` a channel, zeros
    before position 0, float32. The taps are ``gain * kernel``: see
    :class:`Mamba2Mixer`.

    Called with ``splits`` it is the mixer's whole step, ``silu(y)`` in
    ``x``'s dtype with the channels cut at ``splits`` (a tuple of arrays), by
    the carrier ``ops/conv.py:conv_path`` names from ``implementation``, the
    backend and the shape: ``kernel``, one Pallas kernel forward and one
    backward that hold the shifts, the bias and silu in VMEM
    (``ops/conv.py``, ``mesh`` the step's); or ``xla``, the shifted
    multiply-adds below with ``nn.silu``, the cast and ``jnp.split`` after
    them. Called with ``x`` alone it is the shifted multiply-adds, ``y``."""

    taps: int

    @nn.compact
    def __call__(self, x, splits=None, implementation="auto", mesh=None):
        channels = x.shape[-1]
        kernel = self.param("kernel", nn.initializers.xavier_uniform(),
                            (self.taps, channels), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (channels,),
                          jnp.float32)
        w = conv_gain(self.taps, channels) * kernel
        if splits is not None:
            path, interpret = conv_path(implementation, x.shape, self.taps,
                                        splits, x.dtype.itemsize)
            if path == "kernel":
                return causal_conv_silu(x, w, bias, splits, interpret, mesh)
        wide = x.astype(jnp.float32)
        y = bias + sum(w[j] * shift_later(wide, self.taps - 1 - j)
                       for j in range(self.taps))
        if splits is None:
            return y
        return tuple(jnp.split(nn.silu(y).astype(x.dtype), splits, axis=-1))


class Mamba2Mixer(nn.Module):
    """``u [B, S, F]`` -> ``[B, S, F]``:

    ``[z | xBC | dt] = u W_in`` (``inner | inner + 2 * groups * state |
    heads``, ``inner = heads * head_dim``, no bias); ``xBC <-
    silu(conv(xBC))``, a causal depthwise convolution of ``conv_taps`` taps
    with a bias; ``[x | B | C] = xBC``; ``dt <- softplus(dt + dt_bias)``;
    the recurrence of ``ops/ssd.py`` over ``heads`` heads of ``head_dim``
    with a state of ``state`` and ``A = -exp(A_log)`` a head, chunked at
    ``chunk``; ``y <- y + D * x``; ``y <- RMSNorm(y * silu(z)) * w`` over all
    ``inner`` channels (the gate first, then the norm); ``y W_out``.

    **How the learned numbers a head are held.** Every parameter is a leaf
    a seeded initialiser knows (a 2-D ``kernel``, a ``bias`` from 0, a
    ``scale`` from 1: ``Leaf``'s kinds), and what those give has to leave
    the recurrence something to do: with ``A_log`` = 0 and ``dt_bias`` = 0
    every head would forget inside two tokens. So ``A_log = log(a_h) +
    a_log/bias`` and ``dt_bias = c_h + dt_bias/bias`` with ``a_h``, ``c_h``
    the constants of :func:`head_constants`, and the taps are
    ``conv_gain * conv/kernel`` (:func:`conv_gain`), which makes a
    Xavier-uniform kernel the reference code's taps; ``d_skip/scale`` is
    ``D``, 1 as published. The same functions of as many learned numbers; a
    checkpoint of the source loads by subtracting, or dividing by, the
    constants.

    ``scan_impl`` is the block's ``attention_impl``: with the shapes it
    decides whether the scan runs as ``ops/ssd.py``'s kernels or its einsums
    (``ops/ssd.py:scan_path``) and whether ``silu(conv(xBC))`` runs as
    ``ops/conv.py``'s kernels, which write ``x``, ``B`` and ``C`` as the
    scan reads them, or as XLA's shifted multiply-adds with the split after
    (``ops/conv.py:conv_path``); ``mesh`` is the step's, for the kernels.

    Scopes, for the trace: ``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``,
    ``ssm_gate_norm``, ``ssm_out_proj``. Counted when a call is traced:
    ``ssm.scan.calls`` by ``path`` (the one taken) and ``chunk``,
    ``ssm.conv.calls`` by ``path``; gauges ``ssm.scan.chunks`` and
    ``ssm.state_bytes`` (docs/OBSERVABILITY.md)."""

    heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv_taps: int = 4
    chunk: int = 256
    rms_eps: float = 1e-5
    dtype: Dtype = jnp.bfloat16
    scan_impl: str = "auto"
    mesh: Any = None

    @nn.compact
    def __call__(self, u):
        bsz, seq, features = u.shape
        inner, bc = self.heads * self.head_dim, self.groups * self.state
        dense = lambda feats, name: nn.Dense(
            feats, dtype=self.dtype, param_dtype=jnp.float32, name=name,
            use_bias=False, kernel_init=nn.initializers.xavier_uniform())
        registry = get_tracer().registry
        registry.counter(
            "ssm.scan.calls",
            "state-space mixer calls traced, by the scan's path and chunk",
        ).inc(path=scan_path(
            self.scan_impl, (bsz, seq, self.heads, self.head_dim),
            self.state, self.groups, self.chunk)[0], chunk=str(self.chunk))
        cuts = (inner, inner + bc)
        registry.counter(
            "ssm.conv.calls",
            "state-space mixer calls traced, by the convolution's path",
        ).inc(path=conv_path(
            self.scan_impl, (bsz, seq, inner + 2 * bc), self.conv_taps, cuts,
            jnp.dtype(self.dtype).itemsize)[0])
        registry.gauge(
            "ssm.scan.chunks", "chunks a sequence's scan is cut into",
        ).set(max(seq // self.chunk, 1))
        registry.gauge(
            "ssm.state_bytes",
            "bytes of float32 state a layer carries along one sequence",
        ).set(4 * self.heads * self.head_dim * self.state)
        with jax.named_scope("ssm_in_proj"):
            z, xbc, dt = jnp.split(
                dense(2 * inner + 2 * bc + self.heads, "in_proj")(u),
                (inner, 2 * inner + 2 * bc), axis=-1)
        with jax.named_scope("ssm_conv"):
            x, b, c = CausalConv(self.conv_taps, name="conv")(
                xbc, cuts, self.scan_impl, self.mesh)
        with jax.named_scope("ssm_scan"):
            a_h, c_h = head_constants(self.heads)
            head = (self.heads,)
            a = -a_h * jnp.exp(Leaf("bias", head, name="a_log")())
            dt = jax.nn.softplus(dt.astype(jnp.float32) + c_h
                                 + Leaf("bias", head, name="dt_bias")())
            x = x.reshape(bsz, seq, self.heads, self.head_dim)
            y = ssd_scan(x, dt, a,
                         b.reshape(bsz, seq, self.groups, self.state),
                         c.reshape(bsz, seq, self.groups, self.state),
                         self.chunk, self.scan_impl, mesh=self.mesh)
            skip = Leaf("scale", head, name="d_skip")()
            y = (y.astype(jnp.float32) + skip[:, None] * x).reshape(
                bsz, seq, inner)
        with jax.named_scope("ssm_gate_norm"):
            y = RMSNorm(self.rms_eps, self.dtype, name="gate_norm")(
                y * nn.silu(z.astype(jnp.float32)))
        with jax.named_scope("ssm_out_proj"):
            return dense(features, "out_proj")(y)
