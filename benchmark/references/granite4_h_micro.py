"""Plain reference for the ``granite4_h_micro`` configuration: one chip's
share of IBM's granite-4.0-h-micro in straightforward ``jax.numpy`` and
float32 (``Precision.HIGHEST``), its loss, its gradients and the AdamW step
the configuration states. No kernels, no chunked algorithm, nothing imported
from the program: **the state-space recurrence is run itself, a token at a
time**; the convolution is four shifted multiplies written out here.

**The layer equations** (``h`` a layer's input ``[T, 2048]``; every matrix
without bias; ``m = residual_multiplier`` 0.22):

1. ``h_0 = embedding_multiplier * E[ids]`` (12).
2. A layer: ``h <- h + m * mixer(RMSNorm(h))``, then ``h <- h + m *
   MLP(RMSNorm(h))``, ``MLP(u) = (silu(u W1) * u W3) W2`` at 8192
   (``mlp_in`` holds ``W1 | W3`` side by side; the source fuses them the same
   way into ``input_linear``); RMSNorm with eps 1e-5 inside the root.
3. ``layer_types[i] == "mamba"``, ``u = RMSNorm(h)``: ``[z | xBC | dt] = u
   W_in`` (4096 | 4352 | 64); ``xBC_t <- silu(b + sum_j w_j xBC_{t-3+j})``,
   ``j = 0..3``, a tap a channel, zeros before position 0; ``[x | B | C] =
   xBC`` (4096 as 64 heads of 64 | 128 | 128, one group shared by every
   head); ``dt <- softplus(dt + dt_bias)``; ``A = -exp(A_log)`` a head;
   ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t`` (64 x 128 a head,
   zero before position 0), ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y *
   silu(z)) * w`` over all 4,096 channels (the gate first, then the norm:
   ``mamba_n_groups`` 1); the mixer's result ``y W_out``.
4. ``layer_types[i] == "attention"``: q, k, v, o; 32 query heads over 8 K/V
   heads of 64; **no positional signal**; ``softmax(attention_multiplier * q
   k^T + causal) v`` with the multiplier 1/64 where ``1 / sqrt(64)`` would
   be 1/8.
5. After the last layer held: RMSNorm, ``logits = h E^T / logits_scaling``
   (8) over the vocabulary rows held; the loss is the mean next-token
   cross-entropy.

**How the mixer's numbers a head arrive** (``models/ssm.py`` says why): the
parameters are the program's leaves, and ``A_log = log(a_h) + a_log/bias``,
``dt_bias = c_h + dt_bias/bias``, ``w = gain * conv/kernel``, ``D =
d_skip/scale``, with ``a_h``, ``c_h`` and ``gain`` computed here from the
configuration's ``seeded_constants`` (a grid of the Mamba-2 reference code's
initial ranges over the heads; the factor that makes a Xavier-uniform ``[4,
4352]`` matrix ``nn.Conv1d``'s taps).

**Departures from the published description**: none in the mathematics. A
packed row carries its state, convolves and attends across its documents'
boundaries (the traffic has none to reset at); the configuration file lists
each reading under ``assumed`` with the one it was chosen over.

Parameters arrive as the nested dict the program's own tree has
(``token/embedding``, ``layer_<i>/{self_attn_norm,mlp_norm}/scale``,
``layer_<i>/mlp/{mlp_in,mlp_out}/kernel``, a Mamba layer's
``layer_<i>/self_attn/{in_proj,out_proj}/kernel``, ``conv/{kernel,bias}``,
``{a_log,dt_bias}/bias``, ``d_skip/scale``, ``gate_norm/scale``, the
attention layer's ``layer_<i>/self_attn/{query,key,value,attn_out}/kernel``,
``final_norm/scale``). A name looked up and not found is an error.

Memory: 772 M parameters in float32 with Adam's two moments and a gradient
are 12.36 GB of the chip's 16.9, so every layer runs under
``jax.checkpoint`` one block of ``block_rows`` rows at a time, the
recurrence under a two-level ``lax.scan`` whose inner level (``SCAN_ROWS``
tokens) is recomputed in the backward pass (8,192 states of 2 MB a layer
cannot be kept), what a mixer does before and after it ``MIXER_ROWS`` tokens
at a time (a dozen float32 arrays of ``[8192, 4352]`` otherwise live at
once), attention one K/V head's group and ``ATTN_ROWS`` query rows
at a time, the MLP and the head ``MLP_ROWS`` and ``HEAD_ROWS`` tokens at a
time, and ``train_steps`` consumes ``params``: it keeps the starting values
on the host and gives the device buffers to the first step, computes the
gradient and applies the update in two programs, and keeps Adam's moments on
the host while the first of them runs.
"""

from __future__ import annotations

import math
import os
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import precision as _precision  # noqa: E402  (sibling file, no package)

SCAN_ROWS = 64    # tokens of the recurrence kept at a time (memory only)
MIXER_ROWS = 1024  # tokens a mixer takes at a time round its scan (memory only)
ATTN_ROWS = 1024  # query rows a group of heads takes at a time (memory only)
MLP_ROWS = 1024   # tokens the MLP takes at a time (memory only)
HEAD_ROWS = 1024  # tokens the head and the loss take at a time (memory only)


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def _before(x, n):
    """``x [B, S, ...]`` as the position ``n`` places later sees it:
    ``y[:, t] = x[:, t - n]``, zeros before the sequence's start."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :n]), x[:, :-n]], axis=1)


def seeded_constants(sizes: Dict[str, Any]):
    """``(a [heads], c [heads], gain)`` of the module's note above, from the
    configuration's ``seeded_constants``: head ``i * side + j`` of
    ``side**2`` has the ``i``-th of ``side`` decay rates log-spaced over
    ``a_range`` and the ``j``-th of ``side`` step sizes log-spaced over
    ``dt_range``, ``c`` the inverse softplus of the step size; ``gain``
    takes Xavier's bound for ``[taps, channels]`` to ``conv_tap_bound``."""
    k, heads = sizes["seeded_constants"], sizes["mamba_n_heads"]
    side = math.isqrt(heads)
    if side * side != heads:
        raise ValueError(f"{heads} heads are not a square grid")
    a = np.repeat(np.exp(np.linspace(*np.log(k["a_range"]), side)), side)
    dt = np.tile(np.exp(np.linspace(*np.log(k["dt_range"]), side)), side)
    channels = heads * sizes["mamba_d_head"] \
        + 2 * sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    gain = k["conv_tap_bound"] * math.sqrt(
        (sizes["mamba_d_conv"] + channels) / 6.0)
    return (jnp.asarray(a, jnp.float32),
            jnp.asarray(np.log(np.expm1(dt)), jnp.float32), gain)


def recurrence(x, dt, a, b, c, reset_every: int = 0):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t (outer)
    B_t``, a token at a time: ``x [B, S, H * P]`` (a head's ``P`` side by
    side, as the projection leaves them), ``dt [B, S, H]``, ``a [H]``,
    ``b``, ``c`` ``[B, S, G, N]`` -> ``[B, S, H * P]``. ``reset_every`` > 0
    is a control: the state is emptied before every token whose position
    divides by it (a chunked program that hands no state from chunk to
    chunk)."""
    bsz, seq, heads = dt.shape
    p, per_group = x.shape[2] // heads, heads // b.shape[2]
    rows = min(SCAN_ROWS, seq)
    if seq % rows:
        raise ValueError(f"{seq} positions are not a multiple of {rows}")
    keep = jnp.ones((seq,)) if not reset_every else \
        (jnp.arange(seq) % reset_every != 0).astype(jnp.float32)

    def token(state, t):
        x_t, dt_t, b_t, c_t, keep_t = t
        b_t, c_t = (jnp.repeat(g, per_group, axis=1)[:, :, None, :]
                    for g in (b_t, c_t))                 # [B, H, 1, N]
        x_t = x_t.reshape(bsz, heads, p)
        state = state * (keep_t * jnp.exp(dt_t * a))[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t
        return state, jnp.sum(state * c_t, axis=-1).reshape(bsz, heads * p)

    @jax.checkpoint
    def some_tokens(state, ts):
        return jax.lax.scan(token, state, ts)

    blocks = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        seq // rows, rows, *t.shape[:1], *t.shape[2:])
    _, y = jax.lax.scan(
        some_tokens, jnp.zeros((bsz, heads, p, b.shape[3]), jnp.float32),
        (blocks(x), blocks(dt), blocks(b), blocks(c),
         keep.reshape(seq // rows, rows)))
    return jnp.moveaxis(y.reshape(seq, bsz, heads * p), 0, 1)


def mamba_mixer(mm, u, p, sizes, carry_state=True, decay=True, drop_tap=None,
                gate_after_norm=False):
    """Step 3 for ``u [B, S, F]``. The keywords are the controls: no state
    over a chunk's boundary, ``A`` = 0, the tap ``drop_tap`` left out, the
    gate applied after the norm."""
    bsz, seq, _ = u.shape
    heads, d, n = (sizes["mamba_n_heads"], sizes["mamba_d_head"],
                   sizes["mamba_d_state"])
    groups, taps = sizes["mamba_n_groups"], sizes["mamba_d_conv"]
    inner, bc = heads * d, groups * n
    a_h, c_h, gain = seeded_constants(sizes)
    eps = sizes["rms_norm_eps"]
    rows = min(MIXER_ROWS, seq)
    if seq % rows:
        raise ValueError(f"{seq} positions are not a multiple of {rows}")
    if p["conv"]["kernel"].shape != (taps, inner + 2 * bc):
        raise ValueError(f"taps of shape {p['conv']['kernel'].shape}")
    w = gain * p["conv"]["kernel"]
    # A block of tokens sees the taps - 1 before it; before position 0 the
    # projection of nothing is nothing (no bias).
    u_from = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))

    def before_the_scan(first):
        ub = jax.lax.dynamic_slice_in_dim(u_from, first, rows + taps - 1,
                                          axis=1)
        z, xbc, dt = jnp.split(mm(ub, p["in_proj"]["kernel"]),
                               (inner, 2 * inner + 2 * bc), axis=-1)
        xbc = jax.nn.silu(p["conv"]["bias"] + sum(
            w[j] * xbc[:, j:j + rows] for j in range(taps) if j != drop_tap))
        return z[:, taps - 1:], xbc, dt[:, taps - 1:]

    def after_the_scan(block):
        y, x, z = block
        y = y + (p["d_skip"]["scale"][:, None] * x.reshape(
            bsz, rows, heads, d)).reshape(bsz, rows, inner)
        y = _rms_norm(y, p["gate_norm"], eps) * jax.nn.silu(z) \
            if gate_after_norm else _rms_norm(y * jax.nn.silu(z),
                                              p["gate_norm"], eps)
        return mm(y, p["out_proj"]["kernel"])

    together = lambda t: jnp.moveaxis(t, 0, 1).reshape(
        bsz, seq, *t.shape[3:])                  # [blocks, B, rows, ...]
    apart = lambda t: jnp.moveaxis(t.reshape(
        bsz, seq // rows, rows, *t.shape[2:]), 1, 0)
    z, xbc, dt = (together(t) for t in jax.lax.map(
        jax.checkpoint(before_the_scan), jnp.arange(0, seq, rows)))
    x, b, c = jnp.split(xbc, (inner, inner + bc), axis=-1)
    a = -a_h * jnp.exp(p["a_log"]["bias"])
    dt = jax.nn.softplus(dt + c_h + p["dt_bias"]["bias"])
    y = recurrence(x, dt, a if decay else 0.0 * a,
                   b.reshape(bsz, seq, groups, n),
                   c.reshape(bsz, seq, groups, n),
                   0 if carry_state else sizes["mamba_chunk_size"])
    return together(jax.lax.map(jax.checkpoint(after_the_scan),
                                (apart(y), apart(x), apart(z))))


def attention(mm, u, p, sizes, scale):
    """Step 4 for ``u [B, S, F]``: one K/V head's query heads at a time,
    ``ATTN_ROWS`` of their rows at a time, each recomputed in the backward
    pass."""
    b, s, _ = u.shape
    h, hk = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes["hidden_size"] // h
    g, rows = h // hk, min(ATTN_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions are not a multiple of {rows}")
    groups = lambda t, n: t.reshape(b, s, hk, n, d).transpose(
        0, 2, 3, 1, 4).reshape(b * hk, n, s, d)
    qs = groups(mm(u, p["query"]["kernel"]), g)
    ks = groups(mm(u, p["key"]["kernel"]), 1)[:, 0]
    vs = groups(mm(u, p["value"]["kernel"]), 1)[:, 0]
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    @jax.checkpoint
    def one_piece(i):  # [g, rows, D] against the group's [S, D] keys
        group, first = i // (s // rows), (i % (s // rows)) * rows
        qg = jax.lax.dynamic_slice_in_dim(qs[group], first, rows, axis=1)
        scores = mm(qg, ks[group].T) * scale
        mask = jax.lax.dynamic_slice_in_dim(seen, first, rows, axis=0)
        return mm(jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1),
                  vs[group])

    o = jax.lax.map(one_piece, jnp.arange(b * hk * (s // rows)))
    o = o.reshape(b, hk, s // rows, g, rows, d).transpose(0, 2, 4, 1, 3, 5)
    return mm(o.reshape(b, s, h * d), p["attn_out"]["kernel"])


def gated_mlp(mm, u, p):
    """Step 2's MLP for ``u [B, S, F]``, ``MLP_ROWS`` tokens at a time."""
    def some_tokens(t):
        gate, up = jnp.split(mm(t, p["mlp_in"]["kernel"]), 2, axis=-1)
        return mm(jax.nn.silu(gate) * up, p["mlp_out"]["kernel"])

    rows = min(MLP_ROWS, u.shape[0] * u.shape[1])
    out = jax.lax.map(jax.checkpoint(some_tokens),
                      u.reshape(-1, rows, u.shape[-1]))
    return out.reshape(u.shape)


def _layer(mm, x, p, sizes, layer, faults):
    eps, m = sizes["rms_norm_eps"], faults.get(
        "residual_multiplier", sizes["residual_multiplier"])
    u = _rms_norm(x, p["self_attn_norm"], eps)
    kind = sizes["layer_types"][layer]
    if kind == "mamba":
        f = mamba_mixer(mm, u, p["self_attn"], sizes, **{
            k: faults[k] for k in ("carry_state", "decay", "drop_tap",
                                   "gate_after_norm") if k in faults})
    elif kind == "attention":
        f = attention(mm, u, p["self_attn"], sizes, faults.get(
            "attention_multiplier", sizes["attention_multiplier"]))
    else:
        raise ValueError(f"unknown layer type {kind!r}")
    x = x + m * f
    return x + m * gated_mlp(mm, _rms_norm(x, p["mlp_norm"], eps), p["mlp"])


def _by_rows(fn, x, block_rows):
    """``fn`` over blocks of ``block_rows`` rows of ``x``, each recomputed
    in the backward pass: one block's activations are all that is alive."""
    b = x.shape[0]
    block_rows = min(block_rows, b)
    if b % block_rows:
        raise ValueError(f"batch {b} is not a multiple of {block_rows}")
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(
        b // block_rows, block_rows, *x.shape[1:]))
    return out.reshape(x.shape)


def _trunk(mm, params, ids, sizes, block_rows, faults):
    """``ids [B, S]`` -> the last held layer's output ``[B, S, F]``."""
    x = sizes["embedding_multiplier"] * params["token"]["embedding"][ids]
    for layer in sizes["layers_held"]:
        p = params[f"layer_{layer}"]
        x = _by_rows(lambda xb, p=p, layer=layer: _layer(
            mm, xb, p, sizes, layer, faults), x, block_rows)
    return x


def _head_blocks(t):
    rows = min(HEAD_ROWS, t.shape[0] * t.shape[1])
    return t.reshape(-1, rows, *t.shape[2:])


def logits_fn(params, ids, sizes, precision="float32", block_rows=1,
              **faults):
    """``ids [B, S]`` -> logits ``[B, S, V]`` (float32)."""
    mm = _precision.matmul(precision)
    x = _trunk(mm, params, ids, sizes, block_rows, faults)
    x = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
    return mm(x, params["token"]["embedding"].T) / sizes["logits_scaling"]


def loss_fn(params, tokens, sizes, precision="float32", block_rows=1,
            row_share=None, **faults):
    """Mean next-token cross-entropy of ``tokens [B, S+1]``; ``row_share``
    (a control) counts only that share of a row's positions, its first."""
    mm = _precision.matmul(precision)
    x = _trunk(mm, params, tokens[:, :-1], sizes, block_rows, faults)

    def picked(block):  # the head and the loss, a block of tokens at a time
        xb, targets = block
        xb = _rms_norm(xb, params["final_norm"], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(
            mm(xb, params["token"]["embedding"].T) / sizes["logits_scaling"],
            axis=-1)
        return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    logp = jax.lax.map(jax.checkpoint(picked),
                       (_head_blocks(x), _head_blocks(tokens[:, 1:])))
    logp = logp.reshape(x.shape[:2])
    return -jnp.mean(logp if row_share is None
                     else logp[:, :int(row_share * logp.shape[1])])


def learning_rate(count, hp):
    """Linear warm-up from 0, then cosine to ``end_lr_factor`` of the base:
    the rate applied to the update numbered ``count`` (from 0)."""
    base, warm = hp["base_lr"], hp["warmup_steps"]
    decay = max(hp["total_steps"] - warm, 1)
    c = jnp.asarray(count, jnp.float32)
    alpha = hp.get("end_lr_factor", 0.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * jnp.minimum(c - warm, decay) / decay))
    return jnp.where(c < warm, base * c / max(warm, 1),
                     base * ((1.0 - alpha) * cos + alpha))


def _adamw_step(params, mu, nu, count, grads, hp):
    """One update as optax composes it: clip by global norm, Adam moments
    with bias correction, decoupled weight decay on matrices only (the
    convolution's taps are a 2-D ``kernel`` and count as one), all scaled by
    the schedule at ``count``. Returns the clipped gradients' per-leaf norms
    in place of the gradients, which need not outlive it."""
    clip = hp["grad_clip_norm"]
    norms = _leaf_norms(grads)
    norm = jnp.sqrt(sum(jnp.square(n) for n in norms.values()))
    # The clip is one factor for every leaf, applied where a gradient is
    # read: a clipped copy of the gradients is never made.
    scale = jnp.where(norm < clip, 1.0, clip / norm) if clip > 0 else 1.0
    b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
    t = count + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: b1 * m + (1 - b1) * (g * scale), mu, grads)
    nu = jax.tree_util.tree_map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g * scale), nu, grads)
    lr = learning_rate(count, hp)

    def update(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim > 1:
            u = u + wd * p
        return p - lr * u

    return jax.tree_util.tree_map(update, params, mu, nu), mu, nu, \
        {name: n * scale for name, n in norms.items()}


def _leaf_paths(tree):
    """``("layer_0/mlp/mlp_in/kernel", leaf)`` for every leaf."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield "/".join(str(getattr(k, "key", k)) for k in path), leaf


def _leaf_norms(tree) -> Dict[str, Any]:
    return {name: jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for name, leaf in _leaf_paths(tree)}


def train_steps(params, batches: List[Any], sizes: Dict[str, Any],
                hp: Dict[str, float], precision: str = "float32",
                block_rows: int = 1, rng=None, **faults) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from ``params``,
    which this call consumes (see the module's note on memory). ``rng`` is
    accepted for the harness's sake and not read: nothing here is random.
    ``faults`` are the controls, each one thing done wrongly: ``row_share``
    (only that share of a row's positions counts in the loss),
    ``carry_state=False``,
    ``decay=False``, ``drop_tap`` (a tap's index), ``gate_after_norm=True``
    (:func:`mamba_mixer`), ``residual_multiplier`` and
    ``attention_multiplier`` (another value than the configuration's).

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (after clipping), and the norm of each leaf's
    change over all the steps."""
    del rng

    # Two programs, and Adam's moments on the host while the first runs:
    # the gradient's program holds the weights, the gradient and a layer's
    # working set (10 GB), the update's the weights, the gradient and both
    # moments (12.4 GB) and nothing else. In one program all of it is alive
    # in the backward pass, and 16.9 GB do not hold it (memory only).
    gradient = jax.jit(lambda params, tokens: jax.value_and_grad(loss_fn)(
        params, tokens, sizes, precision, block_rows, **faults))
    update = jax.jit(
        lambda params, mu, nu, count, grads: _adamw_step(
            params, mu, nu, count, grads, hp),
        donate_argnums=(0, 1, 2))
    start = jax.device_get(params)
    mu = nu = jax.tree_util.tree_map(np.zeros_like, start)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        loss, grads = gradient(params, jnp.asarray(tokens))
        params, mu, nu, norms = update(
            params, jax.device_put(mu), jax.device_put(nu),
            jnp.asarray(i, jnp.int32), grads)
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in norms.items()}
        if i + 1 < len(batches):
            mu, nu = jax.device_get((mu, nu))
    del mu, nu
    change = jax.jit(lambda new, old: jnp.sqrt(jnp.sum(jnp.square(new - old))))
    moved = {name: float(change(new, old)) for (name, new), (_, old)
             in zip(_leaf_paths(params), _leaf_paths(start))}
    return {"loss": losses, "grad_norms": first, "change_norms": moved}
