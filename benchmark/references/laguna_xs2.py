"""Plain reference for the ``laguna_xs2`` configuration: one chip's share of
poolside's Laguna-XS.2 in straightforward ``jax.numpy`` and float32
(``Precision.HIGHEST``), its loss, its gradients and the AdamW step the
configuration states. No kernels, no sorting, no grouped matmul, nothing
imported from the program.

**The layer equations** (``h`` is a block's input ``[T, 2048]``; ``H_l`` is
``num_attention_heads_per_layer[l]``, 48 in the full-attention layers and 64
in the sliding ones; 8 K/V heads; head size 128; no bias anywhere):

- ``a = RMSNorm(h)``; ``q = a Wq`` ``[T, H_l, 128]``, ``k = a Wk``,
  ``v = a Wv`` ``[T, 8, 128]``. Rotary positions in the two-halves layout
  (dimension ``i`` pairs with ``i + rot/2``): full layers turn the first 64
  of each head's 128 dimensions with YaRN's frequencies (theta 500,000,
  factor 64, original length 4096, beta_fast 64, beta_slow 1) and multiply
  cos and sin by ``attention_factor`` 1.41589; sliding layers turn all 128
  with theta 10,000. Query head ``i`` reads K/V head ``i // (H_l / 8)``.
  ``p = softmax(q k^T / sqrt(128) + mask)``, the mask causal and in sliding
  layers also ``i - j < 512``. ``o = p v``; ``g = sigmoid(a Wg)``
  ``[T, H_l]``; ``h <- h + concat(g * o) Wo``.
- ``m = RMSNorm(h)``. Layer 0: ``h <- h + (silu(m W1) * m W3) W2``, width
  8192. Layers >= 1: ``s = sigmoid(m Wr)`` ``[T, 256]``; ``S`` = the 8
  largest; ``w_e = 2.5 s_e / sum over S of s``;
  ``h <- h + sum over e in S that are held of w_e E_e(m) + E_shared(m)``,
  every ``E`` the same gated MLP at width 512.
- After the last layer held: RMSNorm, ``logits = x W_head`` over the
  vocabulary rows held; the loss is the mean next-token cross-entropy.

**The chip's share** (``benchmark/configs/laguna_xs2.json``): layers
``layers_held`` of the 40, the ``num_experts`` experts from ``experts_held``
on of the 256 the router scores, ``vocab_size`` rows of the embedding and of
the head. What the absent experts would add is left out here as in the
program, and that partial result goes on to the next layer.

**Read into the source** (``config.json`` does not say, and the ``laguna``
modelling code is not on this machine; the configuration file lists each
under ``assumed`` with the reading it was chosen over): ``gating: true`` is
one sigmoid gate a head computed from the block's normed input; scores by
sigmoid, the chosen ones normalised and then scaled by 2.5; the shared expert
ungated; the activation silu; no query or key norm; no auxiliary loss;
RMSNorm's epsilon inside the root; AdamW(0.9, 0.95), weight decay 0.1 on
matrices, clip 1.0, the ``gpt_small_lm`` schedule.

Parameters arrive as the nested dict the program's own tree has
(``token/embedding``, ``layer_<i>/self_attn/{query,key,value,gate,attn_out}
/kernel``, ``layer_<i>/mlp/...``, ``final_norm/scale``, ``lm_head/kernel``):
``mlp_in`` holds ``W1 | W3`` side by side; an expert stack is one 2-D matrix
``[experts * d_in, d_out]``. A name looked up and not found is an error.

Memory: 692 M parameters in float32 with Adam's two moments and a gradient
are 11.1 GB of the chip's 16, so every layer runs under ``jax.checkpoint``
one block of ``block_rows`` rows at a time, attention one K/V head's group
at a time, and ``train_steps`` consumes ``params``: it keeps the starting
values on the host and gives the device buffers to the first step.
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


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def rope_tables(seq_len: int, head_dim: int, rope: Dict[str, Any]):
    """``(cos, sin, rot)``: float32 ``[seq_len, rot / 2]`` tables of one
    entry of the source's ``rope_parameters``, and how many of a head's
    dimensions turn."""
    rot = int(head_dim * rope["partial_rotary_factor"])
    pos_freqs = rope["rope_theta"] ** (np.arange(0, rot, 2, dtype=np.float64)
                                       / rot)
    inv_freq, scale = 1.0 / pos_freqs, 1.0
    if rope["rope_type"] == "yarn":
        def turns_dim(turns):
            return rot * math.log(rope["original_max_position_embeddings"]
                                  / (turns * 2 * math.pi)) \
                / (2 * math.log(rope["rope_theta"]))

        low = max(math.floor(turns_dim(rope["beta_fast"])), 0)
        high = min(math.ceil(turns_dim(rope["beta_slow"])), rot - 1)
        ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3),
                       0.0, 1.0)
        inv_freq = inv_freq / rope["factor"] * ramp + inv_freq * (1.0 - ramp)
        scale = rope["attention_factor"]
    elif rope["rope_type"] != "default":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None]
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32), rot)


def _rotate(x, cos, sin, rot):
    """``x [B, S, H, D]`` turned by its positions."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _attention(mm, a, p, sizes, layer):
    b, s, _ = a.shape
    d, hk = sizes["head_dim"], sizes["num_key_value_heads"]
    h = sizes["num_attention_heads_per_layer"][layer]
    kind = sizes["layer_types"][layer]
    q = mm(a, p["query"]["kernel"]).reshape(b, s, h, d)
    k = mm(a, p["key"]["kernel"]).reshape(b, s, hk, d)
    v = mm(a, p["value"]["kernel"]).reshape(b, s, hk, d)
    cos, sin, rot = rope_tables(s, d, sizes["rope_parameters"][kind])
    q, k = _rotate(q, cos, sin, rot), _rotate(k, cos, sin, rot)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= i
    if kind == "sliding_attention":
        seen = seen & (i - j < sizes["sliding_window"])
    elif kind != "full_attention":
        raise ValueError(f"unknown layer type {kind!r}")

    # One K/V head's query heads at a time, ATTN_ROWS of their rows at a
    # time, each recomputed in the backward pass: a whole layer's scores in
    # float32 would be 8.6 GB.
    g, rows = h // hk, min(ATTN_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions are not a multiple of {rows}")
    groups = lambda t, n: t.transpose(0, 2, 1, 3).reshape(b * hk, n, s, d)
    qs, ks, vs = groups(q, g), groups(k, 1)[:, 0], groups(v, 1)[:, 0]

    @jax.checkpoint
    def one_piece(i):  # [g, rows, D] against the group's [S, D] keys
        group, first = i // (s // rows), (i % (s // rows)) * rows
        qg = jax.lax.dynamic_slice_in_dim(qs[group], first, rows, axis=1)
        scores = mm(qg, ks[group].T) / math.sqrt(d)
        mask = jax.lax.dynamic_slice_in_dim(seen, first, rows, axis=0)
        return mm(jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1),
                  vs[group])

    o = jax.lax.map(one_piece, jnp.arange(b * hk * (s // rows)))
    o = o.reshape(b * hk, s // rows, g, rows, d).transpose(0, 2, 1, 3, 4)
    o = o.reshape(b, h, s, d).transpose(0, 2, 1, 3)         # [B, S, H, D]
    gate = jax.nn.sigmoid(mm(a, p["gate"]["kernel"]))       # [B, S, H]
    return mm((o * gate[..., None]).reshape(b, s, h * d),
              p["attn_out"]["kernel"])


def _gated_mlp(mm, x, w_in, w_out):
    gate, up = jnp.split(mm(x, w_in), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w_out)


MOE_ROWS = 1024   # tokens an expert layer takes at a time (memory only)
ATTN_ROWS = 1024  # query rows a group of heads takes at a time (memory only)


def moe_layer(mm, m, p, sizes, experts=None, shared=True):
    """The expert layer's result for ``m [T, F]``: every held expert run over
    every token, weighted by what the router gave it (0 where it was not
    among the token's chosen). ``experts`` computes only the first so many of
    the experts held (the control: a step that leaves one out); ``shared``
    False leaves the shared expert out."""
    first, held = sizes["experts_held"][0], sizes["num_experts"]
    n = held if experts is None else experts
    f = m.shape[-1]
    w_in = p["experts_in"]["kernel"].reshape(held, f, -1)[:n]
    w_out = p["experts_out"]["kernel"].reshape(held, -1, f)[:n]

    def some_tokens(m):
        scores = jax.nn.sigmoid(mm(m, p["router"]["kernel"]))
        top, chosen = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
        weight = sizes["moe_routed_scaling_factor"] * top \
            / jnp.sum(top, axis=-1, keepdims=True)
        # [tokens, experts]: a token's weight for each, 0 where not chosen.
        dense = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1])
                        * weight[..., None], axis=-2)

        def add_expert(out, expert):  # one compiled body for all of them
            w1, w2, weight_e = expert
            return out + weight_e[:, None] * _gated_mlp(mm, m, w1, w2), None

        out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m),
                              (w_in, w_out, dense[:, first:first + n].T))
        if shared:
            out = out + _gated_mlp(mm, m, p["shared"]["mlp_in"]["kernel"],
                                   p["shared"]["mlp_out"]["kernel"])
        return out

    rows = min(MOE_ROWS, m.shape[0])
    if m.shape[0] % rows:
        raise ValueError(f"{m.shape[0]} tokens are not a multiple of {rows}")
    return jax.lax.map(jax.checkpoint(some_tokens),
                       m.reshape(-1, rows, f)).reshape(m.shape)


def _layer(mm, x, p, sizes, layer, experts):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(mm, _rms_norm(x, p["self_attn_norm"], eps),
                       p["self_attn"], sizes, layer)
    m = _rms_norm(x, p["mlp_norm"], eps)
    kind = sizes["mlp_layer_types"][layer]
    if kind == "dense":
        return x + _gated_mlp(mm, m, p["mlp"]["mlp_in"]["kernel"],
                              p["mlp"]["mlp_out"]["kernel"])
    if kind != "sparse":
        raise ValueError(f"unknown mlp layer type {kind!r}")
    b, s, f = m.shape
    return x + moe_layer(mm, m.reshape(b * s, f), p["mlp"], sizes,
                         experts).reshape(b, s, f)


def _by_rows(fn, x, block_rows):
    """``fn`` over blocks of ``block_rows`` rows of ``x``, each recomputed in
    the backward pass: one block's activations are all that is alive."""
    b = x.shape[0]
    block_rows = min(block_rows, b)
    if b % block_rows:
        raise ValueError(f"batch {b} is not a multiple of {block_rows}")
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape(b // block_rows, block_rows, *x.shape[1:]))
    return out.reshape(b, *out.shape[2:])


def _trunk(mm, params, ids, sizes, block_rows, experts):
    """``ids [B, S]`` -> the last held layer's output ``[B, S, F]``."""
    x = params["token"]["embedding"][ids]
    for layer in sizes["layers_held"]:
        p = params[f"layer_{layer}"]
        x = _by_rows(lambda xb, p=p, layer=layer: _layer(
            mm, xb, p, sizes, layer, experts), x, block_rows)
    return x


def logits_fn(params, ids, sizes, precision="float32", block_rows=1,
              experts=None):
    """``ids [B, S]`` -> logits ``[B, S, V]`` (float32)."""
    mm = _precision.matmul(precision)
    x = _trunk(mm, params, ids, sizes, block_rows, experts)
    x = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
    return mm(x, params["lm_head"]["kernel"])


def loss_fn(params, tokens, sizes, precision="float32", block_rows=1,
            experts=None):
    """Mean next-token cross-entropy of ``tokens [B, S+1]``."""
    mm = _precision.matmul(precision)
    x = _trunk(mm, params, tokens[:, :-1], sizes, block_rows, experts)

    def picked(block):  # the head and the loss, a block of rows at a time
        xb, targets = block
        xb = _rms_norm(xb, params["final_norm"], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(xb, params["lm_head"]["kernel"]), axis=-1)
        return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    b = x.shape[0]
    rows = min(block_rows, b)
    in_blocks = lambda t: t.reshape(b // rows, rows, *t.shape[1:])
    logp = jax.lax.map(jax.checkpoint(picked),
                       (in_blocks(x), in_blocks(tokens[:, 1:])))
    return -jnp.mean(logp)


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
    with bias correction, decoupled weight decay on matrices only, all
    scaled by the schedule at ``count``. Returns the clipped gradients'
    per-leaf norms in place of the gradients, which need not outlive it."""
    clip = hp["grad_clip_norm"]
    norms = _leaf_norms(grads)
    norm = jnp.sqrt(sum(jnp.square(n) for n in norms.values()))
    # The clip is one factor for every leaf, applied where a gradient is
    # read: a clipped copy of 692 M gradients is never made.
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
                block_rows: int = 1, rng=None, rows: int = None,
                experts: int = None) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from ``params``,
    which this call consumes (see the module's note on memory). ``rng`` is
    accepted for the harness's sake and not read: nothing here is random.
    ``rows`` and ``experts`` are the controls: only the first ``rows`` rows
    of each batch count; only the first ``experts`` of the experts held are
    computed.

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (after clipping), and the norm of each leaf's
    change over all the steps."""
    del rng

    def step(params, mu, nu, count, tokens):
        if rows is not None:
            tokens = tokens[:rows]
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, sizes, precision, block_rows, experts)
        return (*_adamw_step(params, mu, nu, count, grads, hp), loss)

    step = jax.jit(step, donate_argnums=(0, 1, 2))
    start = jax.device_get(params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        params, mu, nu, norms, loss = step(
            params, mu, nu, jnp.asarray(i, jnp.int32), jnp.asarray(tokens))
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in norms.items()}
    del mu, nu
    change = jax.jit(lambda new, old: jnp.sqrt(jnp.sum(jnp.square(new - old))))
    moved = {name: float(change(new, old)) for (name, new), (_, old)
             in zip(_leaf_paths(params), _leaf_paths(start))}
    return {"loss": losses, "grad_norms": first, "change_norms": moved}
