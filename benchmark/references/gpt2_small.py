"""Plain reference for the ``gpt2_small`` configuration: GPT-2's decoder in
straightforward ``jax.numpy`` and float32, its loss, its gradients and the
AdamW step that the configuration states. No kernels, no sharding, nothing
imported from the program.

It follows Radford et al. 2019 as the repository's ``gpt_small_lm`` preset
realises it, and these are the preset's departures from the paper (the
configuration file lists them under ``assumed``): a LayerNorm right after
the embedding sum, LayerNorm epsilon 1e-6, Xavier-uniform kernels, dropout
on the embedding and on the two residual branches and none on the attention
weights.

Dropout is what the published recipe trains with, so the timed step has it
on, and the reference has to drop the same elements. It does not ask the
program for its masks: it computes them from the step's key, which the
benchmark makes and hands to both sides, by the rule that flax documents for
``make_rng`` (the key folded with the SHA-1 of the site's path and call
count) and jax's ``bernoulli``. The sites' paths are data in the
configuration file (``dropout_streams``). A program that draws its masks
another way no longer matches, and the comparison says so.

Parameters arrive as the nested dict the program's own tree has
(``token/embedding``, ``position``, ``embed_norm``, ``layer_<i>/...``,
``final_norm``): the benchmark makes the values from the seed and hands the
same ones to both sides. A name this file looks up and does not find is an
error, not a default.
"""

from __future__ import annotations

import hashlib
import math
import os
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import precision as _precision  # noqa: E402  (sibling file, no package)

LN_EPS = 1e-6


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(mm, x, p):
    return mm(x, p["kernel"]) + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def site_key(step_key, path, call: int = 1, separator: bool = False):
    """The key of one dropout site in one step: ``step_key`` folded with the
    first four bytes of the SHA-1 of the site's module path and the number
    of the ``make_rng`` call there (flax's ``LazyRng``)."""
    m = hashlib.sha1()
    for part in list(path) + [call]:
        if separator:
            m.update(b"\00")
        m.update(part.encode("utf-8") if isinstance(part, str)
                 else part.to_bytes((part.bit_length() + 7) // 8, "big"))
    word = int.from_bytes(m.digest()[:4], "big")
    return jax.random.fold_in(step_key, jnp.uint32(word))


def dropout_masks(step_key, sizes, shape) -> Dict[str, Any]:
    """Keep-masks ``[B, S, F]`` of every dropout site of one step, by site
    (``embd``, ``attn_<i>``, ``mlp_<i>``); empty where the rates are 0."""
    streams = sizes["dropout_streams"]
    sites = {"embd": (streams["embd"], sizes["embd_pdrop"])}
    for i in range(sizes["n_layer"]):
        for kind in ("attn", "mlp"):
            sites[f"{kind}_{i}"] = (
                [part.format(i=i) for part in streams[kind]],
                sizes["resid_pdrop"])
    return {
        name: jax.random.bernoulli(
            site_key(step_key, path, streams["call"], streams["separator"]),
            1.0 - rate, shape)
        for name, (path, rate) in sites.items() if rate > 0}


def _drop(x, masks, name, rate):
    if name not in masks:
        return x
    return jnp.where(masks[name], x / (1.0 - rate), 0.0)


def _attention(mm, x, p, num_heads):
    b, s, f = x.shape
    d = f // num_heads

    def heads(t):  # [B,S,F] -> [B,H,S,D]
        return t.reshape(b, s, num_heads, d).transpose(0, 2, 1, 3)

    q, k, v = (heads(_dense(mm, x, p[n])) for n in ("query", "key", "value"))
    scores = mm(q, k.transpose(0, 1, 3, 2)) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -1e30)
    out = mm(jax.nn.softmax(scores, axis=-1), v)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, f)
    return _dense(mm, out, p["attn_out"])


def logits_fn(params, ids, sizes, precision="float32", masks=None):
    """``ids [B, S]`` -> logits ``[B, S, V]`` (float32). ``masks`` are the
    keep-masks of a training step (``dropout_masks``); none is evaluation."""
    mm = _precision.matmul(precision)
    masks = masks or {}
    embd, resid = sizes["embd_pdrop"], sizes["resid_pdrop"]
    s = ids.shape[1]
    x = params["token"]["embedding"][ids] + params["position"][None, :s]
    x = _drop(_layer_norm(x, params["embed_norm"]), masks, "embd", embd)
    for i in range(sizes["n_layer"]):
        p = params[f"layer_{i}"]
        a = _attention(mm, _layer_norm(x, p["self_attn_norm"]),
                       p["self_attn"], sizes["n_head"])
        x = x + _drop(a, masks, f"attn_{i}", resid)
        h = _dense(mm, _layer_norm(x, p["mlp_norm"]), p["mlp"]["mlp_in"])
        h = _dense(mm, _gelu_tanh(h), p["mlp"]["mlp_out"])
        x = x + _drop(h, masks, f"mlp_{i}", resid)
    x = _layer_norm(x, params["final_norm"])
    return mm(x, params["token"]["embedding"].T)


def loss_fn(params, tokens, sizes, precision="float32", masks=None):
    """Mean next-token cross-entropy of ``tokens [B, S+1]``."""
    logits = logits_fn(params, tokens[:, :-1], sizes, precision, masks)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def _loss_and_grads(params, tokens, masks, sizes, precision, block_rows):
    """Loss and gradients of the whole batch, summed over blocks of rows so
    that one block's float32 activations are all that is alive. ``masks``
    are the whole batch's, and are cut into the same blocks."""
    b = tokens.shape[0]
    block_rows = min(block_rows, b)
    if b % block_rows:
        raise ValueError(f"batch {b} is not a multiple of {block_rows}")
    in_blocks = lambda t: t.reshape(b // block_rows, block_rows, *t.shape[1:])
    blocks = in_blocks(tokens)
    grad = jax.value_and_grad(
        lambda p, t, m: loss_fn(p, t, sizes, precision, m))

    def body(carry, block):
        loss_sum, g_sum = carry
        loss, g = grad(params, *block)
        return (loss_sum + loss,
                jax.tree_util.tree_map(jnp.add, g_sum, g)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss_sum, g_sum), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zero),
        (blocks, jax.tree_util.tree_map(in_blocks, masks)))
    n = blocks.shape[0]
    return loss_sum / n, jax.tree_util.tree_map(lambda g: g / n, g_sum)


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


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(tree)))


def _adamw_step(params, mu, nu, count, grads, hp):
    """One update as optax composes it: clip by global norm, Adam moments
    with bias correction, decoupled weight decay on matrices only, all
    scaled by the schedule at ``count``."""
    clip = hp["grad_clip_norm"]
    norm = _global_norm(grads)
    if clip > 0:
        grads = jax.tree_util.tree_map(
            lambda g: jnp.where(norm < clip, g, g / norm * clip), grads)
    b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
    t = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                nu, grads)
    lr = learning_rate(count, hp)

    def update(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim > 1:
            u = u + wd * p
        return p - lr * u

    return jax.tree_util.tree_map(update, params, mu, nu), mu, nu, grads


def _leaf_norms(tree) -> Dict[str, Any]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
    return out


def train_steps(params, batches: List[Any], sizes: Dict[str, Any],
                hp: Dict[str, float], precision: str = "float32",
                block_rows: int = 2, rng=None, rows: int = None
                ) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from ``params``.
    ``rng`` is the key the trainer was given: step ``i`` drops with
    ``fold_in(rng, i)``. It may be left out only where the rates are 0.
    ``rows`` is for the fault of a part of the batch left out: only the
    first ``rows`` rows of each batch count, under the masks they had.

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (after clipping), and the norm of each leaf's
    change over all the steps."""
    if rng is None:
        if max(sizes["embd_pdrop"], sizes["resid_pdrop"]) > 0:
            raise ValueError("the configuration trains with dropout: the "
                             "reference needs the trainer's key")
        rng = jax.random.PRNGKey(0)

    @jax.jit
    def step(params, mu, nu, count, tokens, rng):
        b, s = tokens.shape[0], tokens.shape[1] - 1
        masks = dropout_masks(jax.random.fold_in(rng, count), sizes,
                              (b, s, sizes["n_embd"]))
        if rows is not None:
            tokens = tokens[:rows]
            masks = {k: m[:rows] for k, m in masks.items()}
        loss, grads = _loss_and_grads(params, tokens, masks, sizes,
                                      precision, block_rows)
        new, mu, nu, clipped = _adamw_step(params, mu, nu, count, grads, hp)
        return new, mu, nu, loss, _leaf_norms(clipped)

    @jax.jit
    def change(new, old):
        return _leaf_norms(jax.tree_util.tree_map(jnp.subtract, new, old))

    start = params
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        params, mu, nu, loss, norms = step(
            params, mu, nu, jnp.asarray(i, jnp.int32), jnp.asarray(tokens),
            rng)
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in norms.items()}
    moved = {k: float(v) for k, v in change(params, start).items()}
    return {"loss": losses, "grad_norms": first, "change_norms": moved}
