"""Plain reference for the ``mellum2_12b`` configuration: one four-chip host's
share of JetBrains' Mellum2-12B-A2.5B, **the whole four layers with all 64
experts of each and no ranks**, in straightforward ``jax.numpy`` and float32
(``Precision.HIGHEST``), its loss, its gradients and the AdamW step the
configuration states. No kernels, no sorting, no grouped matmul, no
``shard_map``, no collective, nothing imported from the program.

**The layer equations** (``h`` is a block's input ``[T, 2304]``; 32 query
heads over 4 K/V heads of 128; no bias anywhere):

- ``a = RMSNorm(h)``; ``q = a Wq`` ``[T, 32, 128]``, ``k = a Wk``,
  ``v = a Wv`` ``[T, 4, 128]``. Rotary positions on the whole head in the
  two-halves layout (dimension ``i`` pairs with ``i + 64``), theta 500,000;
  the full-attention layers (index 3, 7, ...) with YaRN's frequencies (factor
  16, original length 8192, beta_fast 32, beta_slow 1) and cos and sin
  multiplied by ``attention_factor`` 1.27726. Query head ``i`` reads K/V head
  ``i // 8``. ``p = softmax(q k^T / sqrt(128) + mask)``, the mask causal and
  in sliding layers also ``i - j < 1024``. ``h <- h + concat(p v) Wo``.
- ``m = RMSNorm(h)``; ``p = softmax(m Wr)`` ``[T, 64]``; ``S`` = the 8 largest;
  ``w_e = p_e / sum over S of p``; ``h <- h + sum over e in S of w_e E_e(m)``,
  ``E_e(m) = (silu(m W1_e) * m W3_e) W2_e`` at width 896. No shared expert.
- After layer 3: RMSNorm, ``logits = x W_head`` over the vocabulary rows
  held; the loss is the mean next-token cross-entropy.

**The host's share** (``benchmark/configs/mellum2_12b.json``): layers
``layers_held`` of the 28 and ``vocab_size`` rows of the embedding and of the
head. No expert is left out.

**Read into the source** (the ``mellum`` modelling code is not on this
machine; the configuration file lists each under ``assumed`` with the reading
it was chosen over): softmax scores normalised over the chosen; no query or
key norm; no router bias; no auxiliary loss; no multi-token-prediction head;
RMSNorm's epsilon inside the root; AdamW(0.9, 0.95), weight decay 0.1 on
matrices, clip 1.0, the ``gpt_small_lm`` schedule.

Parameters arrive as the nested dict the program's own tree has
(``token/embedding``, ``layer_<i>/self_attn/{query,key,value,attn_out}
/kernel``, ``layer_<i>/mlp/{router,experts_in,experts_out}/kernel``,
``final_norm/scale``, ``lm_head/kernel``): ``experts_in`` holds ``W1 | W3``
side by side; an expert stack is one 2-D matrix ``[64 * d_in, d_out]``, an
expert's rows after an expert's. A name looked up and not found is an error.

Memory: 1.784 B parameters in float32 with Adam's two moments and a gradient
are 28.5 GB, more than a chip holds. Where the process sees
``expert_parallel_ranks`` devices, ``train_steps`` places the expert stacks
(and their moments) over them on their rows with ``jax.device_put`` and a
``NamedSharding``, 16 experts a device, the embedding and the head a quarter
of the vocabulary a device, and everything else whole on each; the
activations are whole on each too, and the partitioner does the rest. The
expert layer is written for that: it takes the ``j``-th expert of each of the
``expert_parallel_ranks`` groups of 16 side by side, sixteen times, and sums
what they add; a sum over all 64 experts in another order. Every layer runs
under ``jax.checkpoint`` one block of ``block_rows`` rows at a time, attention
one K/V head's group at a time, and ``train_steps`` consumes ``params``: it
keeps the starting values on the host and deletes the buffers it was given.
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
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
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
    h = sizes["num_attention_heads"]
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
    # float32 would be 34 GB.
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
    return mm(o.reshape(b, s, h * d), p["attn_out"]["kernel"])


def _gated_mlp(mm, x, w_in, w_out):
    gate, up = jnp.split(mm(x, w_in), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w_out)


MOE_ROWS = 1024   # tokens an expert layer takes at a time (memory only)
ATTN_ROWS = 1024  # query rows a group of heads takes at a time (memory only)
HEAD_ROWS = 2048  # positions the head and the loss take at a time (memory only)


def route(mm, m, p, sizes):
    """``(chosen [T, k], weight [T, k])``: softmax over all the experts, the
    ``k`` largest, normalised over the chosen."""
    probs = jax.nn.softmax(mm(m, p["router"]["kernel"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


def moe_layer(mm, m, p, sizes, groups_out=()):
    """The expert layer's result for ``m [T, F]``: every expert run over
    every token, weighted by what the router gave it (0 where it was not
    among the token's chosen). The 64 experts are taken as
    ``expert_parallel_ranks`` groups of consecutive experts, the ``j``-th of
    every group side by side. ``groups_out`` is the control: the groups whose
    parts are left out of the sum (a rank of the program that adds
    nothing)."""
    e, f = sizes["num_experts"], m.shape[-1]
    g = sizes.get("expert_parallel_ranks", 1)
    by_turn = lambda w: w.reshape(g, e // g, *w.shape[1:]).swapaxes(0, 1)
    w_in = by_turn(p["experts_in"]["kernel"].reshape(e, f, -1))
    w_out = by_turn(p["experts_out"]["kernel"].reshape(e, -1, f))
    keep = jnp.asarray([0.0 if i in groups_out else 1.0 for i in range(g)])

    def some_tokens(m):
        chosen, weight = route(mm, m, p, sizes)
        # [tokens, experts]: a token's weight for each, 0 where not chosen.
        dense = jnp.sum(jax.nn.one_hot(chosen, e) * weight[..., None],
                        axis=-2)
        dense = dense.reshape(-1, g, e // g) * keep[None, :, None]
        side = jnp.broadcast_to(m, (g, *m.shape))

        def add_experts(out, turn):  # one compiled body for all of them
            w1, w2, weight_e = turn   # [g, F, 2W], [g, W, F], [T, g]
            parts = _gated_mlp(mm, side, w1, w2)            # [g, T, F]
            return out + jnp.sum(parts * weight_e.T[..., None], axis=0), None

        out, _ = jax.lax.scan(add_experts, jnp.zeros_like(m),
                              (w_in, w_out, dense.transpose(2, 0, 1)))
        return out

    rows = min(MOE_ROWS, m.shape[0])
    if m.shape[0] % rows:
        raise ValueError(f"{m.shape[0]} tokens are not a multiple of {rows}")
    return jax.lax.map(jax.checkpoint(some_tokens),
                       m.reshape(-1, rows, f)).reshape(m.shape)


def _layer(mm, x, p, sizes, layer, groups_out):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(mm, _rms_norm(x, p["self_attn_norm"], eps),
                       p["self_attn"], sizes, layer)
    m = _rms_norm(x, p["mlp_norm"], eps)
    kind = sizes["mlp_layer_types"][layer]
    if kind != "sparse":
        raise ValueError(f"unknown mlp layer type {kind!r}")
    b, s, f = m.shape
    return x + moe_layer(mm, m.reshape(b * s, f), p["mlp"], sizes,
                         groups_out).reshape(b, s, f)


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


def _trunk(mm, params, ids, sizes, block_rows, groups_out):
    """``ids [B, S]`` -> the last held layer's output ``[B, S, F]``."""
    x = params["token"]["embedding"][ids]
    for layer in sizes["layers_held"]:
        p = params[f"layer_{layer}"]
        x = _by_rows(lambda xb, p=p, layer=layer: _layer(
            mm, xb, p, sizes, layer, groups_out), x, block_rows)
    return x


def logits_fn(params, ids, sizes, precision="float32", block_rows=1,
              groups_out=()):
    """``ids [B, S]`` -> logits ``[B, S, V]`` (float32)."""
    mm = _precision.matmul(precision)
    x = _trunk(mm, params, ids, sizes, block_rows, groups_out)
    x = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
    return mm(x, params["lm_head"]["kernel"])


def loss_fn(params, tokens, sizes, precision="float32", block_rows=1,
            groups_out=()):
    """Mean next-token cross-entropy of ``tokens [B, S+1]``."""
    mm = _precision.matmul(precision)
    x = _trunk(mm, params, tokens[:, :-1], sizes, block_rows, groups_out)

    def picked(block):  # the head and the loss, a block of rows at a time
        xb, targets = block
        xb = _rms_norm(xb, params["final_norm"], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(xb, params["lm_head"]["kernel"]), axis=-1)
        return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    # HEAD_ROWS positions of a sequence at a time: one sequence's float32
    # logits, their log-softmax and its gradient are 2.4 GB.
    b, s = tokens.shape[0], tokens.shape[1] - 1
    rows = min(HEAD_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions are not a multiple of {rows}")
    in_blocks = lambda t: t.reshape(b * (s // rows), rows, *t.shape[2:])
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
    # read: a clipped copy of 1.78 B gradients is never made.
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


def state_shardings(params, sizes, devices=None):
    """Where ``train_steps`` puts each leaf: the expert stacks over
    ``expert_parallel_ranks`` devices on their rows (16 experts a device),
    the embedding on its rows and the head on its columns (a quarter of the
    vocabulary a device: with them whole the step reads 15.1 of a chip's
    15.75 GiB by the chip's compiler, 13.8 so), everything else whole on
    each of them; ``None`` where the process sees fewer devices (one device
    holds it all: the tiny sizes of a test)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    ranks = sizes.get("expert_parallel_ranks", 1)
    devices = jax.devices() if devices is None else devices
    if ranks < 2 or len(devices) < ranks:
        return None
    mesh = Mesh(np.asarray(devices[:ranks]), ("ranks",))

    def spec(name):
        if "/experts_" in name or name == "token/embedding":
            return P("ranks")
        return P(None, "ranks") if name == "lm_head/kernel" else P()

    return {name: NamedSharding(mesh, spec(name))
            for name, _ in _leaf_paths(params)}


def train_steps(params, batches: List[Any], sizes: Dict[str, Any],
                hp: Dict[str, float], precision: str = "float32",
                block_rows: int = 1, rng=None, rows: int = None,
                groups_out=()) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from ``params``,
    which this call consumes (see the module's note on memory). ``rng`` is
    accepted for the harness's sake and not read: nothing here is random.
    ``rows`` and ``groups_out`` are the controls: only the first ``rows``
    rows of each batch count; the groups of experts named add nothing.

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (after clipping), and the norm of each leaf's
    change over all the steps."""
    del rng

    def step(params, mu, nu, count, tokens):
        if rows is not None:
            tokens = tokens[:rows]
        loss, grads = jax.value_and_grad(loss_fn)(
            params, tokens, sizes, precision, block_rows, tuple(groups_out))
        return (*_adamw_step(params, mu, nu, count, grads, hp), loss)

    start = jax.device_get(params)
    placed = state_shardings(params, sizes)
    if placed is None:
        step = jax.jit(step, donate_argnums=(0, 1, 2))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    else:
        given, treedef = jax.tree_util.tree_flatten(params)
        where = jax.tree_util.tree_unflatten(treedef, list(placed.values()))
        params = jax.device_put(start, where)
        for leaf in given:   # one device held them all
            leaf.delete()
        step = jax.jit(step, donate_argnums=(0, 1, 2),
                       out_shardings=(where, where, where, None, None))
        zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p),
                        out_shardings=where)
    mu, nu = zeros(params), zeros(params)
    losses, first = [], None
    for i, tokens in enumerate(batches):
        params, mu, nu, norms, loss = step(
            params, mu, nu, jnp.asarray(i, jnp.int32), np.asarray(tokens))
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in norms.items()}
    del mu, nu
    change = jax.jit(lambda new, old: jnp.sqrt(jnp.sum(jnp.square(new - old))))
    moved = {name: float(change(new, old)) for (name, new), (_, old)
             in zip(_leaf_paths(params), _leaf_paths(start))}
    return {"loss": losses, "grad_norms": first, "change_norms": moved}
