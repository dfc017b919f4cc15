"""Plain reference for the ``sdar_30b_a3b`` configuration: one chip's share
of JetLM's SDAR-30B-A3B-Chat trained as a block-diffusion model, in
straightforward ``jax.numpy`` and float32 (``Precision.HIGHEST``): its noise,
its loss, its gradients and the AdamW step the configuration states. No
kernels, no sorting, no grouped matmul, nothing imported from the program:
**the mask is built from its definition over all ``2 L`` positions**.

**The layer equations** (``h`` a block's input ``[T, 2048]``; 32 query heads
over 4 K/V heads of 128; no bias anywhere; ``n`` is RMSNorm with eps 1e-6
inside the root):

- ``a = n(h)``; ``q = a Wq`` ``[T, 32, 128]``, ``k = a Wk``, ``v = a Wv``
  ``[T, 4, 128]``; ``q <- n_q(q)``, ``k <- n_k(k)`` a head over its 128
  channels, a learned scale of 128 each; rotary positions on the whole head
  in the two-halves layout (dimension ``i`` pairs with ``i + 64``), theta
  1e6, **at each position's id**; query head ``i`` reads K/V head ``i // 8``;
  ``o = softmax(q k^T / sqrt(128) + M) v``; ``h <- h + o Wo``.
- ``m = n(h)``; ``p = softmax(m Wr)`` ``[T, 128]``; ``S`` = the 8 largest;
  ``w_e = p_e / sum over S of p``; ``h <- h + sum over e in S held here of
  w_e E_e(m)``, ``E_e(m) = (silu(m W1_e) * m W3_e) W2_e`` at width 768. No
  shared expert, no auxiliary loss. An expert that is not held adds nothing
  (it lies on another of the 8 chips that share the layer).
- After the last layer held: RMSNorm, ``logits = x W_head`` over the
  vocabulary rows held.

**The step** (``noise`` in the configuration's file). For a row ``x`` of
``L`` tokens in blocks of ``b``, with the step's key ``fold_in(rng, step)``:
``k_rate, k_mask = split(key)``; ``u = uniform(k_rate, [rows])``; ``t = eps +
(1 - eps) u``; token ``i`` is masked where ``uniform(k_mask, [rows, L])[i] <
t``; the input is ``[x~ | x]`` (``x~_i`` the mask id where masked) at the
position ids ``[0 .. L-1 | 0 .. L-1]``. With ``B(i) = i // b`` within a copy:
a noised ``i`` sees the noised ``j`` with ``B(j) == B(i)`` and the clean ``j``
with ``B(j) < B(i)``; a clean ``i`` the clean ``j`` with ``B(j) <= B(i)`` and
no noised one. Logits are read at the ``L`` noised positions for the token at
that position (no shift); the loss is ``sum over masked i of -log p(x_i) / t``
over ``rows * L``.

**Read into the source** (SDAR's training code is not on this machine; the
configuration file lists each under ``assumed`` with the reading it was
chosen over): the block length 4, a rate a row on the linear schedule and the
``1 / t`` weight, no shift, q/k norm and the router as Qwen3-MoE has them,
the mask id, AdamW(0.9, 0.95) with the ``gpt_small_lm`` schedule.

Parameters arrive as the nested dict the program's own tree has
(``token/embedding``, ``layer_<i>/self_attn/{query,key,value,attn_out}
/kernel``, ``layer_<i>/self_attn/{query_norm,key_norm}/scale``,
``layer_<i>/mlp/{router,experts_in,experts_out}/kernel``,
``layer_<i>/{self_attn_norm,mlp_norm}/scale``, ``final_norm/scale``,
``lm_head/kernel``): ``experts_in`` holds ``W1 | W3`` side by side; an expert
stack is one 2-D matrix ``[held * d_in, d_out]``, an expert's rows after an
expert's. A name looked up and not found is an error.

Memory: 646 M parameters in float32 with Adam's two moments and a gradient
are 10.3 GB of the chip's 16.9, so every layer runs under ``jax.checkpoint``
one row at a time, attention one K/V head's group and ``ATTN_ROWS`` query
rows at a time against all ``2 L`` keys, an expert layer ``MOE_ROWS`` tokens
and the head ``HEAD_ROWS`` positions at a time, each recomputed in the
backward pass; the step is two programs with Adam's moments on the host
between them (``granite4_h_micro``'s way), and ``train_steps`` consumes
``params``.

The compile cache: this file's programs are compiled outside jax's persistent
cache (``_uncached``). The timed step's executable is some 435 MiB (108 MiB
as the cache keeps it) and the cache of the machine with the chip holds
little more than that: every entry written after it, and this file's are,
pushed it out, so the next process compiled the step again (77 s) and a
traced run compiled it twice (PERF.md, PR 43).
"""

from __future__ import annotations

import contextlib
import math
import os
import sys
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import precision as _precision  # noqa: E402  (sibling file, no package)

MOE_ROWS = 1024   # tokens an expert layer takes at a time (memory only)
ATTN_ROWS = 1024  # query rows a group of heads takes at a time (memory only)
HEAD_ROWS = 2048  # positions the head and loss take at a time (memory only)


@contextlib.contextmanager
def _uncached():
    """What compiles inside is not written to jax's persistent compilation
    cache (the module's note): no compile takes as long as the cache then
    asks of an entry. Reads go on, and find nothing."""
    name = "jax_persistent_cache_min_compile_time_secs"
    before = getattr(jax.config, name)
    jax.config.update(name, float("inf"))
    try:
        yield
    finally:
        jax.config.update(name, before)


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def rope_tables(positions, head_dim: int, theta: float):
    """``(cos, sin)``: float32 ``[len(positions), head_dim / 2]``."""
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                               / head_dim)
    angles = np.asarray(positions, np.float64)[:, None] * inv_freq[None]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def _rotate(x, cos, sin):
    """``x [B, S, H, D]`` turned by its positions, the whole head."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def seen_mask(rows, length: int, block: int, faults=()):
    """``[len(rows), 2 L]`` booleans from the definition for the query
    positions ``rows`` of the ``[noised | clean]`` layout, or ``[len(rows),
    L]`` causal where ``block`` is 0 (the plain call). ``faults`` are the
    controls: ``"clean_sees_noised"`` leaves the dead quadrant live (a clean
    position sees the noised blocks up to its own),
    ``"staircase_off_by_one"`` lets a noised block see its own clean copy."""
    rows = jnp.asarray(rows)
    if not block:
        return jnp.arange(length)[None, :] <= rows[:, None]
    cols = jnp.arange(2 * length)
    q_clean, k_clean = (rows >= length)[:, None], (cols >= length)[None, :]
    qb = ((rows % length) // block)[:, None]
    kb = ((cols % length) // block)[None, :]
    earlier = kb <= qb if "staircase_off_by_one" in faults else kb < qb
    if "clean_sees_noised" in faults:
        noised_cols = jnp.where(q_clean, kb <= qb, kb == qb)
    else:
        noised_cols = ~q_clean & (kb == qb)
    return jnp.where(k_clean, jnp.where(q_clean, kb <= qb, earlier),
                     noised_cols)


def _attention(mm, a, p, sizes, block, faults):
    b, s, _ = a.shape
    d, hk = sizes["head_dim"], sizes["num_key_value_heads"]
    h, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    length = s // 2 if block else s
    q = mm(a, p["query"]["kernel"]).reshape(b, s, h, d)
    k = mm(a, p["key"]["kernel"]).reshape(b, s, hk, d)
    v = mm(a, p["value"]["kernel"]).reshape(b, s, hk, d)
    if "no_qk_norm" not in faults:
        q = _rms_norm(q, p["query_norm"], eps)
        k = _rms_norm(k, p["key_norm"], eps)
    positions = np.arange(s) if not block or "positions_run_on" in faults \
        else np.tile(np.arange(length), 2)
    cos, sin = rope_tables(positions, d, sizes["rope_theta"])
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)

    # One K/V head's query heads at a time, ATTN_ROWS of their rows at a
    # time against every key, each recomputed in the backward pass: a whole
    # layer's scores in float32 would be 34 GB.
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
        mask = seen_mask(first + jnp.arange(rows), length, block, faults)
        return mm(jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1),
                  vs[group])

    o = jax.lax.map(one_piece, jnp.arange(b * hk * (s // rows)))
    o = o.reshape(b * hk, s // rows, g, rows, d).transpose(0, 2, 1, 3, 4)
    o = o.reshape(b, h, s, d).transpose(0, 2, 1, 3)         # [B, S, H, D]
    return mm(o.reshape(b, s, h * d), p["attn_out"]["kernel"])


def _gated_mlp(mm, x, w_in, w_out):
    gate, up = jnp.split(mm(x, w_in), 2, axis=-1)
    return mm(jax.nn.silu(gate) * up, w_out)


def route(mm, m, p, sizes):
    """``(chosen [T, k], weight [T, k])``: softmax over all the experts of
    the layer, held here or not, the ``k`` largest, normalised over the
    chosen."""
    probs = jax.nn.softmax(mm(m, p["router"]["kernel"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, sizes["num_experts_per_tok"])
    return chosen, top / jnp.sum(top, axis=-1, keepdims=True)


def moe_layer(mm, m, p, sizes, experts_out=()):
    """The held experts' part of the layer's result for ``m [T, F]``: every
    held expert run over every token, weighted by what the router gave it (0
    where it was not among the token's chosen). ``experts_out`` is the
    control: held experts that add nothing."""
    e, f = sizes["published"]["num_experts"], m.shape[-1]
    first, held = sizes["experts_held"]
    w_in = p["experts_in"]["kernel"].reshape(held, f, -1)
    w_out = p["experts_out"]["kernel"].reshape(held, -1, f)
    keep = jnp.asarray([0.0 if first + i in experts_out else 1.0
                        for i in range(held)])

    def some_tokens(m):
        chosen, weight = route(mm, m, p, sizes)
        # [tokens, experts]: a token's weight for each, 0 where not chosen.
        dense = jnp.sum(jax.nn.one_hot(chosen, e) * weight[..., None],
                        axis=-2)[:, first:first + held] * keep

        def add_expert(out, turn):  # one compiled body for all of them
            w1, w2, weight_e = turn
            return out + _gated_mlp(mm, m, w1, w2) * weight_e[:, None], None

        out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m),
                              (w_in, w_out, dense.T))
        return out

    rows = min(MOE_ROWS, m.shape[0])
    if m.shape[0] % rows:
        raise ValueError(f"{m.shape[0]} tokens are not a multiple of {rows}")
    return jax.lax.map(jax.checkpoint(some_tokens),
                       m.reshape(-1, rows, f)).reshape(m.shape)


def _layer(mm, x, p, sizes, block, faults, experts_out):
    eps = sizes["rms_norm_eps"]
    x = x + _attention(mm, _rms_norm(x, p["self_attn_norm"], eps),
                       p["self_attn"], sizes, block, faults)
    m = _rms_norm(x, p["mlp_norm"], eps)
    b, s, f = m.shape
    return x + moe_layer(mm, m.reshape(b * s, f), p["mlp"], sizes,
                         experts_out).reshape(b, s, f)


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


def _trunk(mm, params, ids, sizes, block_rows, block, faults, experts_out):
    """``ids [B, S]`` -> the last held layer's output ``[B, S, F]``."""
    x = params["token"]["embedding"][ids]
    for layer in sizes["layers_held"]:
        p = params[f"layer_{layer}"]
        x = _by_rows(lambda xb, p=p: _layer(
            mm, xb, p, sizes, block, faults, experts_out), x, block_rows)
    return x


def _log_probs(mm, params, x, targets, sizes):
    """``log p(targets)`` ``[B, S]`` from the trunk's output ``x``: the final
    norm, the head and the log-softmax, ``HEAD_ROWS`` positions at a time."""
    def picked(part):
        xb, tb = part
        xb = _rms_norm(xb, params["final_norm"], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(xb, params["lm_head"]["kernel"]), axis=-1)
        return jnp.take_along_axis(logp, tb[..., None], axis=-1)[..., 0]

    b, s = targets.shape
    rows = min(HEAD_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions are not a multiple of {rows}")
    in_blocks = lambda t: t.reshape(b * (s // rows), rows, *t.shape[2:])
    return jax.lax.map(jax.checkpoint(picked),
                       (in_blocks(x), in_blocks(targets))).reshape(b, s)


def logits_fn(params, ids, sizes, precision="float32", block_rows=1,
              block=0):
    """``ids [B, S]`` -> logits (float32): the plain causal call ``[B, S,
    V]``, or with ``block`` the block-diffusion call over ``ids = [x~ | x]``
    ``[B, 2 L]``, logits of the noised copy ``[B, L, V]``."""
    mm = _precision.matmul(precision)
    x = _trunk(mm, params, ids, sizes, block_rows, block, (), ())
    if block:
        x = x[:, :ids.shape[1] // 2]
    x = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
    return mm(x, params["lm_head"]["kernel"])


def draw_noise(key, rows: int, length: int, noise: Dict[str, Any]):
    """``(rate [rows], masked [rows, length])`` from a step's key, as the
    configuration's ``noise`` states it."""
    k_rate, k_mask = jax.random.split(key)
    eps = noise["min_rate"]
    rate = eps + (1.0 - eps) * jax.random.uniform(k_rate, (rows,),
                                                  jnp.float32)
    masked = jax.random.uniform(k_mask, (rows, length), jnp.float32) \
        < rate[:, None]
    return rate, masked


def loss_fn(params, tokens, key, sizes, precision="float32", block_rows=1,
            faults=(), experts_out=()):
    """The block-diffusion loss of ``tokens [B, L + 1]`` (the last token of a
    row, a next-token target, is not read) under the step's ``key``.
    ``faults`` and ``experts_out`` are the controls (``seen_mask``,
    ``_attention``, ``moe_layer``; ``"no_rate_weight"`` leaves the ``1 / t``
    out)."""
    mm = _precision.matmul(precision)
    noise = sizes["noise"]
    clean = tokens[:, :-1]
    rows, length = clean.shape
    rate, masked = draw_noise(key, rows, length, noise)
    ids = jnp.concatenate(
        [jnp.where(masked, noise["mask_id"], clean), clean], axis=1)
    x = _trunk(mm, params, ids, sizes, block_rows, noise["block_length"],
               tuple(faults), tuple(experts_out))
    logp = _log_probs(mm, params, x[:, :length], clean, sizes)
    weight = masked if "no_rate_weight" in faults \
        else masked / rate[:, None]
    return -jnp.sum(logp * weight) / (rows * length)


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
    """``("layer_0/mlp/router/kernel", leaf)`` for every leaf."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield "/".join(str(getattr(k, "key", k)) for k in path), leaf


def _leaf_norms(tree) -> Dict[str, Any]:
    return {name: jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for name, leaf in _leaf_paths(tree)}


def train_steps(params, batches: List[Any], sizes: Dict[str, Any],
                hp: Dict[str, float], precision: str = "float32",
                block_rows: int = 1, rng=None, faults=(), experts_out=()
                ) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from ``params``,
    which this call consumes (see the module's note on memory). ``rng`` is
    the key the trainer was given: step ``i`` draws its noise from
    ``fold_in(rng, i)``. ``faults`` and ``experts_out`` are the controls,
    each one thing done wrongly (``loss_fn``).

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (after clipping), and the norm of each leaf's
    change over all the steps."""
    if rng is None:
        raise ValueError("the noise is drawn from the trainer's key: rng")

    with _uncached():
        # Two programs, and Adam's moments on the host while the first
        # runs: the gradient's program holds the weights, the gradient and a
        # layer's working set, the update's the weights, the gradient and
        # both moments and nothing else (memory only).
        gradient = jax.jit(lambda params, tokens, key: jax.value_and_grad(
            loss_fn)(params, tokens, key, sizes, precision, block_rows,
                     tuple(faults), tuple(experts_out)))
        update = jax.jit(
            lambda params, mu, nu, count, grads: _adamw_step(
                params, mu, nu, count, grads, hp),
            donate_argnums=(0, 1, 2))
        start = jax.device_get(params)
        mu = nu = jax.tree_util.tree_map(np.zeros_like, start)
        losses, first = [], None
        for i, tokens in enumerate(batches):
            loss, grads = gradient(params, jnp.asarray(tokens),
                                   jax.random.fold_in(rng, i))
            params, mu, nu, norms = update(
                params, jax.device_put(mu), jax.device_put(nu),
                jnp.asarray(i, jnp.int32), grads)
            losses.append(float(loss))
            if first is None:
                first = {k: float(v) for k, v in norms.items()}
            if i + 1 < len(batches):
                mu, nu = jax.device_get((mu, nu))
        del mu, nu
        change = jax.jit(
            lambda new, old: jnp.sqrt(jnp.sum(jnp.square(new - old))))
        moved = {name: float(change(new, old)) for (name, new), (_, old)
                 in zip(_leaf_paths(params), _leaf_paths(start))}
    return {"loss": losses, "grad_norms": first, "change_norms": moved}
