"""Plain reference for the ``keye_vl2_30b_a3b`` configuration: one chip's
share of Keye-VL-2.0-30B-A3B's language model trained on text rows, in
straightforward ``jax.numpy`` and float32 (``Precision.HIGHEST``): its
forward pass, its objective (cross-entropy plus the indexers' KL loss), its
gradients and the AdamW step the configuration states. No kernels, no bit
masks, no grouped matmul, nothing imported from the program: **the selection
is ``lax.top_k`` over a row's causal index scores, the mask a comparison
with the threshold**.

**The layer equations** (``h`` a block's input ``[S, 2048]``; ``n`` is RMSNorm
with eps 1e-6 inside the root; no bias but the index key's LayerNorm):

- ``a = n(h)``; ``q = a Wq`` ``[S, 32, 128]``, ``k = a Wk``, ``v = a Wv``
  ``[S, 4, 128]``; ``q <- n_q(q)``, ``k <- n_k(k)`` a head over its 128
  channels; rotary positions in the two-halves layout, theta 1e7, the 64
  frequency pairs in sections of 16, 24 and 24, pair ``p`` turned by the
  position stream of its section (a text row's three streams are all ``0 ..
  S - 1``); query head ``i`` reads K/V head ``i // 8``.
- The indexer, from ``abar = stop_gradient(a)``: ``qI = rot(abar W_qI)``
  ``[S, 16, 64]``, ``kI = rot(LN(abar W_kI))`` ``[S, 64]`` (LayerNorm with
  scale and bias, eps 1e-6; the whole head of 64 turned by stream 0, theta
  1e7), ``w = abar W_w`` ``[S, 16]``. ``I[t, s] = 64^-1/2 16^-1/2 sum_j w[t,
  j] relu(qI[t, j] . kI[s])`` for ``s <= t``; ``tau[t]`` the 2048th largest
  of row ``t``'s causal scores (``-inf`` while the row has at most 2048);
  ``S_t = {s <= t : I[t, s] >= tau[t]}``.
- ``o[t, head] = sum_{s in S_t} softmax_{s in S_t}(q[t] . k[s] / sqrt(128))
  v[s]``; ``h <- h + o Wo``. The selection passes no gradient.
- The indexer's loss: ``P[t, s] = mean over heads of p[head, t, s]`` under
  ``stop_gradient``; ``KL_t = sum_{s in S_t} P[t, s] (log P[t, s] - log
  softmax_{s in S_t}(I[t, :])[s])``; ``L_I`` is its mean over the layers held
  and the rows. The objective is ``cross-entropy + L_I``; the step reports
  the cross-entropy as its loss.
- ``m = n(h)``; the router and the experts as ``sdar_30b_a3b``'s (softmax
  over 128, the 8 largest normalised over the chosen, gated MLPs of width
  768; an expert that is not held adds nothing).
- After the last layer held: RMSNorm, ``logits = x W_head`` over the
  vocabulary rows held; next-token cross-entropy over the row.

**Read into the source** (the modelling code is not on this machine; the
configuration file lists each under ``assumed``): a selection a token with
the chunk sizes as a tiling, the indexer's projections from the block's
normed input, LayerNorm on the index key and the two scale factors (DSA's
code), the indexer's rotary turn by stream 0, the KL stage with coefficient
1, q/k norm and the router as Qwen3-MoE has them, the chunked sections.

Parameters arrive as the nested dict the program's own tree has
(``sdar_30b_a3b``'s names and, under ``layer_<i>/self_attn``,
``index_query/kernel``, ``index_key/kernel``, ``index_key_norm/{scale,bias}``,
``index_weight/kernel``). A name looked up and not found is an error.

Memory: as ``sdar_30b_a3b``'s reference, every layer under
``jax.checkpoint``; attention ``ATTN_ROWS`` query rows at a time, all 32
heads of them against all ``S`` keys (the selection is one for the heads and
the loss averages over them), each piece recomputed in the backward pass. A
piece forms its own rows' queries from the block's normed input and returns
its rows' share of ``o Wo``, and a K/V head is read by its group without
being repeated: with ``[S, 32, 128]`` queries and outputs standing whole the
gradient's program wanted 8.1 GiB of temporaries beside 2.5 of weights and
2.5 of gradients and did not load (my chip run, PR 47, call 1); so it wants
3.4. The step is two programs with Adam's moments on the host between them. Its
programs compile outside jax's persistent cache (``_uncached``; PERF.md, PR
43, has why).
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
ATTN_ROWS = 64    # query rows all the heads take at a time (memory only)
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


def rope_tables(streams, head_dim: int, theta: float, sections=None):
    """``(cos, sin)``: float32 ``[S, head_dim / 2]`` for position streams
    ``[n, S]``: frequency pair ``p`` turns by the stream of its section
    (``sections`` pairs each, in order; none: stream 0 turns every pair)."""
    inv_freq = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                               / head_dim)
    streams = np.asarray(streams, np.float64)
    of_pair = np.zeros(inv_freq.size, int) if sections is None \
        else np.repeat(np.arange(len(sections)), sections)
    if of_pair.size != inv_freq.size:
        raise ValueError(f"sections {sections} do not add up to "
                         f"{inv_freq.size} frequency pairs")
    angles = streams[of_pair].T * inv_freq[None]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def _rotate(x, cos, sin):
    """``x [B, S, H, D]`` turned by its positions, the whole head."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)



def _layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def select(scores, rows, topk: int, faults=()):
    """``keep [len(rows), S]`` for the query positions ``rows`` from their
    index scores against every key: the causal keys at or over the row's
    ``topk``-th largest causal score, every causal key of a row that has at
    most ``topk``. The controls: ``"no_selection"`` keeps every causal key,
    ``"topk_1024"`` half as many, ``"selection_not_causal"`` takes the
    threshold over every key, later ones too."""
    s = scores.shape[-1]
    causal = jnp.arange(s)[None, :] <= rows[:, None]
    if "no_selection" in faults:
        return causal
    if "topk_1024" in faults:
        topk //= 2
    if topk >= s:
        return causal
    among = scores if "selection_not_causal" in faults \
        else jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(among, topk)[0][:, -1]
    tau = jnp.where(rows < topk, -jnp.inf, kth)
    return causal & (scores >= tau[:, None])


def _attention(mm, a, p, sizes, faults):
    """``(o Wo [B, S, F], sum over the rows of KL_t)``."""
    b, s, _ = a.shape
    d, hk = sizes["head_dim"], sizes["num_key_value_heads"]
    h, eps = sizes["num_attention_heads"], sizes["rms_norm_eps"]
    sa = sizes["sa_config"]
    hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    theta = sizes["rope_theta"]
    k = mm(a, p["key"]["kernel"]).reshape(b, s, hk, d)
    v = mm(a, p["value"]["kernel"]).reshape(b, s, hk, d)
    k = _rms_norm(k, p["key_norm"], eps)
    streams = np.tile(np.arange(s), (3, 1))  # a text row
    cos, sin = rope_tables(streams, d, theta,
                           sizes["rope_scaling"]["mrope_section"])
    k = _rotate(k, cos, sin)

    def queries(a_rows, first):
        """The 32 query heads of some rows ``[rows, F]``, normed and turned
        by their positions: formed a piece at a time, so that no ``[S, 32,
        128]`` array, nor its cotangents, stands in memory (memory only)."""
        rows = a_rows.shape[0]
        q = _rms_norm(mm(a_rows, p["query"]["kernel"]).reshape(1, rows, h, d),
                      p["query_norm"], eps)
        at = lambda t: jax.lax.dynamic_slice_in_dim(t, first, rows, 0)
        return _rotate(q, at(cos), at(sin))[0].transpose(1, 0, 2)

    abar = a if "indexer_sees_lm_gradient" in faults \
        else jax.lax.stop_gradient(a)
    turn = rope_tables(streams[:1], di, theta)
    qi = _rotate(mm(abar, p["index_query"]["kernel"]).reshape(b, s, hi, di),
                 *turn)
    ki = _rotate(_layer_norm(mm(abar, p["index_key"]["kernel"]),
                             p["index_key_norm"])[:, :, None, :],
                 *turn)[:, :, 0]
    w = mm(abar, p["index_weight"]["kernel"])                 # [B, S, hi]

    rows = min(ATTN_ROWS, s)
    if s % rows:
        raise ValueError(f"{s} positions are not a multiple of {rows}")
    g = h // hk  # query heads to a K/V head
    scale_i = 1.0 / math.sqrt(di) / math.sqrt(hi)
    keys, values = k.transpose(0, 2, 3, 1), v.transpose(0, 2, 1, 3)

    @jax.checkpoint
    def one_piece(i):  # rows of one batch row, all heads, against S keys
        row, first = i // (s // rows), (i % (s // rows)) * rows
        at = first + jnp.arange(rows)
        take = lambda t: jax.lax.dynamic_slice_in_dim(t[row], first, rows, 0)
        # I [rows, S]: a head's relu(qI . kI) weighted and summed.
        hits = jax.nn.relu(mm(take(qi).transpose(1, 0, 2), ki[row].T))
        scores_i = scale_i * jnp.einsum("hts,th->ts", hits, take(w),
                                        precision=_precision.HIGHEST)
        keep = select(jax.lax.stop_gradient(scores_i), at, topk, faults)
        # A K/V head's group of query heads beside each other as its rows:
        # [hk, g rows, D] against [hk, D, S], nothing repeated.
        grouped = queries(take(a), first).reshape(hk, g * rows, d)
        logits = (mm(grouped, keys[row]) / math.sqrt(d)).reshape(h, rows, s)
        probs = jax.nn.softmax(jnp.where(keep, logits, -1e30), axis=-1)
        out = mm(probs.reshape(hk, g * rows, s), values[row]) \
            .reshape(h, rows, d)
        # The rows' share of ``o Wo``, so that no ``[S, 32, 128]`` output is
        # stacked either.
        out = mm(out.transpose(1, 0, 2).reshape(rows, h * d),
                 p["attn_out"]["kernel"])
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=0))
        log_soft = jax.nn.log_softmax(jnp.where(keep, scores_i, -1e30),
                                      axis=-1)
        kl = jnp.sum(jnp.where(
            keep & (target > 0),
            target * (jnp.log(jnp.maximum(target, 1e-37)) - log_soft), 0.0))
        return out, kl

    mixed, kl = jax.lax.map(one_piece, jnp.arange(b * (s // rows)))
    return mixed.reshape(b, s, -1), jnp.sum(kl)


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


def _layer(mm, x, p, sizes, faults, experts_out):
    eps = sizes["rms_norm_eps"]
    mixed, kl = _attention(mm, _rms_norm(x, p["self_attn_norm"], eps),
                           p["self_attn"], sizes, faults)
    x = x + mixed
    m = _rms_norm(x, p["mlp_norm"], eps)
    b, s, f = m.shape
    return x + moe_layer(mm, m.reshape(b * s, f), p["mlp"], sizes,
                         experts_out).reshape(b, s, f), kl


def _by_rows(fn, x, block_rows):
    """``fn`` (a block of rows -> their output and a sum) over blocks of
    ``block_rows`` rows of ``x``, each recomputed in the backward pass: one
    block's activations are all that is alive."""
    b = x.shape[0]
    block_rows = min(block_rows, b)
    if b % block_rows:
        raise ValueError(f"batch {b} is not a multiple of {block_rows}")
    out, total = jax.lax.map(
        jax.checkpoint(fn),
        x.reshape(b // block_rows, block_rows, *x.shape[1:]))
    return out.reshape(b, *out.shape[2:]), jnp.sum(total)


def _trunk(mm, params, ids, sizes, block_rows, faults, experts_out):
    """``ids [B, S]`` -> the last held layer's output ``[B, S, F]`` and the
    indexers' loss: the mean of ``KL_t`` over the layers held and the
    rows."""
    x = params["token"]["embedding"][ids]
    total = 0.0
    for layer in sizes["layers_held"]:
        p = params[f"layer_{layer}"]
        x, kl = _by_rows(lambda xb, p=p: _layer(
            mm, xb, p, sizes, faults, experts_out), x, block_rows)
        total = total + kl
    return x, total / (len(sizes["layers_held"]) * ids.shape[0]
                       * ids.shape[1])


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


def logits_fn(params, ids, sizes, precision="float32", block_rows=1):
    """``ids [B, S]`` -> logits ``[B, S, V]`` (float32)."""
    mm = _precision.matmul(precision)
    x, _ = _trunk(mm, params, ids, sizes, block_rows, (), ())
    x = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
    return mm(x, params["lm_head"]["kernel"])


def loss_parts(params, tokens, sizes, precision="float32", block_rows=1,
               faults=(), experts_out=()):
    """``(cross-entropy, L_I)`` of ``tokens [B, S + 1]``: next-token
    prediction over the row, and the indexers' loss beside it."""
    mm = _precision.matmul(precision)
    x, kl = _trunk(mm, params, tokens[:, :-1], sizes, block_rows,
                   tuple(faults), tuple(experts_out))
    logp = _log_probs(mm, params, x, tokens[:, 1:], sizes)
    return -jnp.mean(logp), kl


def loss_fn(params, tokens, sizes, precision="float32", block_rows=1,
            faults=(), experts_out=()):
    """``(objective, cross-entropy)``: the objective is the cross-entropy
    plus the indexers' loss (``"indexer_loss_dropped"``, a control, leaves
    it out); the step reports the cross-entropy."""
    ce, kl = loss_parts(params, tokens, sizes, precision, block_rows, faults,
                        experts_out)
    return ce + (0.0 if "indexer_loss_dropped" in faults else kl), (ce, kl)


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
    not read: the step draws nothing. ``faults`` and ``experts_out`` are the
    controls, each one thing done wrongly.

    Returns each step's loss (the cross-entropy, as the program reports it)
    and indexer loss, the norm of each leaf of the first gradient as the
    optimizer gets it (after clipping), and the norm of each leaf's change
    over all the steps."""
    del rng
    with _uncached():
        gradient = jax.jit(lambda params, tokens: jax.value_and_grad(
            loss_fn, has_aux=True)(params, tokens, sizes, precision,
                                   block_rows, tuple(faults),
                                   tuple(experts_out)))
        update = jax.jit(
            lambda params, mu, nu, count, grads: _adamw_step(
                params, mu, nu, count, grads, hp),
            donate_argnums=(0, 1, 2))
        start = jax.device_get(params)
        mu = nu = jax.tree_util.tree_map(np.zeros_like, start)
        losses, kls, first = [], [], None
        for i, tokens in enumerate(batches):
            (_, (ce, kl)), grads = gradient(params, jnp.asarray(tokens))
            params, mu, nu, norms = update(
                params, jax.device_put(mu), jax.device_put(nu),
                jnp.asarray(i, jnp.int32), grads)
            losses.append(float(ce))
            kls.append(float(kl))
            if first is None:
                first = {k: float(v) for k, v in norms.items()}
            if i + 1 < len(batches):
                mu, nu = jax.device_get((mu, nu))
        del mu, nu
        change = jax.jit(
            lambda new, old: jnp.sqrt(jnp.sum(jnp.square(new - old))))
        moved = {name: float(change(new, old)) for (name, new), (_, old)
                 in zip(_leaf_paths(params), _leaf_paths(start))}
    return {"loss": losses, "indexer_kl": kls, "grad_norms": first,
            "change_norms": moved}
