"""Plain reference for the ``zaya1_8b`` configuration: one chip's share of
Zyphra's ZAYA1-8B in straightforward ``jax.numpy`` and float32
(``Precision.HIGHEST``), its loss, its gradients and the AdamW step the
configuration states. No kernels, no sorting, no grouped matmul, nothing
imported from the program; the convolutions and the value shift are shifts
of the sequence written out here.

**The layer equations** (layer ``l``; ``h`` its input ``[T, 2048]``; ``r``
the router state of layer ``l - 1``, none at ``l = 0``; 8 query heads over 2
K/V heads of 128; no bias in attention; ``x_{t-1}`` is 0 before a
sequence's first position):

1. ``a = RMSNorm(h)`` (eps 1e-5). ``q~ = a Wq`` ``[T, 8 * 128]``,
   ``k~ = a Wk`` ``[T, 2 * 128]``: projections *down* into the latent.
2. ``c = [q~, k~]`` ``[T, 1280]``. ``c1_t = w0[0] * c_{t-1} + w0[1] * c_t``
   (``conv_depth/kernel`` ``[2, 1280]``, a tap a channel);
   ``c2_t = c1_{t-1} W1[0] + c1_t W1[1]``, ``W1[j]`` block-diagonal, one
   128 x 128 block a head (``conv_heads/kernel`` ``[2 * 128, 1280]``: row
   ``j * 128 + i``, column ``head * 128 + o``).
3. ``m_q[h] = (q~[h] + k~[g(h)]) / 2``, ``g(h) = h // 4``; ``m_k[g]`` the
   mean of ``m_q`` over the group's 4 heads; ``q = c2[:1024] + m_q``,
   ``k = c2[1024:] + m_k``.
4. ``v = [a_t Wv, a_{t-1} Wv']``, each ``[T, 128]``: K/V head 0 sees the
   token, head 1 the token before it.
5. ``q[h] <- sqrt(128) q[h] / |q[h]|``, ``k[g] <- tau_g sqrt(128) k[g] /
   |k[g]|`` (``key_temp/scale`` ``[2]``).
6. Rotary positions on the first 64 of each head's 128 dimensions, theta
   5e6, two-halves layout, q and k.
7. ``o = softmax(q k^T / sqrt(128) + causal) v``; ``f = o Wo``
   ``[T, 1024] -> [T, 2048]``. A packed row attends, convolves and shifts
   across its documents' boundaries.
8. ``h <- (h + b_r) * s_r + (f + b_o) * s_o`` (``self_attn_stream``,
   ``self_attn_result``: a ``bias`` and a ``scale`` of 2048 each); layer
   0's attention sublayer has no ``s_r``, ``b_r``.
9. ``u = RMSNorm(h)``. ``z = u Wd + b_d`` ``[T, 256]``; ``z <- z + gamma *
   r`` for ``l > 0``; ``r' = z`` goes to layer ``l + 1``; ``p = softmax(W3
   gelu(W2 gelu(W1 RMSNorm(z) + b1) + b2))`` ``[T, 16]``, exact GELU;
   ``e = argmax(p + beta)``, no gradient through ``beta``; ``w = p[e]``.
   After each training step ``beta_e <- beta_e - rate * min(n_e / mean(n) -
   1, 1)``, ``n_e`` the tokens of the step that chose expert ``e`` (of all
   16, held or not), ``rate`` the configuration's ``router_balance_rate``.
10. ``y = w E_e(u)`` where ``e`` is held (``E(u) = (silu(u W1) * u W3)
    W2``, width 2048), else 0. ``h <- (h + b_r) * s_r + (y + b_o) * s_o``
    (``mlp_stream``, ``mlp_result``).
11. After the last layer held: RMSNorm, ``logits = h Embed^T`` over the
    vocabulary rows held; the loss is the mean next-token cross-entropy.

**The chip's share** (``benchmark/configs/zaya1_8b.json``): layers
``layers_held`` of the 40, the ``num_experts`` experts from ``experts_held``
on of the 16 the router scores, ``vocab_size`` rows of the embedding, which
is the head too. What the absent experts would add is left out here as in
the program, and that partial result goes on to the next layer.

**Read into the source**: the configuration file lists each reading under
``assumed`` with the one it was chosen over.

Parameters arrive as the nested dict the program's own tree has
(``token/embedding``, ``layer_<i>/self_attn/{query,key,value,value_prev,
conv_depth,conv_heads,attn_out}/kernel``, ``layer_<i>/self_attn/key_temp
/scale``, ``layer_<i>/mlp/router/{down,hidden_0,hidden_1,out}/...``,
``layer_<i>/mlp/router/{scale,bias}`` (gamma, beta), ``layer_<i>/mlp
/experts_{in,out}/kernel``, ``final_norm/scale``): ``experts_in`` holds ``W1
| W3`` side by side; an expert stack is one 2-D matrix ``[experts * d_in,
d_out]``. A name looked up and not found is an error.

Memory: 602 M parameters in float32 with Adam's two moments and a gradient
are 9.6 GB of the chip's 16, so every layer runs under ``jax.checkpoint``
one block of ``block_rows`` rows at a time, attention one K/V head's group
at a time, the expert layer and the head ``MOE_ROWS`` and ``HEAD_ROWS``
tokens at a time, and ``train_steps`` consumes ``params``: it keeps the
starting values on the host and gives the device buffers to the first step.
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

MOE_ROWS = 1024   # tokens an expert layer takes at a time (memory only)
ATTN_ROWS = 1024  # query rows a group of heads takes at a time (memory only)
HEAD_ROWS = 1024  # tokens the head and the loss take at a time (memory only)


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def rope_tables(seq_len: int, head_dim: int, rope: Dict[str, Any]):
    """``(cos, sin, rot)``: float32 ``[seq_len, rot / 2]`` tables of one
    entry of the source's ``rope_parameters``, and how many of a head's
    dimensions turn."""
    if rope["rope_type"] != "default":
        raise ValueError(f"unknown rope_type {rope['rope_type']!r}")
    rot = int(head_dim * rope["partial_rotary_factor"])
    inv_freq = 1.0 / rope["rope_theta"] ** (
        np.arange(0, rot, 2, dtype=np.float64) / rot)
    angles = np.arange(seq_len, dtype=np.float64)[:, None] * inv_freq[None]
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32), rot)


def _rotate(x, cos, sin, rot):
    """``x [B, S, H, D]`` turned by its positions."""
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _before(x, n=1):
    """``x [B, S, ...]`` as the position ``n`` places later sees it:
    ``y[:, t] = x[:, t - n]``, zeros before the sequence's start."""
    for _ in range(n):
        x = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    return x


def _latent(mm, a, p, sizes):
    """Steps 1-5: ``a [B, S, F]`` -> q ``[B, S, H, D]``, k and v ``[B, S,
    KV, D]`` before rotary positions."""
    b, s, _ = a.shape
    d, h = sizes["head_dim"], sizes["num_attention_heads"]
    hk = sizes["num_key_value_heads"]
    g = h // hk
    q0 = mm(a, p["query"]["kernel"])
    k0 = mm(a, p["key"]["kernel"])
    c = jnp.concatenate([q0, k0], axis=-1)
    w0 = p["conv_depth"]["kernel"]
    if w0.shape[0] != sizes["cca_time0"]:
        raise ValueError(f"{w0.shape[0]} depthwise taps, cca_time0 "
                         f"{sizes['cca_time0']}")
    n0, n1 = sizes["cca_time0"], sizes["cca_time1"]
    c1 = sum(w0[j] * _before(c, n0 - 1 - j) for j in range(n0))
    w1 = p["conv_heads"]["kernel"].reshape(n1, d, h + hk, d)
    # One head a group: [heads, tokens, d] @ [heads, d, d].
    by_head = lambda t: t.reshape(b * s, h + hk, d).transpose(1, 0, 2)
    c2 = sum(mm(by_head(_before(c1, n1 - 1 - j)), w1[j].transpose(1, 0, 2))
             for j in range(n1))
    c2 = c2.transpose(1, 0, 2).reshape(b, s, h + hk, d)
    mean_q = 0.5 * (q0.reshape(b, s, hk, g, d) + k0.reshape(b, s, hk, 1, d))
    q = c2[:, :, :h] + mean_q.reshape(b, s, h, d)
    k = c2[:, :, h:] + jnp.mean(mean_q, axis=3)
    v = jnp.concatenate([mm(a, p["value"]["kernel"]),
                         _before(mm(a, p["value_prev"]["kernel"]))],
                        axis=-1).reshape(b, s, hk, d)
    unit = lambda t: math.sqrt(d) * t / jnp.sqrt(
        jnp.sum(jnp.square(t), axis=-1, keepdims=True))
    return unit(q), unit(k) * p["key_temp"]["scale"][:, None], v


def _attention(mm, a, p, sizes, layer):
    b, s, _ = a.shape
    d, h = sizes["head_dim"], sizes["num_attention_heads"]
    hk = sizes["num_key_value_heads"]
    q, k, v = _latent(mm, a, p, sizes)
    cos, sin, rot = rope_tables(
        s, d, sizes["rope_parameters"][sizes["layer_types"][layer]])
    q, k = _rotate(q, cos, sin, rot), _rotate(k, cos, sin, rot)
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    # One K/V head's query heads at a time, ATTN_ROWS of their rows at a
    # time, each recomputed in the backward pass.
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


def router(mm, m, r, p, eps):
    """Step 9 for ``m [T, F]`` and the state ``r [T, hidden]`` of the layer
    before (``None``: none is added): ``(weights [T, experts], z, load)``, a
    token's weight ``p[e]`` at its one chosen expert and 0 elsewhere, the
    state ``z`` for the next layer, and how many tokens chose each expert."""
    z = mm(m, p["down"]["kernel"]) + p["down"]["bias"]
    if r is not None:
        z = z + p["scale"] * r
    y = _rms_norm(z, p["norm"], eps)
    for name in ("hidden_0", "hidden_1"):
        y = jax.nn.gelu(mm(y, p[name]["kernel"]) + p[name]["bias"],
                        approximate=False)
    probs = jax.nn.softmax(mm(y, p["out"]["kernel"]), axis=-1)
    chosen = jnp.argmax(probs + jax.lax.stop_gradient(p["bias"]), axis=-1)
    choice = jax.nn.one_hot(chosen, probs.shape[-1])
    return choice * probs, z, jnp.sum(choice, axis=0)


def moe_layer(mm, m, r, p, sizes, experts=None):
    """The expert layer's result for ``m [T, F]``, the router's state to
    hand on and the tokens that chose each expert ``[experts]``: every held
    expert run over every token, weighted by what the
    router gave it (0 where it was not the token's choice). ``experts``
    computes only the first so many of the experts held (the control: a step
    that leaves one out)."""
    first, held = sizes["experts_held"][0], sizes["num_experts"]
    n = held if experts is None else experts
    f = m.shape[-1]
    w_in = p["experts_in"]["kernel"].reshape(held, f, -1)[:n]
    w_out = p["experts_out"]["kernel"].reshape(held, -1, f)[:n]

    def some_tokens(block):
        m, r = block
        weights, z, load = router(mm, m, r, p["router"],
                                  sizes["rms_norm_eps"])

        def add_expert(out, expert):  # one compiled body for all of them
            w1, w2, weight_e = expert
            return out + weight_e[:, None] * _gated_mlp(mm, m, w1, w2), None

        out, _ = jax.lax.scan(add_expert, jnp.zeros_like(m),
                              (w_in, w_out, weights[:, first:first + n].T))
        return out, z, load

    rows = min(MOE_ROWS, m.shape[0])
    if m.shape[0] % rows:
        raise ValueError(f"{m.shape[0]} tokens are not a multiple of {rows}")
    blocks = lambda t: None if t is None else t.reshape(-1, rows, t.shape[-1])
    out, z, load = jax.lax.map(jax.checkpoint(some_tokens),
                               (blocks(m), blocks(r)))
    return out.reshape(m.shape), z.reshape(m.shape[0], -1), \
        jnp.sum(load, axis=0)


def _join(h, f, p, sub, stream=True):
    """Step 8: the stream and a sublayer's result, each shifted and scaled."""
    if stream:
        h = (h + p[f"{sub}_stream"]["bias"]) * p[f"{sub}_stream"]["scale"]
    return h + (f + p[f"{sub}_result"]["bias"]) * p[f"{sub}_result"]["scale"]


def _layer(mm, x, r, p, sizes, layer, experts):
    eps = sizes["rms_norm_eps"]
    if sizes["layer_types"][layer] != "hybrid":
        raise ValueError(f"unknown layer type {sizes['layer_types'][layer]!r}")
    f = _attention(mm, _rms_norm(x, p["self_attn_norm"], eps),
                   p["self_attn"], sizes, layer)
    x = _join(x, f, p, "self_attn", stream=layer > 0)
    u = _rms_norm(x, p["mlp_norm"], eps)
    b, s, width = u.shape
    y, z, load = moe_layer(mm, u.reshape(b * s, width),
                           None if r is None else r.reshape(b * s, -1),
                           p["mlp"], sizes, experts)
    return _join(x, y.reshape(b, s, width), p, "mlp"), \
        z.reshape(b, s, -1), load


def _by_rows(fn, xs, block_rows):
    """``fn`` over blocks of ``block_rows`` rows of the arrays ``xs`` (a
    ``None`` among them stays ``None``), each recomputed in the backward
    pass: one block's activations are all that is alive. What ``fn`` returns
    comes back stacked, a block an entry."""
    b = xs[0].shape[0]
    block_rows = min(block_rows, b)
    if b % block_rows:
        raise ValueError(f"batch {b} is not a multiple of {block_rows}")
    blocks = lambda t: None if t is None else t.reshape(
        b // block_rows, block_rows, *t.shape[1:])
    return jax.lax.map(jax.checkpoint(lambda block: fn(*block)),
                       tuple(blocks(t) for t in xs))


def _trunk(mm, params, ids, sizes, block_rows, experts, carry_state):
    """``ids [B, S]`` -> the last held layer's output ``[B, S, F]`` and, a
    layer held, the tokens that chose each of its router's experts."""
    x, r, loads = params["token"]["embedding"][ids], None, {}
    rows = lambda t: t.reshape(ids.shape[0], *t.shape[2:])
    for layer in sizes["layers_held"]:
        p = params[f"layer_{layer}"]
        if layer > 0 and r is None:
            # Handed nothing (the control, or a stage that starts past layer
            # 0): the router still has its gamma, and adds it to zeros.
            r = jnp.zeros(x.shape[:2] + (sizes["router_hidden_size"],))
        x, z, load = _by_rows(lambda xb, rb, p=p, layer=layer: _layer(
            mm, xb, rb, p, sizes, layer, experts), (x, r), block_rows)
        x, r = rows(x), rows(z) if carry_state else None
        loads[f"layer_{layer}"] = jnp.sum(load, axis=0)
    return x, loads


def _head_blocks(t):
    rows = min(HEAD_ROWS, t.shape[0] * t.shape[1])
    return t.reshape(-1, rows, *t.shape[2:])


def logits_fn(params, ids, sizes, precision="float32", block_rows=1,
              experts=None, carry_state=True):
    """``ids [B, S]`` -> logits ``[B, S, V]`` (float32)."""
    mm = _precision.matmul(precision)
    x, _ = _trunk(mm, params, ids, sizes, block_rows, experts, carry_state)
    x = _rms_norm(x, params["final_norm"], sizes["rms_norm_eps"])
    return mm(x, params["token"]["embedding"].T)


def loss_fn(params, tokens, sizes, precision="float32", block_rows=1,
            experts=None, carry_state=True):
    """Mean next-token cross-entropy of ``tokens [B, S+1]``, and the loads
    of :func:`_trunk`."""
    mm = _precision.matmul(precision)
    x, loads = _trunk(mm, params, tokens[:, :-1], sizes, block_rows, experts,
                      carry_state)

    def picked(block):  # the head and the loss, a block of tokens at a time
        xb, targets = block
        xb = _rms_norm(xb, params["final_norm"], sizes["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(xb, params["token"]["embedding"].T),
                                  axis=-1)
        return jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]

    logp = jax.lax.map(jax.checkpoint(picked),
                       (_head_blocks(x), _head_blocks(tokens[:, 1:])))
    return -jnp.mean(logp), loads


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
    """``("layer_0/mlp/experts_in/kernel", leaf)`` for every leaf."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        yield "/".join(str(getattr(k, "key", k)) for k in path), leaf


def _leaf_norms(tree) -> Dict[str, Any]:
    return {name: jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for name, leaf in _leaf_paths(tree)}


def train_steps(params, batches: List[Any], sizes: Dict[str, Any],
                hp: Dict[str, float], precision: str = "float32",
                block_rows: int = 1, rng=None, rows: int = None,
                experts: int = None, carry_state: bool = True
                ) -> Dict[str, Any]:
    """Follow the program's first ``len(batches)`` steps from ``params``,
    which this call consumes (see the module's note on memory). ``rng`` is
    accepted for the harness's sake and not read: nothing here is random.
    ``rows``, ``experts`` and ``carry_state`` are the controls: only the
    first ``rows`` rows of each batch count; only the first ``experts`` of
    the experts held are computed; no router is handed the state of the
    layer before.

    Returns each step's loss, the norm of each leaf of the first gradient
    as the optimizer gets it (after clipping), and the norm of each leaf's
    change over all the steps."""
    del rng

    def step(params, mu, nu, count, tokens):
        if rows is not None:
            tokens = tokens[:rows]
        (loss, loads), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, tokens, sizes, precision, block_rows, experts,
            carry_state)
        params, mu, nu, norms = _adamw_step(params, mu, nu, count, grads, hp)
        # The balancing biases' controller, after the optimizer (which
        # leaves them where they were: their gradient is zero).
        for layer, load in loads.items():
            p = params[layer]["mlp"]["router"]
            p["bias"] = p["bias"] - sizes["router_balance_rate"] \
                * jnp.minimum(load / jnp.mean(load) - 1.0, 1.0)
        return params, mu, nu, norms, loss

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
