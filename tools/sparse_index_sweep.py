#!/usr/bin/env python3
"""The learned sparse attention's kernels alone on the chip (``ops/
sparse_index.py`` and the flash kernels under a selection): against the
definitions at a length XLA holds whole (``--check``, default 4096), then
timed by the host's clock at the cell's length (``--time``, default 16384).

    python tools/sparse_index_sweep.py [--check 4096] [--time 16384]

One JSON line a phase (about two chip-minutes whole)."""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from deeplearning_cfn_tpu.ops import sparse_index as si  # noqa: E402
from deeplearning_cfn_tpu.ops.attention import fused_attention  # noqa: E402

B, H, HK, D, HI, DI, TOPK = 1, 32, 4, 128, 16, 64, 2048


def operands(s, whole_numbers):
    ks = jax.random.split(jax.random.PRNGKey(s), 6)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32)
    snap = (lambda t: jnp.round(2 * t)) if whole_numbers else (lambda t: t)
    q, k, v = (normal(ks[i], (B, h, s, D)).astype(jnp.bfloat16)
               for i, h in enumerate((H, HK, HK)))
    qi = snap(normal(ks[3], (B, HI, s, DI))).astype(jnp.bfloat16)
    ki = snap(normal(ks[4], (B, s, DI))).astype(jnp.bfloat16)
    w = snap(normal(ks[5], (B, s, HI)))
    return q, k, v, qi, ki, w


def step(impl):
    def objective(q, k, v, qi, ki, w):
        words, lse_i, kept = si.select_top_k(qi, ki, w, TOPK, impl)
        out, lse = fused_attention(q, k, v, causal=True, implementation=impl,
                                   selected=words)
        kl = si.index_loss(qi, ki, w, q, k, lse, words, lse_i, D ** -0.5,
                           impl)
        weights = jnp.cos(jnp.arange(D, dtype=jnp.float32))
        return jnp.sum(out.astype(jnp.float32) * weights) + jnp.sum(kl), (
            words, kept, kl)
    return jax.jit(jax.value_and_grad(objective, argnums=tuple(range(6)),
                                      has_aux=True))


def check(s):
    args = operands(s, whole_numbers=True)
    (va, (words_a, kept_a, kl_a)), ga = step("pallas")(*args)
    (vb, (words_b, kept_b, kl_b)), gb = step("reference")(*args)
    gap = lambda x, y: float(jnp.max(jnp.abs(
        x.astype(jnp.float32) - y.astype(jnp.float32)))
        / (1e-9 + jnp.max(jnp.abs(y.astype(jnp.float32)))))
    print(json.dumps({
        "phase": "check", "s": s,
        "words_differ": int(jnp.sum(words_a != words_b)),
        "kept": [float(jnp.sum(kept_a)), float(jnp.sum(kept_b))],
        "kl": [float(kl_a[0]), float(kl_b[0])],
        "value": [float(va), float(vb)],
        "grad_gap": dict(zip(("q", "k", "v", "qi", "ki", "w"),
                             (gap(x, y) for x, y in zip(ga, gb)))),
    }), flush=True)


def clock(fn, *args, repeat=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / repeat


def timed(s):
    q, k, v, qi, ki, w = operands(s, whole_numbers=False)
    select = jax.jit(lambda qi, ki, w: si.select_top_k(qi, ki, w, TOPK,
                                                       "pallas"))
    words, lse_i, kept = select(qi, ki, w)
    attn = jax.jit(lambda q, k, v, words: fused_attention(
        q, k, v, causal=True, implementation="pallas", selected=words))
    out, lse = attn(q, k, v, words)
    attn_grad = jax.jit(jax.grad(lambda q, k, v, words: jnp.sum(
        attn(q, k, v, words)[0].astype(jnp.float32)), argnums=(0, 1, 2)))
    plain = jax.jit(lambda q, k, v: fused_attention(
        q, k, v, causal=True, implementation="pallas"))
    plain_grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        plain(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))
    loss = jax.jit(jax.value_and_grad(
        lambda qi, ki, w, q, k, lse, words, lse_i: jnp.sum(si.index_loss(
            qi, ki, w, q, k, lse, words, lse_i, D ** -0.5, "pallas")),
        argnums=(0, 1, 2)))
    pairs = s * (s + 1) / 2
    print(json.dumps({
        "phase": "time", "s": s, "device": jax.devices()[0].device_kind,
        "kept_share": float(jnp.sum(kept)) / pairs,
        "ties": float(jnp.sum(jnp.maximum(kept - TOPK, 0))),
        "select_ms": clock(select, qi, ki, w),
        "flash_sel_fwd_ms": clock(attn, q, k, v, words),
        "flash_sel_fwd_bwd_ms": clock(attn_grad, q, k, v, words),
        "flash_causal_fwd_ms": clock(plain, q, k, v),
        "flash_causal_fwd_bwd_ms": clock(plain_grad, q, k, v),
        "index_loss_ms": clock(loss, qi, ki, w, q, k, lse, words, lse_i),
    }), flush=True)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", type=int, default=4096)
    ap.add_argument("--time", type=int, default=16384)
    a = ap.parse_args()
    if a.check:
        check(a.check)
    if a.time:
        timed(a.time)
