#!/usr/bin/env python3
"""Time megablox's grouped matmul by tile at an expert layer's shapes, alone
on the chip.

    python3 tools/gmm_tile_sweep.py [--steps 10] [--shape mellum2]

For each shape (rows sorted into even groups, the two products of a gated
expert: ``[rows, d] x [d, 2 w]`` and ``[rows, w] x [w, d]``) and each
``(rows, contraction, columns)`` tile it times the forward kernel and the
backward pair (``gmm`` for the rows' gradient, ``tgmm`` for the weights')
through ``jax.grad``, the host's clock round ``steps`` calls that end in
``block_until_ready`` after one warm-up call. ``models/moe.py:_GMM_TILE``
was swept so for Laguna's power-of-two widths (PR 26); Mellum2's 2304 = 18 x
128 and 896 = 7 x 128 are the first widths no power of two divides, where a
tile that does not divide a dimension is padded and masked. A chip run only:
it stops where jax finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# name -> (rows, groups, d_model, expert width)
SHAPES = {
    # A rank of the Mellum2 cell: 32,768 tokens x 8 choices / 4 ranks.
    "mellum2": (65536, 16, 2304, 896),
    "laguna": (8192, 32, 2048, 512),
}
TILES = {
    "mellum2": (
        [(256, 1024, 512), (256, 768, 256), (256, 768, 896), (256, 1152, 896),
         (256, 2304, 256), (512, 768, 896), (512, 1152, 512),
         (256, 1152, 1792), (512, 1024, 512)],
        [(256, 896, 512), (256, 896, 768), (256, 896, 1152), (256, 896, 256),
         (512, 896, 768), (512, 896, 512), (256, 896, 2304)]),
    "laguna": ([(256, 1024, 512), (512, 1024, 512)],
               [(256, 512, 512), (256, 512, 1024)]),
}


def timed(fn, args, steps):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / steps


def measure(name, steps, say):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    rows, groups, d, w = SHAPES[name]
    key = jax.random.PRNGKey(0)
    sizes = jnp.full((groups,), rows // groups, jnp.int32)
    for which, (k, n), tiles in (("in", (d, 2 * w), TILES[name][0]),
                                 ("out", (w, d), TILES[name][1])):
        lhs = jax.random.normal(key, (rows, k), jnp.bfloat16)
        rhs = jax.random.normal(key, (groups, k, n), jnp.bfloat16) * 0.02
        flops = 2.0 * rows * k * n
        for tile in tiles:
            tile = (min(tile[0], rows), min(tile[1], k), min(tile[2], n))
            f = lambda a, b, t=tile: gmm(
                a, b, sizes, preferred_element_type=a.dtype, tiling=t)
            fwd = jax.jit(f)
            bwd = jax.jit(jax.grad(
                lambda a, b: jnp.sum(f(a, b).astype(jnp.float32)),
                argnums=(0, 1)))
            line = {"shape": name, "product": which, "k": k, "n": n,
                    "tile": list(tile)}
            try:
                line["fwd_ms"] = timed(fwd, (lhs, rhs), steps)
                # The gradient call runs the forward too.
                line["fwd_bwd_ms"] = timed(bwd, (lhs, rhs), steps)
                line["fwd_tflops"] = flops / line["fwd_ms"] / 1e9
                line["fwd_bwd_tflops"] = 3 * flops / line["fwd_bwd_ms"] / 1e9
            except Exception as e:  # a tile Mosaic refuses
                line["error"] = repr(e)[:300]
            say(line)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(
        _ROOT, "chiprun_out", "gmm_tile_sweep.jsonl"))
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("gmm_tile_sweep: no TPU here; a time comes only from a "
                 "chip run")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        def say(line):
            text = json.dumps(line)
            print(text, flush=True)
            fh.write(text + "\n")
            fh.flush()

        say({"device": jax.devices()[0].device_kind, "steps": args.steps})
        for name in args.shape or ["mellum2"]:
            measure(name, args.steps, say)


if __name__ == "__main__":
    main()
