#!/usr/bin/env python3
"""Time the rotary-position kernel against the plain form on the chip.

    python3 tools/rope_sweep.py [--steps 20] [--out chiprun_out/rope_sweep.jsonl]

For each of the Laguna cell's four shapes (q of a sliding and of a full
layer, k at both rope settings), forward (``[B,S,H,D]`` turned and given as
``[B,H,S,D]``) and backward (the cotangent brought back) run ``--steps`` times
under the profiler: ``apply_rope`` and the transpose as XLA compiles them,
then ``ops/rope.py``'s kernel at each (row block, heads a block). A time is
the device's busy time a call: the sum of the durations of every operation
the call put on the device. Beside it the least the chip's bandwidth allows:
the tensor read once and written once, and the two tables read once.
``ops/rope.py``'s blocks are fixed from this table (PERF.md, PR 27).

``--shape q_normed --shape k_normed`` (PR 49; the lines are kept as
``tools/rope_sweep_pr49.jsonl``): SDAR's and Keye's q and k of one layer,
``[1,16384,32|4,128]``, with the RMSNorm over each head before the turn.
First the pair as it stood (``rms_norm`` by XLA, then the kernel), then the
norm inside the kernel at each of ``NORMED_BLOCKS``, the mean over a row's
lanes taken both ways: a lane reduction (``xlu``, what ``ops/rope.py`` does)
and a product with a square of ``1/D`` on the MXU (``mxu``). ``unequal``
counts the results that differ from the pair's; the scale's gradient is
compared by its norm. A chip run only: it stops where jax finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deeplearning_cfn_tpu.models.lm import (  # noqa: E402
    _LAGUNA_XS2, _SDAR_30B_A3B)
from deeplearning_cfn_tpu.models.transformer import (  # noqa: E402
    apply_rope, rms_norm)
from deeplearning_cfn_tpu.ops import rope as R  # noqa: E402
from harness import device, xplane  # noqa: E402

Z = _LAGUNA_XS2
# name -> ([B, S, H, D], the layer's rope)
SHAPES = {
    "q_sliding": ((2, 4096, Z["sliding_heads"], 128), Z["sliding_rope"]),
    "q_full": ((2, 4096, Z["full_heads"], 128), Z["full_rope"]),
    "k_sliding": ((2, 4096, Z["kv_heads"], 128), Z["sliding_rope"]),
    "k_full": ((2, 4096, Z["kv_heads"], 128), Z["full_rope"]),
}
BLOCKS = [(rows, heads) for rows in (256, 512, 1024, 2048)
          for heads in (1, 2, 4, 8)]
N = _SDAR_30B_A3B
# name -> [B, S, H, D] of a layer's q and k under SDAR's and Keye's norm.
NORMED = {"q_normed": (1, 16384, N["heads"], 128),
          "k_normed": (1, 16384, N["kv_heads"], 128)}
NORMED_BLOCKS = [(512, 8), (512, 4), (256, 8), (1024, 4)]
_EPS = 1e-6


def _lane_mean_mxu(x):
    """``ops/rope.py:_lane_mean`` as a product on the MXU, float32 passes."""
    d = x.shape[-1]
    return jnp.dot(x, jnp.full((d, d), 1.0 / d, jnp.float32),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


LANE_MEANS = {"xlu": R._lane_mean, "mxu": _lane_mean_mxu}


def _busy_ms(fn, args, steps):
    """Device time a call of the compiled ``fn``: every operation's duration
    in a trace of ``steps`` calls, summed, over ``steps``."""
    jax.block_until_ready(fn(*args))
    trace_dir = tempfile.mkdtemp(prefix="rope_sweep_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                r = fn(*args)
            jax.block_until_ready(r)
        trace = xplane.Trace.from_file(xplane.find_xplane(trace_dir), [])
        return sum(trace.op_seconds(0).values()) / steps * 1e3
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _pair(turn, x, g):
    """The compiled forward and backward of ``turn``; the turn is linear, so
    the backward alone holds none of the forward."""
    fwd = jax.jit(turn).lower(x).compile()
    bwd = jax.jit(lambda g: jax.vjp(turn, x)[1](g)[0]).lower(g).compile()
    return fwd, bwd


def measure(name, steps, say, blocks):
    shape, rope = SHAPES[name]
    b, s, h, d = shape
    kx, kg = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, shape, jnp.bfloat16)
    g = jax.random.normal(kg, (b, h, s, d), jnp.bfloat16)
    cos, sin = rope.tables(s, d)
    peak = device.peaks_of(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    floor_ms = (2 * x.size * 2 + 2 * s * d * 4) / peak * 1e3
    head = {"shape": name, "dims": list(shape), "rot": 2 * cos.shape[1],
            "floor_ms": floor_ms}

    def plain(x):
        return apply_rope(x, rope).transpose(0, 2, 1, 3)

    fwd, bwd = _pair(plain, x, g)
    want = fwd(x), bwd(g)
    say({**head, "path": "xla", "fwd_ms": _busy_ms(fwd, (x,), steps),
         "bwd_ms": _busy_ms(bwd, (g,), steps)})
    for block in blocks:
        line = {**head, "path": "kernel", "rows": block[0],
                "heads": block[1]}
        try:
            # The kernel reads and writes the projection's [B,S,H*D].
            flat = x.reshape(b, s, h * d)
            fwd, bwd = _pair(lambda x: R.rotate_to_heads(
                x, cos, sin, d, blocks=block), flat, g)
            got = fwd(flat), bwd(g).reshape(shape)
            line["unequal"] = [int((a != b).sum()) for a, b in zip(got, want)]
            line["fwd_ms"] = _busy_ms(fwd, (flat,), steps)
            line["bwd_ms"] = _busy_ms(bwd, (g,), steps)
        except Exception as e:  # a block the chip refuses: go on
            line["error"] = f"{type(e).__name__}: {e}"[:400]
        say(line)


def measure_normed(name, steps, say):
    shape = NORMED[name]
    b, s, h, d = shape
    kx, kg, ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (b, s, h * d), jnp.bfloat16)
    g = jax.random.normal(kg, (b, h, s, d), jnp.bfloat16)
    scale = 1.0 + 0.1 * jax.random.normal(ks, (d,), jnp.float32)
    cos, sin = N["rope"].tables(s, d)
    peak = device.peaks_of(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    tables = 2 * s * d * 4
    # Forward: x read, the heads written. Backward: g and x read, dx written.
    head = {"shape": name, "dims": list(shape),
            "floor_fwd_ms": (2 * x.size * 2 + tables) / peak * 1e3,
            "floor_bwd_ms": (3 * x.size * 2 + tables) / peak * 1e3}

    def pair(x, scale):
        normed = rms_norm(x.reshape(shape), scale, _EPS, x.dtype)
        return R.rotate_to_heads(normed.reshape(x.shape), cos, sin, d)

    def compiled(turn):
        fwd = jax.jit(turn).lower(x, scale).compile()
        bwd = jax.jit(lambda x, scale, g: jax.vjp(turn, x, scale)[1](g)) \
            .lower(x, scale, g).compile()
        return fwd, bwd

    fwd, bwd = compiled(pair)
    want = (fwd(x, scale), *bwd(x, scale, g))
    say({**head, "path": "xla_norm+kernel",
         "fwd_ms": _busy_ms(fwd, (x, scale), steps),
         "bwd_ms": _busy_ms(bwd, (x, scale, g), steps)})
    for mean, lane_mean in LANE_MEANS.items():
        for block in NORMED_BLOCKS:
            line = {**head, "path": "fused", "lane_mean": mean,
                    "rows": block[0], "heads": block[1]}
            R._lane_mean = lane_mean
            try:
                fwd, bwd = compiled(lambda x, scale: R.rotate_to_heads(
                    x, cos, sin, d, blocks=block, norm=(scale, _EPS)))
                got = (fwd(x, scale), *bwd(x, scale, g))
                line["unequal"] = [int((a != b).sum())
                                   for a, b in zip(got[:2], want[:2])]
                line["dscale_gap"] = float(
                    jnp.linalg.norm(got[2] - want[2])
                    / jnp.linalg.norm(want[2]))
                line["fwd_ms"] = _busy_ms(fwd, (x, scale), steps)
                line["bwd_ms"] = _busy_ms(bwd, (x, scale, g), steps)
            except Exception as e:  # a block the chip refuses: go on
                line["error"] = f"{type(e).__name__}: {e}"[:400]
            finally:
                R._lane_mean = LANE_MEANS["xlu"]
            say(line)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default=os.path.join(
        _ROOT, "chiprun_out", "rope_sweep.jsonl"))
    ap.add_argument("--shape", action="append",
                    choices=sorted({**SHAPES, **NORMED}),
                    help="only this shape (repeatable); default: Laguna's "
                    "four")
    ap.add_argument("--rows", type=int, action="append",
                    help="only these row blocks (repeatable)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("rope_sweep: no TPU here; a time comes only from a chip run")
    blocks = [b for b in BLOCKS if not args.rows or b[0] in args.rows]
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        def say(line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")
            f.flush()

        say({"device": jax.devices()[0].device_kind, "steps": args.steps})
        for name in args.shape or SHAPES:
            if name in NORMED:
                measure_normed(name, args.steps, say)
            else:
                measure(name, args.steps, say, blocks)


if __name__ == "__main__":
    main()
