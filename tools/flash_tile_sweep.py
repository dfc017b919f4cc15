#!/usr/bin/env python3
"""Sweep the flash kernels' tile plan on the chip, each kernel apart.

    python3 tools/flash_tile_sweep.py [--steps 10] [--sq 1024] [--out chiprun_out/flash_sweep.jsonl]

For each shape and each ``(block_q, block_k, sub_q, sub_k)`` the three
kernels run ``--steps`` times under the profiler, and a kernel's time is the
mean device duration of the events that carry its name (``flash_fwd``,
``flash_bwd_dkdv``, ``flash_bwd_dq``). One JSON line per plan; the last
lines time the XLA path against the kernels at S = 512 by the host's clock.
``ops/attention.py:_tile_plan`` is fixed from this table (PERF.md, PR 25; its
windowed branch from the two shapes of ``laguna_xs2_train_4k``, ``--sq
4096``, PR 29). A chip run only: it stops where jax finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deeplearning_cfn_tpu.ops import attention as A  # noqa: E402
from harness import xplane  # noqa: E402

KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")


def _sq(block, subs):
    return [(block, block, s, s) for s in subs]


# (b, h, sq, sk, d), with K/V heads and a window where the call has them ->
# plans; every other output is compared with the first plan's (the single
# sub-tile the kernels had before PR 25; in the windowed call the plan of
# before PR 29).
SWEEP = [
    ((16, 12, 1024, 1024, 64), _sq(1024, (1024, 512, 256, 128)) + [
        (1024, 1024, 256, 512), (1024, 1024, 512, 256),
        (1024, 1024, 128, 256), (1024, 1024, 256, 128)]),
    ((1, 8, 2048, 2048, 128), _sq(1024, (1024, 512, 256, 128)) + [
        (1024, 1024, 256, 512), (2048, 2048, 256, 256),
        (2048, 2048, 512, 512)]),
    ((1, 12, 8192, 8192, 64), _sq(1024, (1024, 512, 256, 128)) + [
        (1024, 1024, 256, 512)]),
    ((2, 12, 1024, 2048, 64), _sq(1024, (1024, 512, 256, 128))),
    ((2, 12, 1000, 3000, 64), _sq(1024, (1024, 256, 128))),
    # laguna_xs2_train_4k's sliding layers (64 query heads over 8 K/V heads,
    # window 512) and its full ones (48 over 8).
    ((2, 64, 4096, 4096, 128, 8, 512), _sq(1024, (256, 128)) + [
        (1024, 1024, 256, 128), (1024, 512, 256, 256), (1024, 512, 256, 128),
        (512, 512, 512, 512), (512, 512, 256, 256), (512, 512, 256, 128),
        (512, 512, 128, 256), (512, 512, 128, 128), (2048, 1024, 256, 256),
        (1024, 1024, 128, 256), (1024, 1024, 128, 512), (1024, 1024, 64, 128),
        (1024, 1024, 128, 64), (2048, 1024, 128, 128),
        (2048, 2048, 128, 128)]),
    ((2, 48, 4096, 4096, 128, 8), _sq(1024, (1024, 256, 128))),
]


def _call(shape):
    """``(b, h, sq, sk, d, hk, window)`` of a SWEEP shape: as many K/V heads
    as query heads and no window where it names none."""
    b, h, sq, sk, d, *rest = shape
    return (b, h, sq, sk, d, rest[0] if rest else h,
            rest[1] if len(rest) > 1 else 0)


def _inputs(shape, seed=0):
    b, h, sq, sk, d, hk, _ = _call(shape)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda key, heads, s: jax.random.normal(key, (b, heads, s, d),
                                                 jnp.bfloat16)
    return (mk(keys[0], h, sq), mk(keys[1], hk, sk), mk(keys[2], hk, sk),
            mk(keys[3], h, sq))


def _kernel_ms(trace_dir, steps):
    trace = xplane.Trace.from_file(xplane.find_xplane(trace_dir), [])
    out = {}
    for kernel in KERNELS:
        rx = re.compile(rf"^{kernel}(\.\d+)?$")
        spent = [e - s for name, s, e in trace.ops[0] if rx.match(name)]
        if len(spent) != steps:
            raise RuntimeError(f"{kernel}: {len(spent)} events for {steps} "
                               f"calls; names seen: "
                               f"{sorted({n for n, _, _ in trace.ops[0]})}")
        out[kernel] = sum(spent) / steps / 1e6
    return out


def measure(shape, plan, steps, baseline):
    q, k, v, g = _inputs(shape)
    *_, sq, sk, d, _, window = _call(shape)
    scale = d ** -0.5

    def fwd(q, k, v):
        return A._flash_forward(q, k, v, None, True, scale,
                                return_stats=True, plan=plan, window=window)

    def bwd(q, k, v, out, lse, g):
        return A._flash_backward(q, k, v, out, lse, g, True, scale, False,
                                 plan=plan, window=window)

    t0 = time.perf_counter()
    fwd_c = jax.jit(fwd).lower(q, k, v).compile()
    out, lse = fwd_c(q, k, v)
    bwd_c = jax.jit(bwd).lower(q, k, v, out, lse, g).compile()
    grads = jax.block_until_ready(bwd_c(q, k, v, out, lse, g))
    compile_s = time.perf_counter() - t0
    results = (out, *grads)
    total, live, masked = A._subtile_counts(
        sq, sk, plan, A._schedule(sq, sk, plan, True, window=window))
    line = {"shape": list(shape), "plan": list(plan),
            "live": live / total, "masked": masked / total,
            # grid steps a query head, the dead ones, their wasted copies
            "grid": {kernel: A._grid_gauges(kernel, window)
                     for kernel in KERNELS},
            "compile_s": round(compile_s, 2)}
    # By the host's clock too (the whole jitted call: for the backward that
    # is both kernels and the delta beside them), should the trace fail.
    for name, call in (("fwd_call_ms", lambda: fwd_c(q, k, v)),
                       ("bwd_call_ms",
                        lambda: bwd_c(q, k, v, out, lse, g))):
        t0 = time.perf_counter()
        for _ in range(steps):
            r = call()
        jax.block_until_ready(r)
        line[name] = (time.perf_counter() - t0) / steps * 1e3
    trace_dir = tempfile.mkdtemp(prefix="flash_sweep_")
    try:
        with jax.profiler.trace(trace_dir):
            for _ in range(steps):
                o = fwd_c(q, k, v)
                r = bwd_c(q, k, v, out, lse, g)
            jax.block_until_ready((o, r))
        ms = _kernel_ms(trace_dir, steps)
        line.update({f"{kernel}_ms": ms[kernel] for kernel in KERNELS})
        line["backward_ms"] = ms["flash_bwd_dkdv"] + ms["flash_bwd_dq"]
    except (RuntimeError, xplane.TraceError) as e:
        line["trace_error"] = str(e)[:600]
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if baseline is not None:
        line["max_abs_gap"] = [
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(results, baseline)]
    return line, results


def short_seq(steps, say):
    """ROADMAP S6's row: the kernels against XLA's attention at S = 512,
    forward and backward together, by the host's clock."""
    shape = (32, 12, 512, 512, 64)
    q, k, v, g = _inputs(shape)
    for causal in (False, True):
        line = {"shape": list(shape), "causal": causal}
        for impl in ("pallas", "reference"):
            fn = jax.jit(jax.grad(
                lambda q, k, v: jnp.vdot(A.fused_attention(
                    q, k, v, causal=causal, implementation=impl), g),
                argnums=(0, 1, 2)))
            jax.block_until_ready(fn(q, k, v))
            t0 = time.perf_counter()
            for _ in range(steps):
                r = fn(q, k, v)
            jax.block_until_ready(r)
            line[f"{impl}_ms"] = (time.perf_counter() - t0) / steps * 1e3
        say(line)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(
        _ROOT, "chiprun_out", "flash_sweep.jsonl"))
    ap.add_argument("--sq", type=int, action="append",
                    help="only the shapes with this sq (repeatable)")
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        sys.exit("flash_tile_sweep: no TPU here; a time comes only from a "
                 "chip run")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        def say(line):
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")
            f.flush()

        say({"device": jax.devices()[0].device_kind, "steps": args.steps})
        for shape, plans in SWEEP:
            if args.sq and shape[2] not in args.sq:
                continue
            baseline = None
            for plan in plans:
                try:
                    line, results = measure(shape, plan, args.steps,
                                            baseline)
                except Exception as e:  # one plan the chip refuses: go on
                    say({"shape": list(shape), "plan": list(plan),
                         "error": f"{type(e).__name__}: {e}"[:600]})
                    continue
                baseline = baseline or results
                say(line)
        if not args.sq:
            short_seq(args.steps, say)


if __name__ == "__main__":
    main()
