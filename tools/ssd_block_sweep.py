#!/usr/bin/env python3
"""Time the chunked state-space scan's two Pallas kernels (``ops/ssd.py``:
``ssd_fwd``, ``ssd_bwd``) by block, alone on the chip, beside the einsums.

    python3 tools/ssd_block_sweep.py [--steps 20] [--heads 8,16,32]
        [--out chiprun_out/ssd_block_sweep.jsonl]
    JAX_PLATFORMS=cpu python3 tools/ssd_block_sweep.py --rehearse

At the shape of ``granite4_h_micro_train_8k``'s mixers (bfloat16 ``x [1,
8192, 64, 64]``, a state of 128 in one group, chunks of 256, the decay rates
and step sizes of ``models/ssm.py:head_constants``) and for each number of
heads a grid step holds: the host's clock round ``steps`` calls that end
in ``block_until_ready`` after one warm-up call, of the forward alone
(``fwd_ms``) and of the forward that keeps the states with the backward
(``grad_ms``: what a recomputed block runs in the backward pass), the same
two from the profiler's trace as device time a call and the part of it in
the two kernels (``*_device_ms``, ``*_kernel_ms``), and the largest gap of
the result and of every gradient to the einsums' on the same inputs, over
the einsums' largest value. The block ``ops/ssd.py`` chooses is
marked ``"rule": true``; the line ``"blocks": "xla"`` is the einsums.
``--rehearse`` times nothing: it compiles every candidate for a described
v5e and says which Mosaic refuses. A time comes from a chip run only:
without ``--rehearse`` it stops where jax finds no TPU. The lines the block
was read from are ``tools/ssd_block_sweep_pr42.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from deeplearning_cfn_tpu.models.ssm import head_constants  # noqa: E402
from deeplearning_cfn_tpu.ops import ssd  # noqa: E402

BATCH, SEQ, HEADS, HEAD_DIM, STATE, GROUPS, CHUNK = 1, 8192, 64, 64, 128, 1, \
    256
NAMES = ("y", "dx", "ddt", "da", "db", "dc")
TRACE_DIR = os.path.join(_ROOT, ".bench_trace", "ssd_block_sweep")


def shapes():
    return [((BATCH, SEQ, HEADS, HEAD_DIM), jnp.bfloat16),
            ((BATCH, SEQ, HEADS), jnp.float32), ((HEADS,), jnp.float32),
            ((BATCH, SEQ, GROUPS, STATE), jnp.bfloat16),
            ((BATCH, SEQ, GROUPS, STATE), jnp.bfloat16)]


def inputs(seed: int):
    """What a mixer hands its scan at seeded weights: ``x``, ``B``, ``C``
    after silu of a unit normal, the step sizes round each head's own."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    a_h, c_h = head_constants(HEADS)
    act = lambda k, s: jax.nn.silu(jax.random.normal(k, s)) \
        .astype(jnp.bfloat16)
    dt = jax.nn.softplus(c_h + 0.5 * jax.random.normal(
        ks[1], (BATCH, SEQ, HEADS)))
    return (act(ks[0], shapes()[0][0]), dt, -jnp.asarray(a_h),
            act(ks[2], shapes()[3][0]), act(ks[3], shapes()[4][0]))


def functions(blocks):
    """``(forward, forward and backward)`` at ``blocks``; ``None`` is the
    einsums."""
    kw = dict(implementation="reference") if blocks is None \
        else dict(implementation="pallas", block_heads=blocks)
    scan = lambda *t: ssd.ssd_scan(*t, chunk=CHUNK, **kw)

    def both(x, dt, a, b, c, w):
        y, back = jax.vjp(scan, x, dt, a, b, c)
        return (y,) + back(w)

    return jax.jit(scan), jax.jit(both)


def timed(fn, args, steps):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / steps


def device_ms(fn, args, steps, trace_dir):
    """``(ms of device time a call, ms of it in the scan's two kernels)``
    from the profiler's trace of ``steps`` calls: what the host's clock
    cannot tell from the dozen small operations beside the kernels."""
    import glob
    import shutil

    from jax.profiler import ProfileData

    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    try:
        for _ in range(steps):
            out = fn(*args)
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    total = kernels = 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                total += ev.duration_ns
                if "ssd_" in ev.name:
                    kernels += ev.duration_ns
    shutil.rmtree(trace_dir, ignore_errors=True)
    return total / 1e6 / steps, kernels / 1e6 / steps


def candidates(heads):
    unit = ssd._unit(HEAD_DIM)
    return [hb for hb in heads
            if (HEADS // GROUPS) % hb == 0 and hb % unit == 0]


def rehearse(cands, say):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes()]
    for blocks in cands:
        line = {"blocks": blocks, "rehearsal": True}
        for name, fn, more in zip(("fwd", "grad"), functions(blocks),
                                  ([], args[:1])):
            try:
                fn.lower(*args, *more).compile()
                line[name] = "compiles"
            except Exception as e:  # what Mosaic refuses, in its words
                line[name] = str(e).splitlines()[0][:200]
        say(line)


def measure(cands, steps, seed, say):
    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU: a time comes from a chip run only")
    args = inputs(seed)
    w = jax.random.normal(jax.random.PRNGKey(seed + 1), args[0].shape) \
        .astype(jnp.bfloat16)
    rule = ssd.head_block(HEADS, GROUPS, HEAD_DIM)
    want = None
    for blocks in [None] + cands:
        fwd, both = functions(blocks)
        line = {"blocks": "xla" if blocks is None else blocks,
                "rule": blocks == rule, "steps": steps, "seed": seed}
        try:
            got = [np.asarray(t, np.float32) for t in both(*args, w)]
            line["fwd_ms"] = timed(fwd, args, steps)
            line["grad_ms"] = timed(both, (*args, w), steps)
            for name, fn, more in (("fwd", fwd, ()), ("grad", both, (w,))):
                line[f"{name}_device_ms"], line[f"{name}_kernel_ms"] = \
                    device_ms(fn, (*args, *more), steps, TRACE_DIR)
        except Exception as e:
            line["failed"] = str(e).splitlines()[0][:200]
            say(line)
            continue
        if want is None:
            want = got
        line["gap"] = {n: float(np.max(np.abs(g - t)) / np.max(np.abs(t)))
                       for n, g, t in zip(NAMES, got, want)}
        say(line)


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--heads", default="8,16,32,64")
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    ints = lambda text: [int(v) for v in text.split(",")]
    cands = candidates(ints(args.heads))
    out = open(args.out, "w") if args.out else None

    def say(line):
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    if args.rehearse:
        rehearse(cands, say)
    else:
        say({"device": jax.devices()[0].device_kind, "steps": args.steps,
             "shape": [BATCH, SEQ, HEADS, HEAD_DIM, STATE, GROUPS, CHUNK]})
        measure(cands, args.steps, args.seed, say)


if __name__ == "__main__":
    main()
