#!/usr/bin/env python3
"""Time the causal convolution's two Pallas kernels (``ops/conv.py``:
``causal_conv_fwd``, ``causal_conv_bwd``) by block, alone on the chip, beside
XLA's shifted multiply-adds.

    python3 tools/conv_block_sweep.py [--steps 20]
        [--blocks 512:16:256,256:16:256] [--out chiprun_out/conv_sweep.jsonl]
    JAX_PLATFORMS=cpu python3 tools/conv_block_sweep.py --rehearse

At the shape of ``granite4_h_micro_train_8k``'s mixers (bfloat16 ``xBC [1,
8192, 4352]`` cut at 4096 and 4224, 4 taps) and for each block (tokens a grid
step : rows a chunk : lanes a chunk): the host's clock round ``steps`` calls
that end in ``block_until_ready`` after one warm-up call, of the forward
alone (``fwd_ms``) and of the backward alone (``bwd_ms``: its residuals are
the inputs, so autodiff runs no forward with it), the same two from the
profiler's trace as device time a call and the part of it in the kernels
(``*_device_ms``, ``*_kernel_ms``), and the largest gap of the three results
and the three gradients to XLA's on the same inputs, over XLA's largest
value. The block ``ops/conv.py`` chooses is marked ``"rule": true``; the line
``"block": "xla"`` is ``CausalConv``'s shifted form with silu, the cast and
the split. The least a pass can take is its bytes over the chip's 819 GB/s:
143 MB forward (0.174 ms), 214 MB backward (0.261 ms). ``--rehearse`` times
nothing: it compiles every candidate for a described v5e and says which
Mosaic refuses. A time comes from a chip run only: without ``--rehearse`` it
stops where jax finds no TPU. The lines the block was read from are
``tools/conv_block_sweep_pr45.jsonl``.
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

from deeplearning_cfn_tpu.models.ssm import CausalConv, conv_gain  # noqa: E402
from deeplearning_cfn_tpu.ops import conv  # noqa: E402

BATCH, SEQ, CHANNELS, TAPS, SPLITS = 1, 8192, 4352, 4, (4096, 4224)
NAMES = ("x", "b", "c", "dx", "dkernel", "dbias")
TRACE_DIR = os.path.join(_ROOT, ".bench_trace", "conv_block_sweep")
BLOCKS = "512:32:256,256:32:256,128:32:256,512:16:256,512:64:256," \
    "512:32:128,512:32:512,512:16:512,512:16:128"


def shapes():
    """``xBC``, ``conv/kernel``, ``conv/bias`` and the three cotangents."""
    widths = [width for _, width in conv._segments(CHANNELS, SPLITS)]
    return [((BATCH, SEQ, CHANNELS), jnp.bfloat16),
            ((TAPS, CHANNELS), jnp.float32), ((CHANNELS,), jnp.float32)] \
        + [((BATCH, SEQ, width), jnp.bfloat16) for width in widths]


def inputs(seed: int):
    """What a mixer hands its convolution at seeded weights: a projection of
    unit scale, Xavier-uniform taps, a small bias; unit cotangents."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    (x, k, b, *cts) = shapes()
    return [jax.random.normal(ks[0], x[0]).astype(x[1]),
            jax.nn.initializers.xavier_uniform()(ks[1], k[0]),
            0.1 * jax.random.normal(ks[2], b[0])] \
        + [jax.random.normal(key, s).astype(d)
           for key, (s, d) in zip(ks[3:], cts)]


def functions(block):
    """``(forward, backward)`` at ``block``; ``None`` is XLA's form."""
    if block is None:
        fwd = lambda x, kernel, bias: CausalConv(TAPS).apply(
            {"params": {"kernel": kernel, "bias": bias}}, x, SPLITS,
            "reference")
    else:
        fwd = lambda x, kernel, bias: conv.causal_conv_silu(
            x, conv_gain(TAPS, CHANNELS) * kernel, bias, SPLITS, block=block)

    def bwd(x, kernel, bias, *cts):
        return jax.vjp(fwd, x, kernel, bias)[1](tuple(cts))

    return jax.jit(fwd), jax.jit(bwd)


def timed(fn, args, steps):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / steps


def device_ms(fn, args, steps, trace_dir):
    """``(ms of device time a call, ms of it in the two kernels)`` from the
    profiler's trace of ``steps`` calls."""
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
                if "causal_conv_" in ev.name:
                    kernels += ev.duration_ns
    shutil.rmtree(trace_dir, ignore_errors=True)
    return total / 1e6 / steps, kernels / 1e6 / steps


def rehearse(cands, say):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    args = [jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes()]
    for block in cands:
        line = {"block": ":".join(map(str, block)), "rehearsal": True}
        for name, fn, n in zip(("fwd", "bwd"), functions(block), (3, 6)):
            try:
                fn.lower(*args[:n]).compile()
                line[name] = "compiles"
            except Exception as e:  # what Mosaic refuses, in its words
                line[name] = str(e).splitlines()[0][:200]
        say(line)


def measure(cands, steps, seed, say):
    if jax.default_backend() != "tpu":
        raise SystemExit("no TPU: a time comes from a chip run only")
    args = inputs(seed)
    rule = (conv.token_block(SEQ, CHANNELS, 2), conv._ROWS, conv._CHUNK_LANES)
    want = None
    for block in [None] + cands:
        fwd, bwd = functions(block)
        line = {"block": "xla" if block is None else
                ":".join(map(str, block)), "rule": block == rule,
                "steps": steps, "seed": seed}
        try:
            got = [np.asarray(t, np.float32)
                   for t in (*fwd(*args[:3]), *bwd(*args))]
            for name, fn, n in (("fwd", fwd, 3), ("bwd", bwd, 6)):
                line[f"{name}_ms"] = timed(fn, args[:n], steps)
                line[f"{name}_device_ms"], line[f"{name}_kernel_ms"] = \
                    device_ms(fn, args[:n], steps, TRACE_DIR)
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
    parser.add_argument("--blocks", default=BLOCKS)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--out", default="")
    args = parser.parse_args()
    cands = [tuple(int(v) for v in text.split(":"))
             for text in args.blocks.split(",")]
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
             "shape": [BATCH, SEQ, CHANNELS, TAPS, *SPLITS]})
        measure(cands, args.steps, args.seed, say)


if __name__ == "__main__":
    main()
