#!/usr/bin/env python3
"""Time megablox's three grouped-matmul kernels apart, by tile, at the expert
cells' shapes, alone on the chip.

    python3 tools/gmm_tile_sweep.py [--shape mellum2] [--sizes uneven]
        [--kernel tgmm] [--steps 10]
    JAX_PLATFORMS=cpu python3 tools/gmm_tile_sweep.py --rehearse

For each shape (a rank's row buffer sorted into groups, the two products of a
gated expert: ``in`` ``[rows, d] x [d, 2 w]`` and ``out`` ``[rows, w] x [w,
d]``), each of the three kernels a product runs in a training step (``gmm``
forward, ``gmm_t`` the rows' gradient: the same kernel with the weights
transposed, so its contraction is the forward's columns, ``tgmm`` the
weights' gradient) and each ``(rows, contraction, columns)`` tile of
:func:`candidates`, the host's clock round ``steps`` calls that end in
``block_until_ready`` after one warm-up call; the tile
``models/moe.py:gmm_tile`` chooses is marked ``"rule": true`` and the one
tile of PRs 26-37, (256, 1024, 512), ``"was": true``. ``--sizes even``
divides the live rows evenly over the groups; ``uneven`` draws them as the
cells' routers leave them (``SHAPES``: the fullest group over the mean, the
share of the buffer that is live). ``--rehearse`` times nothing: it compiles
every candidate for a described v5e and says which Mosaic refuses (VMEM). A
time comes from a chip run only: without ``--rehearse`` it stops where jax
finds no TPU. ``gmm_tile``'s caps were read from this tool's lines
(``tools/gmm_tile_sweep_pr38.jsonl``).
"""

from __future__ import annotations

import argparse
import itertools
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

from deeplearning_cfn_tpu.models import moe  # noqa: E402

# name -> (buffer rows, live rows, groups, d_model, expert width, fullest
# group's rows over the mean group's): what a traced step of each cell shows
# (PERF.md section 5).
SHAPES = {
    # A rank of the Mellum2 cell: 32,768 tokens x 8 choices / 4 ranks live
    # in the usual buffer of twice that.
    "mellum2": (131072, 65536, 16, 2304, 896, 2.9),
    # Laguna's cell: 8,192 tokens x 8 choices x 32 / 256 experts, a seeded
    # router far from uniform.
    "laguna": (16384, 8192, 32, 2048, 512, 6.0),
    # ZAYA1's cell: one buffer of every pair, half of them the held half's,
    # the balancing bias holding an expert at ~510 rows.
    "zaya1": (8192, 4080, 8, 2048, 2048, 1.2),
}
KERNELS = ("gmm", "gmm_t", "tgmm")
WAS = (256, 1024, 512)


def group_sizes(live: int, groups: int, fullest_over_mean: float,
                even: bool) -> np.ndarray:
    """``groups`` row counts that sum to ``live``: equal, or falling off
    geometrically so that the fullest is ``fullest_over_mean`` times the
    mean, in a fixed shuffled order."""
    if even:
        share = np.full(groups, 1.0 / groups)
    else:
        lo, hi = 0.0, 50.0
        for _ in range(60):       # the decay that gives the ratio
            a = (lo + hi) / 2
            w = np.exp(-a * np.arange(groups) / (groups - 1))
            lo, hi = (a, hi) if w[0] / w.mean() < fullest_over_mean \
                else (lo, a)
        share = np.random.default_rng(0).permutation(w / w.sum())
    sizes = np.floor(share * live).astype(np.int32)
    sizes[np.argmax(sizes)] += live - sizes.sum()
    return sizes


def was(m: int, k: int, n: int):
    """The one tile of PRs 26-37, clipped to what a kernel is asked."""
    return min(WAS[0], m), min(WAS[1], k), min(WAS[2], n)


def _divisors(x: int):
    """The lane-tile multiples that divide ``x``, from 512 up (the whole of
    a narrower ``x``)."""
    return [t for t in range(512, x + 1, 128) if x % t == 0] or [x]


def candidates(kernel: str, m: int, k: int, n: int, groups: int):
    """The tiles worth timing for ``kernel`` asked at ``(m, k, n)``: row
    tiles of 256 and 512 (1024 too where rows are ``tgmm``'s contraction),
    contraction and column tiles that divide, under 14 MiB of the rule's
    own reckoning (two over its budget, to see the edge); with the old tile
    and the rule's."""
    rows = (256, 512, 1024) if kernel == "tgmm" else (256, 512)
    found = [t for t in itertools.product(rows, _divisors(k), _divisors(n))
             if m % t[0] == 0
             and moe.gmm_tile_vmem(kernel, *t) <= 14 * 2 ** 20]
    for extra in (was(m, k, n), moe.gmm_tile(kernel, m, k, n, groups)):
        if extra not in found:
            found.append(extra)
    return found


def kernel_call(kernel: str, tile, interpret: bool = False):
    """``kernel`` of the product ``lhs [m, k] x rhs [groups, k, n]`` with
    cotangent ``grad [m, n]``, as ``models/moe.py`` calls it."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    if kernel == "gmm":
        return lambda lhs, rhs, grad, sizes: gmm(
            lhs, rhs, sizes, lhs.dtype, tile, interpret=interpret)
    if kernel == "gmm_t":
        return lambda lhs, rhs, grad, sizes: gmm(
            grad, rhs, sizes, lhs.dtype, tile, transpose_rhs=True,
            interpret=interpret)
    return lambda lhs, rhs, grad, sizes: tgmm(
        lhs.swapaxes(0, 1), grad, sizes, rhs.dtype, tile,
        num_actual_groups=rhs.shape[0], interpret=interpret)


def asked_at(kernel: str, m: int, k: int, n: int):
    """The ``(m, k, n)`` a kernel of the product ``[m, k] x [k, n]`` sees:
    the backward ``gmm`` contracts over the forward's columns."""
    return (m, n, k) if kernel == "gmm_t" else (m, k, n)


def timed(fn, args, steps):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / steps


def cases(names, kernels):
    for name in names:
        rows, live, groups, d, w, ratio = SHAPES[name]
        for product, (k, n) in (("in", (d, 2 * w)), ("out", (w, d))):
            for kernel in kernels:
                at = asked_at(kernel, rows, k, n)
                for tile in candidates(kernel, *at, groups):
                    yield name, product, kernel, (rows, k, n), at, tile


def line_of(name, product, kernel, mkn, at, tile, groups):
    return {"shape": name, "product": product, "kernel": kernel,
            "m": mkn[0], "k": mkn[1], "n": mkn[2], "groups": groups,
            "tile": list(tile), "was": tile == was(*at),
            "rule": tile == moe.gmm_tile(kernel, *at, groups),
            "vmem_mib": round(moe.gmm_tile_vmem(kernel, *tile) / 2 ** 20, 2)}


def rehearse(names, kernels, say):
    """Every candidate compiled for a described v5e: what Mosaic refuses."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    for name, product, kernel, (m, k, n), at, tile in cases(names, kernels):
        groups = SHAPES[name][2]
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
            s, dt, sharding=chip)
        line = line_of(name, product, kernel, (m, k, n), at, tile, groups)
        try:
            jax.jit(kernel_call(kernel, tile)).lower(
                shape((m, k)), shape((groups, k, n)), shape((m, n)),
                shape((groups,), jnp.int32)).compile()
            line["compiles"] = True
        except Exception as e:  # a tile Mosaic refuses
            line["compiles"] = False
            line["error"] = repr(e)[:200]
        say(line)


def measure(names, kernels, sizes_kinds, steps, say):
    key = jax.random.PRNGKey(0)
    made = {}
    for name, product, kernel, (m, k, n), at, tile in cases(names, kernels):
        _, live, groups, _, _, ratio = SHAPES[name]
        if (name, product) not in made:
            made.clear()
            made[name, product] = (
                jax.random.normal(key, (m, k), jnp.bfloat16),
                jax.random.normal(key, (groups, k, n), jnp.bfloat16) * 0.02,
                jax.random.normal(key, (m, n), jnp.bfloat16))
        fn = jax.jit(kernel_call(kernel, tile))
        for kind in sizes_kinds:
            sizes = group_sizes(live, groups, ratio, kind == "even")
            line = dict(line_of(name, product, kernel, (m, k, n), at, tile,
                                groups), sizes=kind, live=int(sizes.sum()),
                        fullest=int(sizes.max()))
            try:
                line["ms"] = timed(
                    fn, (*made[name, product], jnp.asarray(sizes)), steps)
                line["tflops"] = 2.0 * live * k * n / line["ms"] / 1e9
            except Exception as e:  # a tile Mosaic refuses
                line["error"] = repr(e)[:200]
            say(line)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--out", default=os.path.join(
        _ROOT, "chiprun_out", "gmm_tile_sweep.jsonl"))
    ap.add_argument("--shape", action="append", choices=sorted(SHAPES))
    ap.add_argument("--kernel", action="append", choices=KERNELS)
    ap.add_argument("--sizes", choices=("even", "uneven", "both"),
                    default="both")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if not args.rehearse and jax.default_backend() != "tpu":
        sys.exit("gmm_tile_sweep: no TPU here; a time comes only from a "
                 "chip run")
    names = args.shape or sorted(SHAPES)
    kernels = args.kernel or KERNELS
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        def say(line):
            text = json.dumps(line)
            print(text, flush=True)
            fh.write(text + "\n")
            fh.flush()

        if args.rehearse:
            say({"rehearsal": "compiled for a described v5e, nothing timed"})
            rehearse(names, kernels, say)
            return
        say({"device": jax.devices()[0].device_kind, "steps": args.steps})
        measure(names, kernels, ("even", "uneven") if args.sizes == "both"
                else (args.sizes,), args.steps, say)


if __name__ == "__main__":
    main()
