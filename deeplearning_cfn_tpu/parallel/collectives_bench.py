"""Collectives microbenchmark — the rebuild's `nccl-tests` (SURVEY.md §3.3).

The reference stack proved its interconnect with nccl-tests (allreduce
bus-bandwidth sweeps over EFA) before burning GPU-hours. The TPU equivalent
measures the XLA collectives the training step actually uses — psum
(allreduce), all_gather, ppermute (the ring primitive), reduce_scatter
(psum_scatter) — over the mesh's ICI links, via shard_map so the collective
is explicit rather than compiler-inferred.

Reported number is algorithmic bus bandwidth (bytes moved per rank per
second, with the standard 2(n-1)/n allreduce correction) so results are
comparable with nccl-tests' busbw column.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..runtime.profiling import StepTimer


def _busbw_factor(op: str, n: int) -> float:
    """Bytes-on-wire per rank as a multiple of the per-rank INPUT buffer,
    ring-algorithm counts matching nccl-tests' busbw conventions:
    allreduce 2(n-1)/n, reduce-scatter (n-1)/n; all-gather's per-rank input
    is one shard and it receives the other n-1 shards."""
    if op == "psum":
        return 2.0 * (n - 1) / n
    if op == "all_gather":
        return float(n - 1)
    if op == "psum_scatter":
        return (n - 1) / n
    return 1.0  # ppermute: each rank sends its shard once


def run_collectives_bench(
    mesh: Optional[Mesh] = None,
    axis: str = "data",
    size_mb: float = 64.0,
    ops: Optional[List[str]] = None,
    iters: int = 10,
    warmup: int = 3,
) -> List[Dict]:
    """Time each collective over ``axis``; returns one record per op."""
    if mesh is None:
        from .mesh import build_mesh

        mesh = build_mesh()
    n = mesh.shape[axis]
    ops = ops or ["psum", "all_gather", "psum_scatter", "ppermute"]
    elems = int(size_mb * 1e6 / 4)
    # Divisible by n² : the global buffer shards n ways, and reduce-scatter
    # splits each rank's LOCAL shard n ways again.
    elems = max(n * n, elems - elems % (n * n))
    results = []
    spec = P(axis)
    x = jax.device_put(
        jnp.arange(elems, dtype=jnp.float32),
        NamedSharding(mesh, spec))

    perm = [(i, (i + 1) % n) for i in range(n)]
    fns = {
        "psum": lambda x: jax.lax.psum(x, axis),
        "all_gather": lambda x: jax.lax.all_gather(x, axis, tiled=True),
        "psum_scatter": lambda x: jax.lax.psum_scatter(x, axis, tiled=True),
        "ppermute": lambda x: jax.lax.ppermute(x, axis, perm),
    }
    for op in ops:
        fn = fns[op]

        @functools.partial(
            jax.jit,
            out_shardings=NamedSharding(
                mesh, P() if op == "all_gather" else spec))
        @functools.partial(shard_map, mesh=mesh, in_specs=(spec,),
                           out_specs=P() if op == "all_gather" else spec,
                           check_vma=False)
        def timed(x, fn=fn):
            return fn(x)

        timer = StepTimer(warmup=warmup)
        out = timed(x)  # compile
        jax.block_until_ready(out)
        for _ in range(warmup + iters):
            timer.start()
            out = timed(x)
            timer.stop(out)
        mean_s = timer.summary()["mean_step_s"]
        # Per-rank payload: each rank holds elems/n locally except psum
        # (shard_map sees the local shard; psum moves the whole local
        # buffer through the ring).
        local_bytes = (elems // n) * 4
        busbw = local_bytes * _busbw_factor(op, n) / mean_s
        results.append({
            "op": op,
            "axis": axis,
            "ranks": n,
            "payload_mb": round(local_bytes / 1e6, 3),
            "mean_time_s": round(mean_s, 6),
            "busbw_gbps": round(busbw / 1e9, 3),
        })
    return results


def main():
    import json

    for rec in run_collectives_bench():
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
