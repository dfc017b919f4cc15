"""GPipe-style pipeline parallelism over a 'pipe' mesh axis.

The reference has no pipeline parallelism (SURVEY.md §3.2 lists PP as
absent — its workloads all fit one GPU's memory); this op extends the
rebuild's parallelism inventory the TPU-native way: a single-program SPMD
schedule under ``shard_map`` where every pipeline stage is the SAME traced
program, stage identity is ``lax.axis_index``, activations hop to the next
stage with ``ppermute`` over ICI, and the whole (M + S - 1)-tick schedule
is one ``lax.scan`` — fully jit-compiled, differentiable (the backward
pass is the reverse schedule, derived by AD: scan and ppermute both have
exact transposes), and composable with the data/expert/model axes.

Layout contract:
- stage parameters are STACKED on a leading layer dim [L, ...] and sharded
  ``P('pipe')`` — each device holds its stage's L/S layers;
- the batch stays sharded over the data axes and REPLICATED over 'pipe'
  (every stage sees the same microbatch stream; only one stage's compute
  per tick is "real" for a given microbatch — the (S-1)/(M+S-1) bubble
  that is inherent to GPipe; raise n_microbatches to amortize it);
- the final stage's outputs are returned to every stage with one psum over
  'pipe' (masked: other stages contribute zeros), making the result
  pipe-invariant so downstream (loss, heads) runs replicated-over-pipe.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def gpipe(
    stage_fn: Callable[[PyTree, PyTree], PyTree],
    stage_params: PyTree,
    xs: PyTree,
    *,
    mesh: Mesh,
    n_microbatches: int,
    axis_name: str = "pipe",
    batch_spec: Any = "data",
) -> PyTree:
    """Run ``stage_fn`` as an S-stage pipeline over ``mesh[axis_name]``.

    stage_fn(local_params, state) -> state: applies ONE stage's layers to a
    microbatch ``state`` (a pytree; leaves [mb, ...]). It must return the
    same structure — pass-through leaves (e.g. an attention bias that every
    layer needs) travel with the microbatch through the pipeline.

    stage_params: pytree with leaves stacked [L, ...]; sharded P('pipe') on
    dim 0, so inside the pipeline each device sees [L/S, ...].

    xs: pytree of batch-leading arrays [B, ...] sharded ``batch_spec`` on
    dim 0 (and replicated over 'pipe'). B_local must divide into
    ``n_microbatches`` equal microbatches.
    """
    n_stages = mesh.shape[axis_name]
    m = n_microbatches

    def body(params, local_xs):
        def to_mb(t):
            b = t.shape[0]
            if b % m:
                raise ValueError(
                    f"local batch {b} not divisible into {m} microbatches")
            return t.reshape((m, b // m) + t.shape[1:])

        xs_mb = jax.tree_util.tree_map(to_mb, local_xs)
        idx = jax.lax.axis_index(axis_name)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        zero_state = jax.tree_util.tree_map(
            lambda t: jnp.zeros_like(t[0]), xs_mb)
        out0 = jax.tree_util.tree_map(jnp.zeros_like, xs_mb)

        def tick(carry, t):
            state, out = carry
            # Stage 0 ingests microbatch t from the host-fed input; later
            # stages consume what ppermute delivered last tick.
            ingest = jax.tree_util.tree_map(
                lambda full, cur: jnp.where(
                    idx == 0,
                    jax.lax.dynamic_index_in_dim(
                        full, jnp.minimum(t, m - 1), 0, keepdims=False),
                    cur),
                xs_mb, state)
            y = stage_fn(params, ingest)
            # The last stage finished microbatch t-(S-1): record it.
            mb_done = t - (n_stages - 1)
            mb_clip = jnp.maximum(mb_done, 0)
            write = jnp.logical_and(idx == n_stages - 1, mb_done >= 0)
            out = jax.tree_util.tree_map(
                lambda o, yy: jax.lax.dynamic_update_index_in_dim(
                    o,
                    jnp.where(write, yy,
                              jax.lax.dynamic_index_in_dim(
                                  o, mb_clip, 0, keepdims=False)),
                    mb_clip, 0),
                out, y)
            state = jax.lax.ppermute(y, axis_name, perm)
            return (state, out), None

        (_, out), _ = jax.lax.scan(
            tick, (zero_state, out0), jnp.arange(m + n_stages - 1))
        # Broadcast the last stage's results to every stage (others hold
        # garbage from the bubble): masked psum over 'pipe'.
        out = jax.tree_util.tree_map(
            lambda o: jax.lax.psum(
                jnp.where(idx == n_stages - 1, o, jnp.zeros_like(o)),
                axis_name),
            out)
        return jax.tree_util.tree_map(
            lambda t: t.reshape((t.shape[0] * t.shape[1],) + t.shape[2:]),
            out)

    x_spec = P(batch_spec)
    mapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )
    return mapped(stage_params, xs)


def scan_layers(
    layer_fn: Callable[[PyTree, PyTree], PyTree]
) -> Callable[[PyTree, PyTree], PyTree]:
    """Lift a single-layer fn into a stage fn that scans its local stack:
    ``stage_fn(params_with_leading_layer_dim, state)``. The scan keeps
    compile time O(1) in depth — XLA traces one layer body per stage."""

    def stage_fn(params, state):
        def step(h, layer_params):
            return layer_fn(layer_params, h), None

        out, _ = jax.lax.scan(step, state, params)
        return out

    return stage_fn
