"""Sharded train state.

The reference kept replica state per-process (each GPU rank held its own full
copy; Horovod broadcast from rank 0 at start — SURVEY.md §4.2). Here state is
one logical pytree with explicit NamedShardings over the mesh; "broadcast from
rank 0" is replaced by initializing under a sharding constraint so every
device materializes the same (or its shard of the) state directly.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.struct
import flax.traverse_util
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.trace import span
from ..parallel.sharding import param_sharding_tree, replicated
from ..runtime import jit_events

PyTree = Any


class TrainState(flax.struct.PyTreeNode):
    step: jnp.ndarray
    params: PyTree
    batch_stats: PyTree  # BatchNorm running stats ({} for stat-free models)
    opt_state: PyTree
    ema_params: Optional[PyTree] = None

    def apply_gradients(self, grads: PyTree, tx: optax.GradientTransformation,
                        ema_decay: float = 0.0, nudges: PyTree = None
                        ) -> "TrainState":
        """One optimizer step. ``nudges`` is a sparse tree under the
        parameters' own names: what the model's forward pass asked to have
        added to a parameter that no gradient reaches (a router's balancing
        bias), added after the optimizer's update."""
        updates, new_opt_state = tx.update(grads, self.opt_state, self.params)
        new_params = optax.apply_updates(self.params, updates)
        if nudges:
            new_params = _nudged(new_params, nudges)
        new_ema = self.ema_params
        if new_ema is not None and ema_decay > 0:
            with jax.named_scope("ema"):
                new_ema = jax.tree_util.tree_map(
                    lambda e, p: e * ema_decay + p * (1.0 - ema_decay),
                    new_ema, new_params,
                )
        return self.replace(
            step=self.step + 1, params=new_params, opt_state=new_opt_state,
            ema_params=new_ema,
        )


def _nudged(params: PyTree, nudges: PyTree) -> PyTree:
    steps = flax.traverse_util.flatten_dict(nudges)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    names = [tuple(k.key for k in path) for path, _ in leaves]
    if not set(steps) <= set(names):
        raise KeyError(f"nudges for no parameter: "
                       f"{sorted(set(steps) - set(names))[:4]}")
    return jax.tree_util.tree_unflatten(treedef, [
        leaf + steps[name] if name in steps else leaf
        for name, (_, leaf) in zip(names, leaves)])


def create_train_state(
    rng: jax.Array,
    init_fn: Callable[[jax.Array], PyTree],
    tx: optax.GradientTransformation,
    mesh: Mesh,
    param_rules=(),
    ema: bool = False,
    shard_opt_state: bool = False,
) -> TrainState:
    """Initialize state directly into its sharded layout.

    ``init_fn(rng)`` returns flax variables ({'params': ..., 'batch_stats'?}).
    Init runs under jit with output shardings derived from the param rules so
    large models never materialize unsharded on one device — the TPU
    replacement for "rank 0 inits then broadcasts".

    ``shard_opt_state=True`` is the ZeRO-1 layout: params and grads stay
    replicated (pure DP semantics, bit-identical updates), but every
    param-mirroring optimizer slot (momentum, mu/nu, LAMB stats) shards one
    divisible dim over the 'data' axis. GSPMD then partitions the
    elementwise optimizer update across the axis and all-gathers only the
    parameter updates — optimizer memory drops by the data-parallel ways
    (at BERT-base/LAMB scale: 2 × 440 MB of slots → ~14 MB/chip on 64
    chips) for one extra collective per step.

    The whole of it, the wait for the device included, is the span
    ``train.init_state``, with the parameters' count and the state's bytes
    over all devices.
    """
    jit_events.install()
    with span("train.init_state") as build:
        state, shapes = _sharded_init(rng, init_fn, tx, mesh, param_rules,
                                      ema, shard_opt_state)
        build.annotate(
            params=sum(x.size for x in
                       jax.tree_util.tree_leaves(shapes.params)),
            bytes=sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(shapes)))
        return jax.block_until_ready(state)


def _sharded_init(rng, init_fn, tx, mesh, param_rules, ema, shard_opt_state):
    """The state, dispatched and not waited for, and its shapes."""
    var_shapes = jax.eval_shape(init_fn, rng)
    params_shape = var_shapes["params"]
    param_sh = param_sharding_tree(params_shape, mesh, param_rules)
    stats_shape = var_shapes.get("batch_stats", {})
    stats_sh = jax.tree_util.tree_map(lambda _: replicated(mesh), stats_shape)

    def make_state(rng):
        variables = init_fn(rng)
        params = variables["params"]
        stats = variables.get("batch_stats", {})
        opt_state = tx.init(params)
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=stats,
            opt_state=opt_state,
            ema_params=params if ema else None,
        )

    state_shapes = jax.eval_shape(make_state, rng)

    # Sharding tree: params + ema follow the rules; opt_state slots that
    # mirror params inherit their sharding (plus the ZeRO-1 data-axis shard
    # when enabled); everything else replicated.
    out_sh = TrainState(
        step=replicated(mesh),
        params=param_sh,
        batch_stats=stats_sh,
        opt_state=_opt_state_shardings(state_shapes.opt_state, params_shape,
                                       param_sh, mesh,
                                       zero1=shard_opt_state),
        ema_params=param_sh if ema else None,
    )
    make_sharded = jax.jit(make_state, out_shardings=out_sh)
    return make_sharded(rng), state_shapes


def _zero1_spec(shape, base_sharding, mesh):
    """Extend a mirror slot's sharding with a 'data'-axis shard on the
    first dim that is unsharded and divisible; leave the rest alone (a TP
    'model' shard on another dim composes). Slots whose spec already uses
    'data' (e.g. an FSDP-style param rule) are left untouched — a mesh
    axis may appear only once per spec."""
    ways = mesh.shape.get("data", 1)
    if ways <= 1 or not shape:
        return base_sharding
    spec = list(base_sharding.spec) + \
        [None] * (len(shape) - len(base_sharding.spec))
    used = [a for s in spec for a in
            (s if isinstance(s, tuple) else (s,)) if a is not None]
    if "data" in used:
        return base_sharding
    for dim, size in enumerate(shape):
        if spec[dim] is None and size % ways == 0:
            spec[dim] = "data"
            return NamedSharding(mesh, P(*spec))
    return base_sharding  # nothing divisible: stays as-is


def _opt_state_shardings(opt_state_shape, params_shape, param_sh, mesh,
                         zero1: bool = False):
    """Optimizer slots that mirror a param (momentum, mu/nu) inherit its
    sharding; scalars/counters are replicated. Matched structurally: any
    subtree of opt_state whose treedef equals the param treedef gets param
    shardings."""
    params_def = jax.tree_util.tree_structure(params_shape)
    param_sh_leaves = jax.tree_util.tree_leaves(param_sh)
    if zero1:
        shape_leaves = jax.tree_util.tree_leaves(params_shape)
        param_sh_leaves = [
            _zero1_spec(tuple(s.shape), sh, mesh)
            for s, sh in zip(shape_leaves, param_sh_leaves)
        ]

    def assign(node):
        try:
            node_def = jax.tree_util.tree_structure(node)
        except Exception:  # pragma: no cover
            return None
        if node_def == params_def:
            return jax.tree_util.tree_unflatten(node_def, param_sh_leaves)
        return None

    def recurse(node):
        hit = assign(node)
        if hit is not None:
            return hit
        if isinstance(node, tuple) and type(node) is not tuple:
            # NamedTuple (optax states): recurse fieldwise, rebuild same type.
            return type(node)(*(recurse(c) for c in node))
        if isinstance(node, tuple):
            return tuple(recurse(c) for c in node)
        if isinstance(node, list):
            return [recurse(c) for c in node]
        if isinstance(node, dict):
            return {k: recurse(v) for k, v in node.items()}
        return replicated(mesh)

    return recurse(opt_state_shape)
