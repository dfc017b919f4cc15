#!/usr/bin/env python3
"""Compile each training cell's step for a described ``v5e:2x2`` at the
cell's real shapes, with no chip attached, and print what the chip's compiler
says: ``memory_analysis()``, the number of ``tpu_custom_call``s (the Pallas
flash kernels: 36 expected, 12 forward and 24 backward) and the collectives
in a multi-chip step.

    JAX_PLATFORMS=cpu python3 benchmark/rehearse_compile.py [workload ...]

A rehearsal the builder runs before any chip call; its output goes into
``PERF.md``. Nothing runs, so it says nothing about results or times, and a
compile that passes is never reported as a chip run. ``attention_impl=pallas``
is named because under ``JAX_PLATFORMS=cpu`` the program's ``auto`` takes the
XLA path. Not a test: it describes a topology at its top level.
"""

from __future__ import annotations

import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)


def compile_step(cell):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harness import train_steps, weights
    from deeplearning_cfn_tpu.parallel.mesh import build_mesh
    from deeplearning_cfn_tpu.parallel.sharding import batch_sharding, \
        param_sharding_tree, replicated
    from deeplearning_cfn_tpu.train.optim import build_optimizer, \
        build_schedule
    from deeplearning_cfn_tpu.train.state import TrainState, \
        _opt_state_shardings
    from deeplearning_cfn_tpu.train.task import build_task
    from deeplearning_cfn_tpu.train.trainer import Trainer

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cfg = train_steps.build_program_config(cell, seed=0)
    cfg.model.kwargs["attention_impl"] = "pallas"
    mesh = build_mesh(cfg.mesh, devices=list(topo.devices)[:cell.chips])
    task = build_task(cfg, mesh=mesh)
    tx = build_optimizer(cfg.optimizer, build_schedule(
        cfg.schedule, cfg.train.steps, cfg.train.global_batch, None))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    shapes = jax.eval_shape(task.init, key)["params"]

    def make_state(rng):
        params = weights.make(shapes, rng)
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          batch_stats={}, opt_state=tx.init(params))

    state_shapes = jax.eval_shape(make_state, key)
    param_sh = param_sharding_tree(shapes, mesh, task.param_rules)
    state_sh = TrainState(
        step=replicated(mesh), params=param_sh, batch_stats={},
        opt_state=_opt_state_shardings(
            state_shapes.opt_state, shapes, param_sh, mesh,
            zero1=cfg.train.shard_opt_state))
    struct = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
    state = jax.tree_util.tree_map(struct, state_shapes, state_sh)
    gb, s = cfg.train.global_batch, cfg.data.seq_len
    batch = {
        "tokens": jax.ShapeDtypeStruct((gb, s + 1), jnp.int32,
                                       sharding=batch_sharding(mesh, 2)),
        "loss_mask": jax.ShapeDtypeStruct((gb, s), jnp.float32,
                                          sharding=batch_sharding(mesh, 2)),
    }
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    trainer = Trainer(cfg, task.loss_fn, tx, mesh=mesh)
    step = jax.jit(trainer._train_step_fn(), donate_argnums=(0,),
                   out_shardings=(state_sh, None))
    t0 = time.perf_counter()
    compiled = step.lower(state, batch, rng).compile()
    return cfg, compiled, time.perf_counter() - t0


def main(argv):
    from harness import manifest

    m = manifest.load_manifest()
    names = argv or [w["name"] for w in m["workloads"]]
    for name in names:
        cell = manifest.Cell(m, name)
        if cell.traffic["kind"] != "train_steps":
            print(f"{name}: not a training cell, skipped")
            continue
        cfg, compiled, seconds = compile_step(cell)
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        gib = 2.0 ** 30
        print(f"{name}: global batch {cfg.train.global_batch} x "
              f"{cfg.data.seq_len}, vocab {cfg.data.vocab_size}, "
              f"{cell.chips} described v5e chip(s); compiled in "
              f"{seconds:.0f} s")
        print(f"  per device: arguments {mem.argument_size_in_bytes / gib:.2f}"
              f" GiB + temporaries {mem.temp_size_in_bytes / gib:.2f} GiB + "
              f"outputs {mem.output_size_in_bytes / gib:.2f} GiB (aliased "
              f"{mem.alias_size_in_bytes / gib:.2f} GiB)")
        print(f"  tpu_custom_call: {text.count('tpu_custom_call')}")
        found = {}
        for op in re.findall(r"= \S+ (all-reduce|all-gather|reduce-scatter|"
                             r"all-to-all|collective-permute)(?:-start)?\(",
                             text):
            found[op] = found.get(op, 0) + 1
        print(f"  collectives: {found or 'none'}")


if __name__ == "__main__":
    main(sys.argv[1:])
