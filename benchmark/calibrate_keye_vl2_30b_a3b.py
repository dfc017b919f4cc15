#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, what sound steps and what
the controls of the ``keye_vl2_30b_a3b`` configuration give, many seeds in
one process: the trainer is compiled once, and for each seed the program's
own first steps (the benchmark's ``first_steps`` through ``Trainer.fit``) are
compared with the plain float32 reference. The controls put the reference in
the program's place with one fault: computed in int8, one precision below the
bfloat16 the configuration states; every causal key kept (no selection); the
best 1024 kept for 2048; the threshold taken over later keys too; the
indexers' loss left out of the objective; the indexer's input not detached
(the cross-entropy's gradient reaches the indexer's side and the KL's the
trunk); 15 of the 16 held experts. Each has to fail the comparison by at
least one of the cell's three limits.

    python3 benchmark/calibrate_keye_vl2_30b_a3b.py --seeds 8 --control-seeds 1

``calibrate_sdar_30b_a3b.py``'s loop. ``PERF.md`` and the configuration's
``limits_set_from`` keep the readings.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)

from calibrate_mellum2_12b import _reading, say  # noqa: E402
from harness import compare, device, manifest, train_steps, weights  # noqa: E402

CONTROLS = {
    "int8": dict(precision="int8"),
    "no_selection": dict(faults=("no_selection",)),
    "topk_1024": dict(faults=("topk_1024",)),
    "selection_not_causal": dict(faults=("selection_not_causal",)),
    "indexer_loss_dropped": dict(faults=("indexer_loss_dropped",)),
    "indexer_sees_lm_gradient": dict(faults=("indexer_sees_lm_gradient",)),
    "an_expert_out": dict(experts_out=(5,)),
}


def main(argv=None):
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="keye_vl2_30b_a3b_train_16k")
    p.add_argument("--seeds", type=int, default=8)
    p.add_argument("--first-seed", type=int, default=4_700_000_033)
    p.add_argument("--control-seeds", type=int, default=1)
    p.add_argument("--controls", default=",".join(CONTROLS),
                   help="read on each of the first --control-seeds seeds")
    args = p.parse_args(argv)
    device.place_compile_cache()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    devices = device.require_chips(cell.chips)
    hp = dict(cell.config["optimizer"])
    limits = {**cell.config["limits"], **cell.traffic.get("limits", {})}
    block = int(cell.traffic.get("reference_block_rows", 1))
    quiet = lambda _name: contextlib.nullcontext()
    trainer, t0 = None, time.perf_counter()
    for n, seed in enumerate(args.first_seed + 7919 * i
                             for i in range(args.seeds)):
        cfg = train_steps.build_program_config(cell, seed)
        fresh, state, shapes, mesh = train_steps.build_trainer(
            cell, cfg, seed, devices)
        trainer = trainer or fresh      # compiled once, for every seed
        feed = train_steps.build_feed(cell, cfg, seed, mesh, quiet)
        rng = jax.random.split(jax.random.PRNGKey(cfg.train.seed), 3)[2]
        state, program = train_steps.first_steps(
            trainer, state, feed, rng, shapes, seed, say)
        say(f"seed {seed}: device memory peak after the program's steps "
            f"{device.memory_peak_bytes(devices)} bytes")
        del state, fresh
        batches = list(feed.first)
        make = jax.jit(lambda key: weights.make(shapes, key))

        def reference(**kw):
            return cell.reference.train_steps(
                make(weights.seed_key(seed)), batches, cell.config, hp,
                block_rows=block, rng=rng, **kw)

        sound = reference()
        say(f"seed {seed}: the reference's indexer loss a step "
            f"{sound['indexer_kl']}")
        numbers = compare.train_numbers(program, sound)
        ok = all(numbers[k] <= limits[k] for k in limits)
        _reading(f"sound ({'within' if ok else 'OVER'} the file's limits)",
                 seed, numbers, t0)
        if n >= args.control_seeds:
            continue
        for name in filter(None, args.controls.split(",")):
            _reading(f"control {name}", seed, compare.train_numbers(
                reference(**CONTROLS[name]), sound), t0)


if __name__ == "__main__":
    main()
