#!/usr/bin/env python3
"""Read, on four chips and at the cell's own size, what sound steps and what
the controls of the ``mellum2_12b`` configuration give, many seeds in one
process: the trainer is compiled once, and for each seed the program's own
first steps (the benchmark's ``first_steps`` through ``Trainer.fit`` on the
``expert=4`` mesh) are compared with the plain float32 reference of the whole
four layers. The controls put the reference in the program's place (a)
computed in int8, one precision below the bfloat16 the configuration states,
(b) computed in bfloat16, the program's own precision (ISSUE 35 asks for it;
it cannot be expected to fail: the program computes in it), (c) with one
rank's parts left out of every expert layer's sum (``rank_out``), (d) with
half of each batch left out. Each of (a), (c), (d) has to fail the comparison
by at least one of the cell's three limits.

    python3 benchmark/calibrate_mellum2_12b.py --seeds 10 --control-seeds 2

``PERF.md`` and the configuration's ``limits_set_from`` keep the readings.
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

from harness import compare, device, manifest, train_steps, weights  # noqa: E402

CONTROLS = {
    "int8": dict(precision="int8"),
    "bfloat16": dict(precision="bfloat16"),
    "rank_out": dict(groups_out=(1,)),
    "half_batch": None,   # rows = half of the global batch
}


def say(text):
    print(f"[calibrate] {text}", flush=True)


def _reading(what, seed, numbers, t0):
    say(f"READING seed {seed} {what}: "
        + ", ".join(f"{k} {v:.4g}" for k, v in numbers.items()
                    if not k.startswith("_"))
        + f"; worst leaves {numbers['_grad_leaf']}, "
          f"{numbers['_change_leaf']} ({time.perf_counter() - t0:.0f} s)")


def main(argv=None):
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="mellum2_12b_train_8k_ep4")
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=3_500_000_033)
    p.add_argument("--control-seeds", type=int, default=2)
    p.add_argument("--controls", default="int8,rank_out",
                   help="read on each of the first --control-seeds seeds")
    p.add_argument("--first-seed-controls", default="bfloat16,half_batch",
                   help="read on the first seed alone")
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
                block_rows=block, **kw)

        sound = reference()
        numbers = compare.train_numbers(program, sound)
        ok = all(numbers[k] <= limits[k] for k in limits)
        _reading(f"sound ({'within' if ok else 'OVER'} the file's limits)",
                 seed, numbers, t0)
        if n >= args.control_seeds:
            continue
        names = args.controls.split(",") + (
            args.first_seed_controls.split(",") if n == 0 else [])
        for name in filter(None, names):
            kw = CONTROLS[name] or dict(rows=cfg.train.global_batch // 2)
            _reading(f"control {name}", seed,
                     compare.train_numbers(reference(**kw), sound), t0)


if __name__ == "__main__":
    main()
