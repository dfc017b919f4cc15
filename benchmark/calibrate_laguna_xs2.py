#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, what the controls of the
``laguna_xs2`` configuration give: the plain reference put in the program's
place (a) computed in int8, one precision below the bfloat16 the
configuration states, (b) with half of the batch left out, (c) computing 31
of its 32 held experts. Each has to fail the comparison by at least one of
the cell's three limits.

    python3 benchmark/calibrate_laguna_xs2.py --seeds 2

``calibrate.py`` does not fit: it calls one reference several times on the
same parameters, and this reference consumes them (692 M parameters leave no
room for a second copy), and it knows no control of a configuration's own.
What sound runs give is read from the benchmark's own runs, which print each
number beside its limit. ``PERF.md`` keeps the readings.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)

from harness import compare, device, manifest, train_steps, weights  # noqa: E402

CONTROLS = {
    "int8": dict(precision="int8"),
    "half_batch": dict(rows=1),
    "one_expert_out": None,   # experts = held - 1, from the configuration
}


def say(text):
    print(f"[calibrate] {text}", flush=True)


def main(argv=None):
    import jax

    from deeplearning_cfn_tpu.train.task import build_task

    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="laguna_xs2_train_4k")
    p.add_argument("--seeds", type=int, default=2)
    p.add_argument("--first-seed", type=int, default=2_600_000_033)
    p.add_argument("--controls", default=",".join(CONTROLS))
    args = p.parse_args(argv)
    device.place_compile_cache()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    device.require_chips(cell.chips)
    hp = dict(cell.config["optimizer"])
    block = int(cell.traffic.get("reference_block_rows", 1))
    t0 = time.perf_counter()
    for seed in (args.first_seed + 7919 * i for i in range(args.seeds)):
        cfg = train_steps.build_program_config(cell, seed)
        task = build_task(cfg)
        shapes = jax.eval_shape(task.init, weights.seed_key(seed))["params"]
        make = jax.jit(lambda key: weights.make(shapes, key))
        gb = cfg.train.global_batch
        tokens = train_steps.make_tokens(seed, cell.traffic, cfg.data.seq_len,
                                         cfg.data.vocab_size)
        batches = [tokens[i * gb:(i + 1) * gb]
                   for i in range(train_steps.CHECK_STEPS)]

        def reference(**kw):
            return cell.reference.train_steps(
                make(weights.seed_key(seed)), batches, cell.config, hp,
                block_rows=block, **kw)

        sound = reference()
        say(f"seed {seed}: reference losses {sound['loss']} "
            f"({time.perf_counter() - t0:.0f} s)")
        for name in args.controls.split(","):
            kw = CONTROLS[name] or dict(
                experts=cell.config["num_experts"] - 1)
            numbers = compare.train_numbers(reference(**kw), sound)
            say(f"READING seed {seed} control {name}: "
                + ", ".join(f"{k} {v:.4g}" for k, v in numbers.items()
                            if not k.startswith("_"))
                + f"; worst leaves {numbers['_grad_leaf']}, "
                  f"{numbers['_change_leaf']} "
                  f"({time.perf_counter() - t0:.0f} s)")


if __name__ == "__main__":
    main()
