#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers that the limits of
``correct`` are set from: what sound runs of the program give over many
seeds, and what the *control* gives — the reference put in the program's
place, computed one precision below the one the configuration states.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 --control-seeds 3

One process for all seeds, because set-up is long. A training cell needs no
measured window: its readings are of the first steps. The benchmark's own
runs never run this; ``PERF.md`` keeps the readings beside each limit.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(0, _HERE)

from harness import compare, device, manifest, weights  # noqa: E402


def say(text):
    print(f"[calibrate] {text}", flush=True)


def _precision_below(cell):
    return cell.reference._precision.below(cell.config["precision"])


def train(cell, seeds, control_seeds, devices):
    import contextlib

    import jax

    from harness import train_steps as ts

    rows = []
    for n, seed in enumerate(seeds):
        cfg = ts.build_program_config(cell, seed)
        trainer, state, shapes, mesh = ts.build_trainer(cell, cfg, seed,
                                                        devices)
        feed = ts.build_feed(cell, cfg, seed, mesh,
                             lambda _n: contextlib.nullcontext())
        rng = jax.random.split(jax.random.PRNGKey(cfg.train.seed), 3)[2]
        state, program = ts.first_steps(trainer, state, feed, rng, shapes,
                                        seed, say)
        batches = list(feed.first)
        del state, trainer, feed
        params = jax.jit(lambda key: weights.make(shapes, key))(
            weights.seed_key(seed))
        block = int(cell.traffic.get("reference_block_rows", 2))
        hp = dict(cell.config["optimizer"])
        ref = cell.reference.train_steps(params, batches, cell.config, hp,
                                         "float32", block, rng)
        sound = compare.train_numbers(program, ref)
        # The faults each hardly-moved number is there to catch: half the
        # batch left out (loss), a step that returns its state unchanged.
        half = cell.reference.train_steps(
            params, batches, cell.config, hp, "float32", block, rng,
            rows=len(batches[0]) // 2)
        fault_loss = max(abs(h - r) / abs(r)
                         for h, r in zip(half["loss"], ref["loss"]))
        row = {"seed": seed, **{k: v for k, v in sound.items()
                                if not k.startswith("_")},
               "fault_half_batch_loss_rel": fault_loss,
               "fault_unchanged_state_change_gap": 1.0}
        if n < control_seeds:
            # A step that drops nothing (or draws other masks): the
            # reference with its rates at 0 in the program's place.
            plain = cell.reference.train_steps(
                params, batches, dict(cell.config, embd_pdrop=0.0,
                                      resid_pdrop=0.0), hp, "float32", block)
            row.update({f"fault_no_dropout_{k}": v for k, v in
                        compare.train_numbers(plain, ref).items()
                        if not k.startswith("_")})
            low = cell.reference.train_steps(
                params, batches, cell.config, hp, _precision_below(cell),
                block, rng)
            control = compare.train_numbers(low, ref)
            row.update({f"control_{k}": v for k, v in control.items()
                        if not k.startswith("_")})
        del params
        rows.append(row)
        say(f"READING {row}")
    return rows


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--first-seed", type=int, default=2_500_000_011)
    args = p.parse_args(argv)
    device.place_compile_cache()
    cell = manifest.Cell(manifest.load_manifest(), args.workload)
    devices = device.require_chips(cell.chips)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    t0 = time.perf_counter()
    if cell.traffic["kind"] != "train_steps":
        raise SystemExit(f"no calibration for traffic of kind "
                         f"{cell.traffic['kind']!r}")
    rows = train(cell, seeds, args.control_seeds, devices)
    for k in sorted({k for r in rows for k in r} - {"seed"}):
        vals = [r[k] for r in rows if k in r]
        say(f"SUMMARY {k}: min {min(vals):.6g} max {max(vals):.6g} over "
            f"{len(vals)} seeds")
    say(f"{len(rows)} seeds in {time.perf_counter() - t0:.0f} s on "
        f"{devices[0].device_kind} x {len(devices)}")


if __name__ == "__main__":
    main()
