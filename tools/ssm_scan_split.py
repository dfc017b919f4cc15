#!/usr/bin/env python3
"""Split the device time a traced benchmark run finds under the state-space
mixer's scopes by pass (forward, the recomputed forward, backward) and by the
operation that stands last in an instruction's ``op_name``.

    python3 tools/ssm_scan_split.py --out chiprun_out/split.json -- \
        --workload granite4_h_micro_train_8k --seed 7 --seconds 20 --trace 1

It runs ``benchmark/run.py``'s ``main`` with the arguments after ``--`` and
listens to ``harness/scopes.py:scoped_ops``, the join every per-layer reader
of a scope goes through: the numbers are the readers' own, only kept apart.
Not imported by anything a cell runs; the benchmark's files are not touched.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))

SCOPES = ("ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm",
          "ssm_out_proj")


def phase_of(op_name: str) -> str:
    if "rematted_computation" in op_name:
        return "recomputed"
    if "transpose(jvp" in op_name:
        return "backward"
    return "forward"


def split(ops, steps: int):
    """``{scope: {phase: {"ms": …, "ops": {tail: ms}}}}`` a step."""
    table = {}
    for op_name, seconds in ops:
        found = re.search(r"/self_attn/(ssm_\w+)/(.*)$", op_name)
        if not found or found.group(1) not in SCOPES:
            continue
        phase = table.setdefault(found.group(1), {}).setdefault(
            phase_of(op_name), {"ms": 0.0,
                                "ops": collections.Counter()})
        ms = 1e3 * seconds / steps
        phase["ms"] += ms
        phase["ops"][found.group(2).split("/")[-1]] += ms
    for phases in table.values():
        for phase in phases.values():
            phase["ops"] = dict(sorted(phase["ops"].items(),
                                       key=lambda kv: -kv[1])[:12])
    return table


def main(argv) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", required=True)
    parser.add_argument("rest", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)
    rest = args.rest[1:] if args.rest[:1] == ["--"] else args.rest

    import run
    from harness import scopes

    scoped_ops = scopes.scoped_ops

    def listening(ctx):
        ops = scoped_ops(ctx)
        if ops is not None and "ssm_split_written" not in ctx:
            ctx["ssm_split_written"] = True
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(split(ops, ctx["run"]["steps"]), f, indent=1)
        return ops

    scopes.scoped_ops = listening
    return run.main(rest)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
