#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, what the controls of the
``zaya1_8b`` configuration give: the plain reference put in the program's
place (a) computed in int8, one precision below the bfloat16 the
configuration states, (b) with half of the batch left out, (c) computing 7
of its 8 held experts, (d) with no router handed the state of the layer
before. Each has to fail the comparison by at least one of the cell's three
limits.

    python3 benchmark/calibrate_zaya1_8b.py --seeds 2

``calibrate_laguna_xs2.py``'s loop (this reference too consumes the
parameters it is given, so each control makes them again from the seed),
with this configuration's cell and controls in place of that one's. What
sound runs give is read from the benchmark's own runs, which print each
number beside its limit. ``PERF.md`` keeps the readings.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate_laguna_xs2 as laguna  # noqa: E402

CONTROLS = {
    "int8": dict(precision="int8"),
    "half_batch": dict(rows=1),
    "one_expert_out": None,   # experts = held - 1, from the configuration
    "state_dropped": dict(carry_state=False),
}


def main(argv=None):
    laguna.CONTROLS = CONTROLS
    # Defaults first: what the caller gives after them wins.
    laguna.main(["--workload", "zaya1_8b_train_4k", "--first-seed",
                 "3100000033", "--controls", ",".join(CONTROLS)]
                + list(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    main()
