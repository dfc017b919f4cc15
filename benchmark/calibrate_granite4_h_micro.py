#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, what the controls of the
``granite4_h_micro`` configuration give: the plain reference put in the
program's place with one fault. The usual two: computed in int8, one
precision below the bfloat16 the configuration states; the second half of
the row's positions left out of the loss (a step is one row, so there is no
half of a batch to leave out). And the ones a state-space layer needs,
because at seeded weights the loss hardly feels the scan: (a) the state not
handed from chunk to chunk, (b) the decay left out (``A`` = 0), (c) one
convolution tap dropped (the oldest), (d) the residual multiplier 1 for
0.22, (e) attention scores over 8 (``1 / sqrt(64)``) for over 64, (f) the
gate applied after the norm. Each has to fail the comparison by at least one
of the cell's three limits.

    python3 benchmark/calibrate_granite4_h_micro.py --seeds 12 --control-seeds 1

``calibrate_mellum2_12b.py``'s loop, many seeds in one process (the trainer
is compiled once; for each seed the program's own first steps through
``Trainer.fit`` are compared with the plain float32 reference, which consumes
the parameters it is given, so each control makes them again from the seed),
with this configuration's cell and controls in place of that one's.
``PERF.md`` and the configuration's ``limits_set_from`` keep the readings.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate_mellum2_12b as base  # noqa: E402

CONTROLS = {
    "int8": dict(precision="int8"),
    "half_row": dict(row_share=0.5),
    "state_dropped": dict(carry_state=False),
    "no_decay": dict(decay=False),
    "tap_dropped": dict(drop_tap=0),
    "residual_1": dict(residual_multiplier=1.0),
    "scores_over_8": dict(attention_multiplier=0.125),
    "gate_after_norm": dict(gate_after_norm=True),
}


def main(argv=None):
    base.CONTROLS = CONTROLS
    # Defaults first: what the caller gives after them wins.
    base.main(["--workload", "granite4_h_micro_train_8k", "--first-seed",
               "4100000033", "--seeds", "12", "--control-seeds", "1",
               "--controls", ",".join(CONTROLS), "--first-seed-controls", ""]
              + list(sys.argv[1:] if argv is None else argv))


if __name__ == "__main__":
    main()
