"""``collective_ms`` (collectives): device time a step in all-gather,
reduce-scatter, all-reduce, all-to-all and collective-permute operations
(``-start`` and ``-done`` halves included; told by name), a chip, the mean
over the cell's chips, from the device trace (``harness/collectives.py``).
Left out where the trace has none."""
from harness.collectives import ms_per_step


def read(ctx):
    return ms_per_step(ctx, exposed=False)
