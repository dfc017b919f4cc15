"""``collective_exposed_ms`` (collectives): the part of ``collective_ms``
during which the same chip runs no other operation: what the step waits for.
The mean over the cell's chips, from the device trace
(``harness/collectives.py``)."""
from harness.collectives import ms_per_step


def read(ctx):
    return ms_per_step(ctx, exposed=True)
