"""``optimizer_ms`` (train loop): device time a step under the trainer's
scope ``optimizer``: the clip, the schedule, AdamW, the update of the
parameters and of their EMA, from the device trace by section
(``harness/sections.py``)."""
from harness.sections import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "optimizer")
