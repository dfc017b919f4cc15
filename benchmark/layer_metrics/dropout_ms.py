"""``dropout_ms`` (model code): device time a step in the operations that
draw or apply a dropout mask: those whose ``op_name``, or that of any
instruction their fusion holds, lies under a flax ``Dropout`` module or the
trainer's ``step_rng``, from the device trace by section
(``harness/sections.py``). On the chip every mask is drawn inside the fusion
that consumes it, so this also holds that fusion's other work: an upper
bound on what the masks cost, which falls when they get cheaper or leave."""
from harness.sections import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "dropout")
