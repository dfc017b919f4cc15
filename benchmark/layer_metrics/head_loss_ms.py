"""``head_loss_ms`` (model code): device time a step in the output head
(the scope ``lm_head``) and the loss (``lm_loss``), forward and backward,
from the device trace by section (``harness/sections.py``)."""
from harness.sections import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "head", "loss")
