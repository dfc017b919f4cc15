"""``blocks_ms`` (model code): device time a step in the trunk outside the
flash kernels and outside the operations that hold a dropout mask
(``dropout_ms``): the attention projections, the MLPs, the layer norms and
the embedding, from the device trace by section (``harness/sections.py``)."""
from harness.sections import ms_per_step


def read(ctx):
    return ms_per_step(ctx, "attn_proj", "mlp", "norm", "embed")
