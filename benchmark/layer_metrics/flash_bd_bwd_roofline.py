"""``flash_bd_bwd_roofline`` (kernels): the two backward flash kernels' share
of the roofline of a block-diffusion layout's live pairs alone, from the
device trace and ``harness/opcount_sdar.py``."""
from harness.layout_kernels import layout_roofline


def read(ctx):
    return layout_roofline(ctx, backward=True)
