"""``flash_bd_fwd_roofline`` (kernels): the forward flash kernel's share of
the roofline of a block-diffusion layout's live pairs alone, from the device
trace and ``harness/opcount_sdar.py``; the blocks are recomputed, so the
kernel's time holds two calls a layer where the least time counts one."""
from harness.layout_kernels import layout_roofline


def read(ctx):
    return layout_roofline(ctx, backward=False)
