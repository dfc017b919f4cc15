"""``flash_full_bwd_roofline`` (kernels): the full-attention layers' two
backward flash kernels' share of the causal half's roofline, a K/V head read
once for its group, from the device trace and ``harness/opcount_window.py``."""
from harness.window_kernels import layer_roofline


def read(ctx):
    return layer_roofline(ctx, "full", backward=True)
