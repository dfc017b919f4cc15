"""``flash_fwd_roofline`` (kernels): the attention forward kernel's share of
its roofline, from the device trace and ``harness/opcount.py``."""
from harness.kernels import flash_roofline


def read(ctx):
    return flash_roofline(ctx, backward=False)
