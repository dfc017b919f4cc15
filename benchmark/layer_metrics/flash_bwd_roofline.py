"""``flash_bwd_roofline`` (kernels): the two attention backward kernels'
share, together, of the backward pass's roofline, from the device trace and
``harness/opcount.py``."""
from harness.kernels import flash_roofline


def read(ctx):
    return flash_roofline(ctx, backward=True)
