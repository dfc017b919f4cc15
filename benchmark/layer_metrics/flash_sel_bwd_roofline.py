"""``flash_sel_bwd_roofline`` (kernels): the two backward flash kernels'
share of the roofline of the pairs a selection *kept*, from the device trace
and ``harness/opcount_keye_vl2.py``."""
from harness.selected_kernels import flash_roofline


def read(ctx):
    return flash_roofline(ctx, backward=True)
