"""``flash_sel_fwd_roofline`` (kernels): the forward flash kernel's share of
the roofline of the pairs a selection *kept* (``kept_share`` of the causal
triangle, from the program's counter), from the device trace and
``harness/opcount_keye_vl2.py``: the kernel computes the triangle's tiles and
masks inside them, so it reads at most ``kept_share`` of its matrix-unit
share."""
from harness.selected_kernels import flash_roofline


def read(ctx):
    return flash_roofline(ctx, backward=False)
