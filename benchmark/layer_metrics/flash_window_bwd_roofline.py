"""``flash_window_bwd_roofline`` (kernels): the sliding layers' two backward
flash kernels' share of the roofline of the band ``i - j < window`` alone,
from the device trace and ``harness/opcount_window.py``."""
from harness.window_kernels import layer_roofline


def read(ctx):
    return layer_roofline(ctx, "window", backward=True)
