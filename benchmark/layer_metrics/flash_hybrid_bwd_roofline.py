"""``flash_hybrid_bwd_roofline`` (kernels): the two backward flash kernels'
share of the causal half's roofline in a model whose layers are all
``hybrid`` (8 query heads over 2 K/V heads in ``zaya1_8b``: a group of 4), a
K/V head read once for its group, from the device trace
(``harness/hybrid_kernels.py``)."""
from harness.hybrid_kernels import roofline


def read(ctx):
    return roofline(ctx, backward=True)
