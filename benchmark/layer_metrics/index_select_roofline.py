"""``index_select_roofline`` (kernels): the least time the chip's peaks allow
for one read of ``qI``, ``kI`` and ``w``, the products of ``I`` over the
causal triangle and one write of the selection's bits
(``harness/opcount_keye_vl2.py:index_select``), over the device time under
the scope ``indexer_select``, which also holds the 32 counts of the
threshold's bisection: comparisons, which the matrix unit's peak does not
count."""
from harness import opcount_keye_vl2
from harness.selected_kernels import roofline


def read(ctx):
    sa = ctx["cell"].config.get("sa_config", {})
    return roofline(
        ctx, "index_select_roofline", r"\bindexer_select\b",
        lambda config, run, share: opcount_keye_vl2.index_select(
            run["global_batch"], sa["indexer_num_heads"],
            sa["indexer_head_dim"], run["seq_len"]))
