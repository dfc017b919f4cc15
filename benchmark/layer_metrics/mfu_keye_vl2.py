"""``mfu_keye_vl2`` (model code): ``mfu`` for the ``KeyeVL2`` language model
under learned sparse attention: matmul and attention operations the forward
and backward passes need per trained token for what the chip holds
(``harness/opcount_keye_vl2.py``: attention over the pairs the selections
*kept*, the share read from the program's
``attention.selected.kept_share.steps``, not over the triangle the kernels
walk; the indexer's scores over the triangle; the blocks' recomputation not
counted), times the window's tokens per second, over chips times the
published bf16 peak."""
from harness.opcount_keye_vl2 import train_flops_per_token
from harness.selected_kernels import kept_share


def read(ctx):
    config, share = ctx["cell"].config, kept_share(ctx)
    if ctx["peaks"] is None or share is None:
        return None
    per_token = train_flops_per_token(config, ctx["run"]["seq_len"], share)
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    ctx["say"](f"mfu_keye_vl2: {per_token / 1e9:.4f} GFLOP a trained token "
               f"at a kept share of {share:.4f}")
    return 100.0 * per_token * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"])
