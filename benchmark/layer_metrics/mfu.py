"""``mfu`` (model code): operations the forward and backward passes need per
token, from shapes (``harness/opcount.py``), times the window's tokens per
second, over chips times the published bf16 peak."""
from harness.opcount import lm_train_flops_per_token


def read(ctx):
    if ctx["peaks"] is None:
        return None
    c = ctx["cell"].config
    per_token = lm_train_flops_per_token(
        c["n_layer"], c["n_embd"], c["n_inner"], c["vocab_size"],
        ctx["run"]["seq_len"])
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    chips = ctx["device"]["count"]
    return 100.0 * per_token * rate / (
        chips * ctx["peaks"]["bf16_flops_per_s"])
