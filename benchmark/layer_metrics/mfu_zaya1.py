"""``mfu_zaya1`` (model code): ``mfu`` for the ``zaya`` decoder: matmul,
convolution and attention operations the forward and backward passes need
per trained token for what the chip holds, the routed expert at a uniform
router's expectation (``harness/opcount_zaya1.py``), times the window's
tokens per second, over chips times the published bf16 peak. The uniform
expectation, not the histogram ``moe.rows_held.steps``: that holds set-up's
three steps, in which the balancing biases' controller is still bringing a
seeded router's load to the uniform one, and the window, whose tokens per
second this is a share of, runs at it (``PERF.md`` section 6, PR 31)."""
from harness.opcount_zaya1 import train_flops_per_token


def read(ctx):
    config = ctx["cell"].config
    if ctx["peaks"] is None or "cca_time0" not in config:
        return None
    per_token = train_flops_per_token(config, ctx["run"]["seq_len"])
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    ctx["say"](f"mfu_zaya1: {per_token / 1e9:.4f} GFLOP a trained token")
    return 100.0 * per_token * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"])
