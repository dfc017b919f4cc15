"""``mfu_mellum2`` (model code): ``mfu`` for the ``mellum`` decoder on its
four-chip host: matmul and attention operations the forward and backward
passes need per trained token for what the host holds, all of a token's
experts among them (``harness/opcount_mellum2.py``), times the window's
tokens per second, over the cell's chips times the published bf16 peak."""
from harness.opcount_mellum2 import train_flops_per_token


def read(ctx):
    config = ctx["cell"].config
    if ctx["peaks"] is None or config.get("model_type") != "mellum":
        return None
    per_token = train_flops_per_token(config, ctx["run"]["seq_len"])
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    ctx["say"](f"mfu_mellum2: {per_token / 1e9:.4f} GFLOP a trained token")
    return 100.0 * per_token * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"])
