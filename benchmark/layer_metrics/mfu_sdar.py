"""``mfu_sdar`` (model code): ``mfu`` for the ``sdar_moe`` decoder trained as
a block-diffusion model: matmul and attention operations the forward and
backward passes need per trained data token for what the chip holds, two
positions a token through the blocks, the head on one, attention's live
pairs (``harness/opcount_sdar.py``; the blocks' recomputation is not
counted), times the window's tokens per second, over chips times the
published bf16 peak."""
from harness.opcount_sdar import train_flops_per_token


def read(ctx):
    config = ctx["cell"].config
    if ctx["peaks"] is None or config.get("model_type") != "sdar_moe":
        return None
    per_token = train_flops_per_token(config, ctx["run"]["seq_len"])
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    ctx["say"](f"mfu_sdar: {per_token / 1e9:.4f} GFLOP a trained token")
    return 100.0 * per_token * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"])
