"""``mfu_sparse`` (model code): ``mfu`` for a configuration that is not
GPT-2's block: operations the forward and backward passes need per token,
counted from the configuration's per-layer lists for the layers, experts and
vocabulary rows held (``harness/opcount_sparse_lm.py``), times the window's
tokens per second, over chips times the published bf16 peak."""
from harness.opcount_sparse_lm import train_flops_per_token


def read(ctx):
    config = ctx["cell"].config
    if ctx["peaks"] is None or "layers_held" not in config:
        return None
    per_token = train_flops_per_token(config, ctx["run"]["seq_len"])
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    ctx["say"](f"mfu_sparse: {per_token / 1e9:.4f} GFLOP a trained token")
    return 100.0 * per_token * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"])
