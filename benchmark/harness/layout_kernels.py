"""The flash kernels' share of their roofline under a block-diffusion
layout: the least time the chip's peaks allow for the live pairs of every
layer held (``opcount_sdar.flash_layout``: ``L**2 + L b`` a head, a K/V head
read once for its group, each call counted once) over the kernels' summed
device time by scope, which holds the recomputed forward too and every
sub-tile the kernels compute under an edge: it cannot pass 100 %."""

from __future__ import annotations

from . import opcount, opcount_sdar, scopes


def layout_roofline(ctx, backward: bool):
    """``flash_bd_<fwd|bwd>_roofline`` in per cent; ``None`` where the
    configuration states no block-diffusion noise or the trace has no flash
    kernel."""
    config, peaks = ctx["cell"].config, ctx["peaks"]
    name = f"flash_bd_{'bwd' if backward else 'fwd'}_roofline"
    if peaks is None or "noise" not in config:
        return None
    kernel = r"core_attention/flash_bwd_(dkdv|dq)\b" if backward \
        else r"core_attention/flash_fwd\b"
    seconds = scopes.seconds_matching(ctx, kernel)
    if seconds is None:
        return None
    run, layers = ctx["run"], len(config["layers_held"])
    flops, nbytes = opcount_sdar.flash_layout(
        run["global_batch"], config["num_attention_heads"],
        config["num_key_value_heads"], run["seq_len"],
        config["noise"]["block_length"], config["head_dim"], backward)
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    steps = run["steps"]
    ctx["say"](f"{name}: {layers} layers, {flops / 1e9:.1f} GFLOP and "
               f"{nbytes / 1e9:.3f} GB a layer's call, {bound}-bound, least "
               f"{1e3 * layers * least:.3f} ms a step, "
               f"{1e3 * seconds / steps:.3f} ms a step in the kernels")
    return 100.0 * layers * least * steps / seconds
