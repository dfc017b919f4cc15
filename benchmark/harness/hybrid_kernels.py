"""The flash kernels' share of their roofline in a model whose attention
layers are all alike and of the type ``hybrid`` (``zaya``: every layer an
attention in a latent and an expert layer): the least time the chip's peaks
allow for the causal half at the configuration's heads, a K/V head read once
for its group (``opcount_window.flash_band`` with a window as long as the
sequence), over the kernels' summed device time. ``window_kernels.py`` reads
the models whose layers differ, by ``layer_types`` and a list of heads a
layer."""

from __future__ import annotations

from . import opcount, opcount_window, scopes


def roofline(ctx, backward: bool):
    """``flash_hybrid_<fwd|bwd>_roofline`` in per cent; ``None`` where the
    configuration's layers are not all ``hybrid`` or the trace has no such
    kernel."""
    config, peaks = ctx["cell"].config, ctx["peaks"]
    name = f"flash_hybrid_{'bwd' if backward else 'fwd'}_roofline"
    if peaks is None or set(config.get("layer_types", ())) != {"hybrid"}:
        return None
    kernel = r"core_attention/flash_bwd_(dkdv|dq)\b" if backward \
        else r"core_attention/flash_fwd\b"
    seconds = scopes.seconds_matching(ctx, kernel)
    if seconds is None:
        ctx["say"](f"{name}: no flash kernel in the trace")
        return None
    b, s = ctx["run"]["global_batch"], ctx["run"]["seq_len"]
    flops, nbytes = opcount_window.flash_band(
        b, config["num_attention_heads"], config["num_key_value_heads"],
        s, s, config["head_dim"], s, backward)
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    layers, steps = len(config["layers_held"]), ctx["run"]["steps"]
    ctx["say"](f"{name}: {layers} layers, {bound}-bound, "
               f"{1e3 * seconds / steps:.3f} ms a step on device 0, least "
               f"{1e3 * layers * least:.3f} ms a step for the causal half")
    return 100.0 * layers * least * steps / seconds
