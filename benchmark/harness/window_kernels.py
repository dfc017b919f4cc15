"""The flash kernels' share of their roofline in a model whose layers differ:
the least time the chip's peaks allow for what one kind of layer needs
(``opcount_window.py``: the band ``i - j < window`` alone in a sliding layer,
the causal half in a full one, a K/V head read once for its group) over those
layers' kernels' summed device time. Which kernels are a layer's is read from
their scope: ``layer_<i>`` with ``layer_types[i]`` in the configuration's
file."""

from __future__ import annotations

import re

from . import opcount, opcount_window, scopes

_LAYER = re.compile(r"\blayer_(\d+)/")
KINDS = {"window": "sliding_attention", "full": "full_attention"}


def layer_roofline(ctx, kind: str, backward: bool):
    """``flash_<kind>_<fwd|bwd>_roofline`` in per cent, ``kind`` a key of
    ``KINDS``; ``None`` where the configuration has no layer types or the
    trace no such kernel."""
    config, peaks = ctx["cell"].config, ctx["peaks"]
    name = f"flash_{kind}_{'bwd' if backward else 'fwd'}_roofline"
    if peaks is None or "layer_types" not in config:
        return None
    types, wanted = config["layer_types"], KINDS[kind]

    def of_kind(op_name):
        found = _LAYER.search(op_name)
        return bool(found) and types[int(found.group(1))] == wanted

    kernel = r"core_attention/flash_bwd_(dkdv|dq)\b" if backward \
        else r"core_attention/flash_fwd\b"
    seconds = scopes.seconds_matching(ctx, kernel, of_kind)
    if seconds is None:
        ctx["say"](f"{name}: no {wanted} layer's kernel in the trace")
        return None
    layers = [i for i in config["layers_held"] if types[i] == wanted]
    b, s = ctx["run"]["global_batch"], ctx["run"]["seq_len"]
    # A window as long as the sequence is the causal half.
    window = config["sliding_window"] if kind == "window" else s
    least = 0.0
    for i in layers:
        flops, nbytes = opcount_window.flash_band(
            b, config["num_attention_heads_per_layer"][i],
            config["num_key_value_heads"], s, s, config["head_dim"],
            window, backward)
        least += opcount.roofline_seconds(flops, nbytes, peaks)[0]
    steps = ctx["run"]["steps"]
    counted = "the band alone" if kind == "window" else "the causal half"
    ctx["say"](f"{name}: {len(layers)} {wanted} layers, "
               f"{1e3 * seconds / steps:.3f} ms a step on device 0, least "
               f"{1e3 * least:.3f} ms a step for {counted}")
    return 100.0 * least * steps / seconds
