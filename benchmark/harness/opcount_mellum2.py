"""Operations a training step needs per token in the ``mellum`` decoder for
what one four-chip host holds: sliding-window and full attention over grouped
K/V heads, a softmax router over all the experts and every one of a token's
``num_experts_per_tok`` experts (all of them are on the host), an untied head
over the vocabulary rows held; counted from the configuration's file as
``opcount_sparse_lm`` counts Laguna's."""

from __future__ import annotations

from typing import Any, Dict

from . import opcount_window


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations per token by part (a multiply-add is two), summed
    over the layers held. An attention core counts the pairs a row sees on
    average over a sequence of ``seq_len`` (``opcount_window.band_pairs``:
    the band ``i - j < sliding_window`` in a sliding layer, the causal half
    in a full one)."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    parts = dict.fromkeys(
        ("projections", "cores", "router", "experts", "head"), 0.0)
    for i in config["layers_held"]:
        parts["projections"] += 2.0 * d * (2 * h * hd + 2 * kv * hd)
        window = config["sliding_window"] \
            if config["layer_types"][i] == "sliding_attention" else seq_len
        pairs = opcount_window.band_pairs(seq_len, seq_len, window) / seq_len
        parts["cores"] += 4.0 * h * hd * pairs
        parts["router"] += 2.0 * d * config["num_experts"]
        parts["experts"] += 6.0 * d * config["num_experts_per_tok"] \
            * config["moe_intermediate_size"]
    parts["head"] = 2.0 * d * config["vocab_size"]
    return parts


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: three times the forward pass (two products in
    the backward pass for each of the forward's; recomputation is not
    counted)."""
    return 3.0 * sum(forward_parts(config, seq_len).values())
