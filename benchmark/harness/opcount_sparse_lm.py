"""Operations a training step needs per token in a decoder whose layers
differ: a head count a layer over grouped K/V heads, window or full causal
attention, a head-wise gate, a dense gated MLP or routed experts with a
shared one, an untied head; counted from the configuration's file as
``opcount.lm_train_flops_per_token`` counts GPT-2's block from two widths.
Only the layers, experts and vocabulary rows this chip holds count."""

from __future__ import annotations

from typing import Any, Dict

from . import opcount_window


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations per token by part (a multiply-add is two). An
    attention core counts the pairs a row sees on average over a sequence of
    ``seq_len`` (``opcount_window.band_pairs``); a token's routed experts
    count at a uniform router's expectation, ``num_experts_per_tok`` times
    the share of the published experts held here, and the router multiplies
    by all of the published experts."""
    d, hd = config["hidden_size"], config["head_dim"]
    kv = config["num_key_value_heads"]
    published = config.get("published", {}).get(
        "num_experts", config["num_experts"])
    routed = config["num_experts_per_tok"] * config["num_experts"] / published
    parts = dict.fromkeys(
        ("projections", "cores", "dense_mlp", "experts", "head"), 0.0)
    for i in config["layers_held"]:
        h = config["num_attention_heads_per_layer"][i]
        gate = h if config.get("gating") else 0
        parts["projections"] += 2.0 * d * (2 * h * hd + 2 * kv * hd + gate)
        window = config["sliding_window"] \
            if config["layer_types"][i] == "sliding_attention" else seq_len
        pairs = opcount_window.band_pairs(seq_len, seq_len, window) / seq_len
        parts["cores"] += 4.0 * h * hd * pairs
        if config["mlp_layer_types"][i] == "dense":
            parts["dense_mlp"] += 6.0 * d * config["intermediate_size"]
        else:
            parts["experts"] += 2.0 * d * published + 6.0 * d * (
                config["shared_expert_intermediate_size"]
                + routed * config["moe_intermediate_size"])
    parts["head"] = 2.0 * d * config["vocab_size"]
    return parts


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: three times the forward pass, as
    ``opcount.lm_train_flops_per_token`` has it (two products in the backward
    pass for each of the forward's; recomputation is not counted)."""
    return 3.0 * sum(forward_parts(config, seq_len).values())
