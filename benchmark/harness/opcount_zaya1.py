"""Operations a training step needs per token in the ``zaya`` decoder: an
attention whose projections go down into a latent, two short convolutions
there, a causal core over grouped K/V heads, an MLP router and one routed
expert a token, a tied head; counted from the configuration's file for the
layers, experts and vocabulary rows this chip holds, as
``opcount_sparse_lm`` counts Laguna's."""

from __future__ import annotations

from typing import Any, Dict


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations per token by part (a multiply-add is two), summed
    over the layers held. The core counts the ``(seq_len + 1) / 2`` pairs a
    row sees on average; a token's routed expert counts at a uniform
    router's expectation, ``num_experts_per_tok`` times the share of the
    published experts held here; the router multiplies for all of them."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    published = config.get("published", {}).get(
        "num_experts", config["num_experts"])
    routed = config["num_experts_per_tok"] * config["num_experts"] / published
    rh, layers = config["router_hidden_size"], len(config["layers_held"])
    latent = (h + kv) * hd
    return {
        # q and k down, the two value halves (kv * hd together), o back up.
        "projections": layers * 2.0 * d * (latent + kv * hd + h * hd),
        # A tap a channel; then a head_dim-square block a head and a tap.
        "convolutions": layers * 2.0 * latent * (
            config["cca_time0"] + config["cca_time1"] * hd),
        "cores": layers * 4.0 * h * hd * (seq_len + 1) / 2.0,
        "router": layers * 2.0 * (d * rh + 2 * rh * rh + rh * published),
        "experts": layers * 6.0 * d * routed
        * config["moe_intermediate_size"],
        "head": 2.0 * d * config["vocab_size"],
    }


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: three times the forward pass (two products in
    the backward pass for each of the forward's; recomputation is not
    counted)."""
    return 3.0 * sum(forward_parts(config, seq_len).values())
