"""Operations a training step needs per token in the ``granitemoehybrid``
decoder without experts: Mamba-2 mixers and attention layers by
``layer_types``, a gated MLP in every layer, a tied head; counted from the
configuration's file for the layers and vocabulary rows this chip holds, as
``opcount_zaya1`` counts ZAYA's. A multiply-add is two operations."""

from __future__ import annotations

from typing import Any, Dict

from .opcount_ssd import mamba_layers, scan_forward_flops_per_token


def forward_parts(config: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    """Forward operations per token by part, summed over the layers held.
    A Mamba layer: ``in_proj`` to ``2 inner + 2 G N + H``, the taps, the scan
    (``opcount_ssd``), ``out_proj``. An attention layer: q and o at the
    hidden size, k and v at the K/V heads, the core over the ``(seq_len +
    1) / 2`` pairs a row sees on average. The norms, the gate and the
    multipliers are not counted (no products)."""
    d, layers = config["hidden_size"], len(config["layers_held"])
    mamba = mamba_layers(config)
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    conv = inner + 2 * config["mamba_n_groups"] * config["mamba_d_state"]
    return {
        "ssm_projections": mamba * 2.0 * d * (
            inner + conv + config["mamba_n_heads"] + inner),
        "ssm_conv": mamba * 2.0 * config["mamba_d_conv"] * conv,
        "ssm_scan": mamba * scan_forward_flops_per_token(config),
        "attn_projections": (layers - mamba) * 2.0 * d * (
            2 * d + 2 * kv * (d // heads)),
        "attn_cores": (layers - mamba) * 4.0 * d * (seq_len + 1) / 2.0,
        "mlp": layers * 6.0 * d * config["shared_intermediate_size"],
        "head": 2.0 * d * config["vocab_size"],
    }


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward and backward: three times the forward pass (two products in
    the backward pass for each of the forward's; the blocks' recomputation
    is not counted)."""
    return 3.0 * sum(forward_parts(config, seq_len).values())
