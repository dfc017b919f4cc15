"""Operations and bytes of the ``sdar_moe`` decoder trained as a
block-diffusion model, for what one chip holds, from the configuration's file
alone (``opcount.py`` says what counts). A step runs ``2 L`` positions (the
noised copy and the clean row) through every block and the head on the ``L``
noised ones; attention needs the live pairs of the layout and nothing else:
``L**2 + L * b`` a head of the ``(2 L)**2`` (a noised block sees itself and
the clean blocks before it, a clean block the clean blocks up to itself)."""

from __future__ import annotations

from typing import Any, Dict, Tuple


def live_pairs(length: int, block: int) -> float:
    """(row, column) pairs a head computes for one row of ``length`` tokens
    in blocks of ``block``: ``L b`` noised over noised, ``L (L - b) / 2``
    noised over clean, ``L (L + b) / 2`` clean over clean."""
    return float(length) * (length + block)


def flash_layout(rows: int, h: int, hk: int, length: int, block: int, d: int,
                 backward: bool, itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one attention call over ``rows`` rows of ``2
    L`` positions, ``q [rows, h, 2 L, d]``, ``k/v [rows, hk, 2 L, d]``.
    Forward: two products a live pair; reads q, k, v, writes o, a K/V head
    once for its group. Backward: five products (the scores again, dV, dP,
    dQ, dK); reads q, k, v, o, do, writes dq, dk, dv. The repository's two
    backward kernels each form the scores and dP again, and its kernels
    compute whole sub-tiles under the edges: neither is counted."""
    per_pair, passes = (10.0, 4) if backward else (4.0, 2)
    flops = per_pair * rows * h * live_pairs(length, block) * d
    nbytes = itemsize * rows * d * passes * 2 * length * (h + hk)
    return flops, nbytes


def forward_parts(config: Dict[str, Any], length: int) -> Dict[str, float]:
    """Forward operations per trained data token by part (a multiply-add is
    two), summed over the layers held: two positions a token through the
    projections, the router (all the published experts wide) and the experts
    (a uniform router's expectation of a position's
    ``num_experts_per_tok`` that land on the experts held here), the live
    pairs a token, the head once."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    published = config["published"]["num_experts"]
    routed = config["num_experts_per_tok"] * config["num_experts"] / published
    block = config["noise"]["block_length"]
    layers = len(config["layers_held"])
    return {
        "projections": layers * 2 * 2.0 * d * (2 * h * hd + 2 * kv * hd),
        "cores": layers * 4.0 * h * hd * live_pairs(length, block) / length,
        "router": layers * 2 * 2.0 * d * published,
        "experts": layers * 2 * 6.0 * d * routed
        * config["moe_intermediate_size"],
        "head": 2.0 * d * config["vocab_size"],
    }


def train_flops_per_token(config: Dict[str, Any], length: int) -> float:
    """Forward and backward: three times the forward pass (two products in
    the backward pass for each of the forward's; the blocks' recomputation
    is not counted)."""
    return 3.0 * sum(forward_parts(config, length).values())
