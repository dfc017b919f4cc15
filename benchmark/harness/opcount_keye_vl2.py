"""Operations and bytes of the ``KeyeVL2`` language model trained on text
rows under learned sparse attention, for what one chip holds, from the
configuration's file and the share of the causal pairs the step's selections
kept (``opcount.py`` says what counts). **A count is of the work the
mathematics needs, whatever implements it**: attention's products over the
pairs a row *keeps* (``kept_share`` of the causal triangle), not over the
triangle the repository's kernels walk; the indexer's scores over the whole
triangle (every causal pair has to be scored before any can be dropped) and
their backward pass over the kept pairs (the loss reads no other). The
loss's second pass over ``q . k`` is recomputation and is not counted."""

from __future__ import annotations

from typing import Any, Dict, Tuple


def causal_pairs(length: int) -> float:
    return length * (length + 1) / 2.0


def kept_share_expected(length: int, topk: int) -> float:
    """The share of the causal pairs kept where no score ties: every pair of
    the first ``topk`` rows, ``topk`` a row after."""
    if length <= topk:
        return 1.0
    return (causal_pairs(topk) + topk * (length - topk)) \
        / causal_pairs(length)


def flash_selected(rows: int, h: int, hk: int, length: int, d: int,
                   kept_share: float, backward: bool, itemsize: int = 2
                   ) -> Tuple[float, float]:
    """(operations, bytes) of one attention call over ``rows`` rows of
    ``length`` positions under a selection. Forward: two products a kept
    pair; reads q, k, v and the selection's bits (one a causal pair, packed),
    writes o. Backward: five products a kept pair; reads q, k, v, o, do and
    the bits, writes dq, dk, dv."""
    per_pair, passes = (10.0, 4) if backward else (4.0, 2)
    kept = kept_share * causal_pairs(length)
    flops = per_pair * rows * h * kept * d
    nbytes = itemsize * rows * d * passes * length * (h + hk) \
        + rows * causal_pairs(length) / 8.0
    return flops, nbytes


def index_select(rows: int, hi: int, di: int, length: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one layer's selection: the products of ``I``
    over the causal triangle (``hi`` heads of ``di``); one read of ``qI``,
    ``kI`` and the float32 ``w``, one write of the bits. Finding the
    threshold is comparisons, which the peak of the matrix unit does not
    count."""
    flops = 2.0 * rows * hi * di * causal_pairs(length)
    nbytes = rows * length * (itemsize * (hi * di + di) + 4.0 * hi) \
        + rows * causal_pairs(length) / 8.0
    return flops, nbytes


def forward_parts(config: Dict[str, Any], length: int, kept_share: float
                  ) -> Dict[str, float]:
    """Forward operations per trained token by part (a multiply-add is two),
    summed over the layers held."""
    d, hd = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    sa = config["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    published = config["published"]["num_experts"]
    routed = config["num_experts_per_tok"] * config["num_experts"] / published
    layers = len(config["layers_held"])
    pairs = causal_pairs(length) / length  # causal pairs a token
    return {
        "projections": layers * 2.0 * d * (2 * h * hd + 2 * kv * hd),
        "cores": layers * 4.0 * h * hd * kept_share * pairs,
        "indexer_projections": layers * 2.0 * d * (hi * di + di + hi),
        "index_scores": layers * 2.0 * hi * di * pairs,
        "router": layers * 2.0 * d * published,
        "experts": layers * 6.0 * d * routed
        * config["moe_intermediate_size"],
        "head": 2.0 * d * config["vocab_size"],
    }


def train_flops_per_token(config: Dict[str, Any], length: int,
                          kept_share: float) -> float:
    """Forward and backward: three times the forward pass, but for the
    indexer's scores, whose backward pass (two products for the forward's
    one) runs over the kept pairs alone. The blocks' recomputation is not
    counted."""
    parts = forward_parts(config, length, kept_share)
    scores = parts.pop("index_scores")
    return 3.0 * sum(parts.values()) + scores * (1.0 + 2.0 * kept_share)
