"""Operations and bytes that the algorithms need, from shapes alone.

XLA's ``cost_analysis()`` counts a scan body once, counts recomputation, and
counts zero for a Pallas ``tpu_custom_call``; these functions count what the
mathematics requires and nothing else. A multiply-add is two operations.
"""

from __future__ import annotations

from typing import Dict, Tuple


def block_matmul_params(d_model: int, d_ff: int) -> int:
    """Weights of one transformer block that a token is multiplied by:
    query, key, value, output (4 d^2) and the two MLP matrices (2 d d_ff)."""
    return 4 * d_model * d_model + 2 * d_model * d_ff


def block_forward_flops(tokens: int, seq_len: int, d_model: int, d_ff: int,
                        causal: bool) -> float:
    """Forward operations of one block over ``tokens`` tokens in sequences
    of ``seq_len``: 2 per weight per token, plus attention's two products
    (QK^T and PV, 2 * seq_len * d_model each per token), halved when causal
    because half of the score matrix is never needed."""
    attn = 4.0 * seq_len * d_model * (0.5 if causal else 1.0)
    return tokens * (2.0 * block_matmul_params(d_model, d_ff) + attn)


def lm_train_flops_per_token(n_layer: int, d_model: int, d_ff: int,
                             vocab: int, seq_len: int) -> float:
    """Forward and backward operations per trained token of a decoder-only
    LM with a tied output: 6 per multiplied weight (the embedding lookup is
    no product, the output head is) plus attention's 12 L S d, causal halved.
    Recomputation is not counted."""
    n_matmul = n_layer * block_matmul_params(d_model, d_ff) + d_model * vocab
    return 6.0 * n_matmul + 6.0 * n_layer * seq_len * d_model


def flash_forward(b: int, h: int, sq: int, sk: int, d: int, causal: bool,
                  itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of one attention forward call ``[b,h,sq,d]`` x
    ``[b,h,sk,d]``: two products; reads q, k, v and writes o once."""
    flops = 4.0 * b * h * sq * sk * d * (0.5 if causal else 1.0)
    nbytes = itemsize * b * h * d * (2 * sq + 2 * sk)
    return flops, nbytes


def flash_backward(b: int, h: int, sq: int, sk: int, d: int, causal: bool,
                   itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of the backward pass of that call: five products
    (the scores again, dV, dP, dQ, dK); reads q, k, v, o, do and writes dq,
    dk, dv once. The repository splits it into two kernels that each form
    the scores and dP again: that is recomputation, and is not counted."""
    flops = 10.0 * b * h * sq * sk * d * (0.5 if causal else 1.0)
    nbytes = itemsize * b * h * d * (4 * sq + 4 * sk)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float,
                     peaks: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
