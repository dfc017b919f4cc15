"""Operations and bytes a state-space layer's scan needs, from the
configuration and the run's shape alone, the same whatever implements the
scan (``opcount.py`` says what counts; a multiply-add is two operations).

The operations are those of the chunked (SSD) algorithm at the published
chunk ``Q`` (``deeplearning_cfn_tpu/ops/ssd.py`` lists its four steps), a
token and a layer, for ``H`` heads of ``P`` with a state of ``N`` in ``G``
groups:

    C B^T inside a chunk, once a group          2 Q N G
    (decay * C B^T) times dt x, a head          2 Q P H
    a chunk's closing state B^T (decay dt x)    2 N P H
    C times the entering state                  2 N P H

and nothing for the exponentials, the running sums or the hand-over between
chunks (no products). The whole ``Q x Q`` block is counted, not its causal
half: the algorithm multiplies it whole. Backward is twice forward (two
products for each of the forward's); recomputation is not counted.

The bytes are ``x``, ``B``, ``C``, ``dt`` and ``y`` and their cotangents,
each read or written once: bfloat16 but ``dt``, which is float32.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def scan_forward_flops_per_token(config: Dict[str, Any]) -> float:
    q, n, g = (config["mamba_chunk_size"], config["mamba_d_state"],
               config["mamba_n_groups"])
    h, p = config["mamba_n_heads"], config["mamba_d_head"]
    return 2.0 * q * n * g + 2.0 * q * p * h + 4.0 * n * p * h


def scan_bytes_per_token(config: Dict[str, Any]) -> float:
    """Forward and backward together: five arrays and five cotangents."""
    inner = config["mamba_n_heads"] * config["mamba_d_head"]
    bc = config["mamba_n_groups"] * config["mamba_d_state"]
    return 2.0 * (2 * (2 * inner + 2 * bc) + 4 * config["mamba_n_heads"])


def mamba_layers(config: Dict[str, Any]) -> int:
    return sum(config["layer_types"][i] == "mamba"
               for i in config["layers_held"])


def scan_step(config: Dict[str, Any], tokens: int) -> Tuple[float, float]:
    """(operations, bytes) of a training step's scans over ``tokens``
    tokens, forward and backward, in every Mamba layer held."""
    layers = mamba_layers(config)
    return (3.0 * scan_forward_flops_per_token(config) * tokens * layers,
            scan_bytes_per_token(config) * tokens * layers)
