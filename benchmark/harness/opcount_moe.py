"""Operations and bytes an expert layer's grouped matmuls need for the rows
really routed, from shapes alone (``opcount.py`` says what counts). One
gated expert is ``(silu(x W1) * x W3) W2``: ``W1 | W3`` one ``[d, 2 m]``
product, ``W2`` one ``[m, d]`` product."""

from __future__ import annotations

from typing import Tuple


def grouped_matmul(rows: float, groups: int, k: int, n: int, backward: bool,
                   itemsize: int = 2) -> Tuple[float, float]:
    """(operations, bytes) of ``rows`` sorted rows ``[rows, k]`` times their
    group's ``[k, n]`` of ``groups`` matrices. Forward: one product; reads
    the rows and every matrix, writes the result once. Backward: two
    products (the rows' gradient, the matrices' gradient); reads the result's
    gradient twice, the matrices and the rows once, writes both gradients."""
    flops = 2.0 * rows * k * n
    moved = rows * (k + n) + groups * k * n
    if backward:
        return 2.0 * flops, itemsize * (2.0 * moved + rows * n)
    return flops, itemsize * moved


def experts_step(rows: float, layers: int, experts: int, d_model: int,
                 width: int) -> Tuple[float, float]:
    """(operations, bytes) of a training step's grouped matmuls, forward and
    backward: ``rows`` (token, choice) rows over all ``layers`` expert
    layers, each holding ``experts`` experts of ``width``."""
    flops = nbytes = 0.0
    for k, n in ((d_model, 2 * width), (width, d_model)):
        for backward in (False, True):
            f, b = grouped_matmul(rows / layers, experts, k, n, backward)
            flops += layers * f
            nbytes += layers * b
    return flops, nbytes
