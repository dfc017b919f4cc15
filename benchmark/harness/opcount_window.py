"""Operations and bytes of causal attention under a sliding window with
grouped K/V heads, from shapes alone: only the band ``0 <= i - j < window``
counts, and a K/V head is read once for all the query heads that share it
(``opcount.flash_forward`` / ``flash_backward`` count the whole causal half
and one K/V head a query head)."""

from __future__ import annotations

from typing import Tuple


def band_pairs(sq: int, sk: int, window: int) -> float:
    """(row, column) pairs a row sees, ends aligned: row ``i`` of ``sq`` is
    at position ``i + sk - sq`` and sees ``min(position + 1, window)``."""
    first = sk - sq
    ramp = max(0, min(sq, window - 1 - first))      # rows still under window
    seen_ramp = ramp * (first + 1) + ramp * (ramp - 1) / 2.0
    return seen_ramp + (sq - ramp) * float(window)


def flash_band(b: int, h: int, hk: int, sq: int, sk: int, d: int,
               window: int, backward: bool, itemsize: int = 2
               ) -> Tuple[float, float]:
    """(operations, bytes) of one call ``q [b,h,sq,d]``, ``k/v [b,hk,sk,d]``.
    Forward: two products a pair; reads q, k, v, writes o. Backward: five
    (the scores again, dV, dP, dQ, dK); reads q, k, v, o, do, writes dq, dk,
    dv. The repository's two backward kernels each form the scores and dP
    again: recomputation, not counted."""
    pairs = band_pairs(sq, sk, window)
    per_pair, passes = (10.0, 4) if backward else (4.0, 2)
    flops = per_pair * b * h * pairs * d
    nbytes = itemsize * b * d * passes * (h * sq + hk * sk)
    return flops, nbytes
