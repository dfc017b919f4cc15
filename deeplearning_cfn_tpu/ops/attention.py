"""Fused multi-head attention: Pallas flash kernel + jnp reference.

Replaces the reference workloads' cuDNN/fused-CUDA attention (BERT, NMT —
SURVEY.md §3.3 "cuDNN / framework kernels"). Design:

- ``attention_reference``: straight jnp softmax(QKᵀ/√d + bias)V — the
  numerics oracle and the CPU/GPU fallback. XLA fuses this well already;
  the flash kernel's win is avoiding the [S,S] materialization in HBM.
- ``_flash_forward``: Pallas TPU kernel, online-softmax blocked over the KV
  sequence (flash attention). Grid is (batch, heads, Q blocks, KV blocks)
  with the KV axis innermost: running (m, l, acc) stats live in VMEM
  scratch and every operand is block-mapped, so per-step VMEM is O(block)
  — sequence length is bounded by HBM, not VMEM (cross-host long-context
  is the ring-attention path in ring_attention.py).
- ``_flash_backward``: FlashAttention-2-style blocked dq/dk/dv kernels with
  the same grid-accumulation structure — the forward saves only O and the
  per-row logsumexp, the backward recomputes P per block, so training
  memory is O(S) too (bias-free path).
- ``fused_attention``: public entry — on TPU dispatches to the kernels,
  except the hardware-measured short-sequence window (Sk < 1024, backward
  intermediate under cap) where XLA's own fused attention is faster;
  reference elsewhere. With a bias, the backward falls back to the
  reference VJP (a trainable bias's cotangent is [Sq,Sk]-shaped anyway).

Shapes: q [B, H, Sq, D]; k/v [B, H, Sk, D]; optional additive bias
broadcastable to [B, H, Sq, Sk] (use -inf for padding); returns [B, H, Sq, D].
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import get_tracer
from ..parallel.kernels import batch_axes_of, rows_spec, shard_rows
from .sparse_index import LANES, SUPER, packed_width, selected_bits, \
    unpack_selection

# The names of the flash forward kernel's output and row statistics
# (logsumexp) among a differentiated call's residuals, for
# ``jax.checkpoint_policies.save_only_these_names``.
FLASH_OUT = "flash_out"
FLASH_LSE = "flash_lse"

_NEG_INF = -1e30  # large-negative instead of -inf: keeps masked softmax NaN-free

# Flash kernel tiling: the grid tile (block) one grid step holds in VMEM, and
# the sub-tile in whose units a causal call's backward kernels leave out,
# inside the grid step, what lies above the diagonal. Swept on a v5e chip on
# 2026-09-28 (tools/flash_tile_sweep.py; the table is in PERF.md, PR 25),
# causal bf16 [16,12,1024,64], [1,8,2048,128], [1,12,8192,64], two sq < sk:
# - The 1024 x 1024 block stands (2026-07-31, BERT-shaped d=64 cases at S in
#   {512, 1024, 2048, 8192}: it beat 256 x 128 grid tiles by 1.3-4.7x fwd+bwd;
#   a grid step costs more than the tiles it would skip).
# - Everything about a sub-tile has to be static. Loops over sub-tiles with
#   bounds from program_id ran 2-3.5x slower than one whole tile at S = 8192;
#   the schedule below is decided from the static lengths instead.
# - The backward kernels follow the live share: 2.13 ms a call as one piece,
#   1.48 at 256-square, 1.54 at 128-square, 1.66 at 512 ([16,12,1024,64]).
# - The forward kernel does not: 0.81 ms as one piece, 0.95 at 512, 1.04 at
#   256, 0.74 at 128, 1.17 at 64. And every piece is traced and lowered again
#   for every layer: 128-square in all three kernels added 4 s to a training
#   step's first call, 256-square in the backward pair alone under 1 s.
# A windowed call (2026-09-28, bf16 [2,64,4096,128] over 8 K/V heads, window
# 512, on a grid of the band, ms a call forward / dK/dV / dQ; PERF.md, PR 29):
# - 1024-square blocks of 256-square sub-tiles 4.18 / 3.33 / 2.23; of
#   128-square ones 3.06 / 3.07 / 2.44: the forward follows the masked area
#   (150 sub-tiles of 1024 computed for 45 of 256), the backward pair does not
#   (5.51 together against 5.56) and keeps the fewer pieces.
# - Smaller blocks lose: 512-square 5.38 / 3.24 / 2.71 at 256, 3.71 / 3.51 /
#   2.86 at 128 (twice the grid steps for the same sub-tiles), 1024 x 512
#   5.03 / 4.79 / 3.11 (three K/V blocks a q block, five of dK/dV's 16 steps
#   dead).
# - 2048-square blocks of 128-square sub-tiles win the forward by a further
#   0.33 ms (2.72) and no more fit VMEM at d = 256; not taken.
_BLOCK_Q = 1024
_BLOCK_K = 1024
_SUB_Q = 256
_SUB_K = 256
_WINDOW_FWD_SUB = 128  # both ways, in a windowed call's forward kernel
# Row statistics (logsumexp, delta) are stored lane-replicated with a
# trailing dim of 8: Mosaic requires a block's last two dims to be
# (divisible by 8, divisible by 128) or equal to the array's — a bare
# [..., block_q] row vector satisfies neither on real hardware (it only
# works in interpret mode, which skips the check).
_STAT_LANES = 8


def _ceil8(n: int) -> int:
    return max(8, -(-n // 8) * 8)


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    """The mask of a block-diffusion training row (``fused_attention``'s
    ``layout``): ``2 * length`` positions, a noised copy of the row then the
    clean row, each cut into blocks of ``block``. With ``B(i)`` the block of
    a position within its copy, a noised position ``i`` sees the noised
    ``j`` with ``B(j) == B(i)`` and the clean ``j`` with ``B(j) < B(i)``; a
    clean ``i`` sees the clean ``j`` with ``B(j) <= B(i)`` and no noised
    position. Of the ``(2 length)**2`` pairs ``length**2 + length * block``
    are live. Static wherever it goes: a pytree without leaves, so that a
    recomputed block (``flax.linen.remat``) takes it as a keyword."""
    length: int
    block: int

    def __post_init__(self):
        if self.block < 1 or self.length < 1 or self.length % self.block:
            raise ValueError(f"a row of {self.length} positions is not a "
                             f"whole number of blocks of {self.block}")

    def mask(self) -> jnp.ndarray:
        """``[2 length, 2 length]`` booleans, straight from the definition:
        what the XLA path and ``attention_reference`` mask by."""
        at = jnp.arange(2 * self.length)
        clean, blk = at >= self.length, (at % self.length) // self.block
        q_clean, k_clean = clean[:, None], clean[None, :]
        qb, kb = blk[:, None], blk[None, :]
        return jnp.where(k_clean, jnp.where(q_clean, kb <= qb, kb < qb),
                         ~q_clean & (kb == qb))


def _mask_name(window: int = 0, layout: Optional[BlockDiffusion] = None,
               selected: bool = False) -> str:
    """The ``mask`` label of a call's flash gauges and counter."""
    if layout is not None:
        return "block_diffusion"
    if selected:
        return "selected"
    return "window" if window else ""


def _tile_plan(sq: int, sk: int, d: int, causal: bool,
               backward: bool = False, window: int = 0,
               layout: Optional[BlockDiffusion] = None
               ) -> Tuple[int, int, int, int]:
    """``(block_q, block_k, sub_q, sub_k)`` for one call's forward kernel or
    its two backward kernels, from what the call can observe (pure,
    unit-tested). The backward kernels of a causal call compute their grid
    tile in ``sub``-sized pieces, so that what lies above the diagonal is
    left out inside the grid step; their blocks are whole numbers of
    sub-tiles. Everything else keeps one piece per grid tile (``sub ==
    block``), the kernel it has had since the first sweep: a non-causal call
    has nothing to leave out, a call with a bias has no backward kernel, and
    the forward kernel gains only at 128-square, where tracing its 16 pieces
    a layer costs a training step's set-up more than it saves (the sweep
    above). A call with a ``window`` has sub-tiles in all three kernels: a
    band of rows needs ``window + sub`` columns whatever the length, so the
    forward leaves out more than the pieces cost it, and most at 128-square,
    where the band has few pieces to trace. A ``layout`` is planned over one
    copy of its row (each is padded to whole grid tiles, which are whole
    numbers of the layout's blocks) with the sub-tiles of a windowed call:
    its edges are as narrow, three tiles a band of rows, and it was not
    swept apart."""
    del d  # one rule held for d = 64 and d = 128
    edges = window or layout is not None
    fwd_sub = _WINDOW_FWD_SUB if edges and not backward else None
    if layout is not None:
        sq = sk = layout.length

    def one(s, block, sub):
        if not ((causal or layout is not None) and (backward or edges)) \
                or s <= sub:
            b = min(block, _ceil8(s))
            return b, b
        return min(block, -(-s // sub) * sub), sub

    block_q, sub_q = one(sq, _BLOCK_Q, fwd_sub or _SUB_Q)
    block_k, sub_k = one(sk, _BLOCK_K, fwd_sub or _SUB_K)
    return block_q, block_k, sub_q, sub_k


def _check_layout_plan(layout: BlockDiffusion, plan) -> None:
    """What the layout's kernels ask of a plan, ``_tile_plan``'s or a test's
    (``_flash_forward`` and ``_flash_backward`` call it on either)."""
    block_q, block_k = plan[:2]
    if block_q != block_k or block_q % layout.block \
            or layout.block & (layout.block - 1):
        raise ValueError(
            f"the flash kernels take a block-diffusion layout whose block "
            f"length is a power of two that divides their square grid tile;"
            f" got blocks of {layout.block} under tiles of {block_q} x "
            f"{block_k}: implementation='reference' takes any")


# The three cases of a sub-tile, decided from positions alone (a row's
# position is its index plus ``seq_k - seq_q``: the diagonal aligns the ends):
# above the diagonal (its first column lies past its last row) it is not
# computed; crossed by the diagonal it is computed under the mask; wholly
# below, it is computed with no mask at all. (Padded columns lie above the
# diagonal of every real row, so the causal cases cover them.) A sliding
# window (row ``i`` sees columns ``i - window < j <= i``) adds its far edge,
# with the same three cases on the other side. Sequence
# lengths are static, so all of it is decided while the kernel is traced:
# ``_schedule`` gives, for each offset ``rel`` between a grid tile's first row
# and its first column that the call's grid has, the pieces of the tile to
# compute. A kernel's grid step looks its ``rel`` up and runs those pieces;
# no loop bound and no slice depends on a ``program_id``.


_CAUSAL, _WINDOW = 1, 2
# A block-diffusion layout's three edges, each within one quadrant of the
# ``[noised | clean]`` square (the fourth, clean rows over noised columns, is
# dead): a position's block against a column's. They are also what a grid
# tile on an edge is called (``_BlockWalk.rel``); 0 is a tile under an edge.
_SAME_BLOCK, _EARLIER_BLOCKS, _BLOCKS_UP_TO = 4, 8, 16
_BLOCK_EDGES = _SAME_BLOCK | _EARLIER_BLOCKS | _BLOCKS_UP_TO


def _subtile_kind(q0: int, q1: int, k0: int, k1: int, window: int,
                  edge: int = 0, block: int = 0):
    """A sub-tile of rows at positions ``[q0, q1)`` and columns ``[k0, k1)``:
    None where nothing of it is needed (its first column lies past its last
    row, or its last column ``window`` or more before its first row), else
    the masks it needs: ``_CAUSAL`` where the diagonal crosses it,
    ``_WINDOW`` where the window's far edge does, 0 where neither.

    With an ``edge`` the positions are those within a copy of a
    block-diffusion row cut into blocks of ``block``, in the quadrant whose
    edge it is, and the answer is by the blocks the rows and the columns
    touch: None where no pair is live, 0 where every pair is, else the
    ``edge`` itself."""
    if edge:
        qb0, qb1 = q0 // block, (q1 - 1) // block
        kb0, kb1 = k0 // block, (k1 - 1) // block
        if edge == _SAME_BLOCK:
            if kb0 > qb1 or kb1 < qb0:
                return None
            return 0 if qb0 == qb1 == kb0 == kb1 else edge
        reach = edge == _BLOCKS_UP_TO  # a clean row sees its own block too
        if kb0 >= qb1 + reach:
            return None
        return 0 if kb1 < qb0 + reach else edge
    if k0 > q1 - 1 or (window and q0 - (k1 - 1) >= window):
        return None
    kind = _CAUSAL if k1 - 1 > q0 else 0
    if window and (q1 - 1) - k0 >= window:
        kind |= _WINDOW
    return kind


def _staircase(rel: int, plan, by_columns: bool, window: int = 0,
               edge: int = 0, block: int = 0):
    """The pieces of a grid tile whose first row stands at position ``rel``
    of its own columns, band by band: ``[((o0, o1), [(i0, i1, kind),
    ...]), ...]``. A band is ``sub_q`` rows with pieces of columns (forward,
    dQ) or, ``by_columns``, ``sub_k`` columns with pieces of rows (dK/dV).
    Neighbouring sub-tiles of a band that need the same masks
    (``_subtile_kind``) are one piece."""
    block_q, block_k, sub_q, sub_k = plan
    n_r, n_c = block_q // sub_q, block_k // sub_k
    inner = sub_q if by_columns else sub_k
    bands = []
    for o in range(n_c if by_columns else n_r):
        pieces = []
        for i in range(n_r if by_columns else n_c):
            r, c = (i, o) if by_columns else (o, i)
            kind = _subtile_kind(rel + r * sub_q, rel + (r + 1) * sub_q,
                                 c * sub_k, (c + 1) * sub_k, window, edge,
                                 block)
            if kind is None:
                continue
            if pieces and pieces[-1][1:] == (i * inner, kind):
                pieces[-1] = (pieces[-1][0], (i + 1) * inner, kind)
            else:
                pieces.append((i * inner, (i + 1) * inner, kind))
        if pieces:
            outer = sub_k if by_columns else sub_q
            bands.append(((o * outer, (o + 1) * outer), pieces))
    return bands


def _grid_rels(sq: int, sk: int, block_q: int, block_k: int):
    """``rel`` of every grid tile of a call: its first row's position less
    its first column's."""
    return [iq * block_q - kb * block_k + sk - sq
            for iq in range(-(-sq // block_q))
            for kb in range(-(-sk // block_k))]


def _schedule(sq: int, sk: int, plan, causal: bool, by_columns: bool = False,
              mask_whole: bool = False, window: int = 0,
              layout: Optional[BlockDiffusion] = None):
    """What a grid step computes, as cases ``(lo, hi, bands)``: the bands
    (as ``_staircase`` gives them) for a tile whose ``rel`` is ``lo == hi``,
    or at least ``lo`` where ``hi`` is None, or anything where both are. A
    tile no case takes lies above the diagonal.

    With one sub-tile per block every live tile is one piece, masked as it
    has always been (``mask_whole`` for a non-causal call: the backward masks
    padded columns itself, the forward takes them as a bias): the kernel of
    before the sub-tiles. Otherwise a tile the diagonal crosses gets its
    staircase, and a tile below it is one unmasked piece. With a ``window``
    a tile far below the diagonal is not computed either, so every offset
    the grid has gets its own case, or none.

    A ``layout``'s cases are keyed by what ``_BlockWalk.rel`` calls a tile:
    the edge it lies on (the diagonal tile of a quadrant: its staircase
    under that edge) or 0 (a tile under a staircase: one unmasked piece)."""
    block_q, block_k, sub_q, sub_k = plan
    outer, inner = (block_k, block_q) if by_columns else (block_q, block_k)
    if layout is not None:
        cases = [(0, 0, [((0, outer), [(0, inner, 0)])])] + [
            (edge, edge, _staircase(0, plan, by_columns, edge=edge,
                                    block=layout.block))
            for edge in (_SAME_BLOCK, _EARLIER_BLOCKS, _BLOCKS_UP_TO)]
        return [case for case in cases if case[2]]
    if window:
        cases = [(rel, rel, _staircase(rel, plan, by_columns, window))
                 for rel in sorted(set(_grid_rels(sq, sk, block_q, block_k)))]
        return [case for case in cases if case[2]]

    def whole(masked):
        return [((0, outer), [(0, inner, masked)])]

    if not causal:
        return [(None, None, whole(mask_whole))]
    if (sub_q, sub_k) == (block_q, block_k):
        return [(1 - block_q, None, whole(True))]
    crossed = sorted({rel for rel in _grid_rels(sq, sk, block_q, block_k)
                      if -block_q < rel < block_k - 1})
    return [(rel, rel, _staircase(rel, plan, by_columns))
            for rel in crossed] + [(block_k - 1, None, whole(False))]


def _bands_at(cases, rel: int):
    """The bands of the case that takes a tile at ``rel`` (``_run_schedule``
    decides the same inside a kernel), or none."""
    for lo, hi, bands in cases:
        if (lo is None or lo <= rel) and (hi is None or rel <= hi):
            return bands
    return []


def _subtile_counts(sq: int, sk: int, plan, cases, walk=None
                    ) -> Tuple[int, int, int]:
    """``(all, computed, masked)`` sub-tiles of one head of a call, counted
    from the ``cases`` (``_schedule``) its kernel runs; those of a
    block-diffusion layout's padded square through its ``walk``, which
    names its tiles."""
    block_q, block_k, sub_q, sub_k = plan
    tiles = [walk.rel(o, block) for o in range(walk.outer_blocks)
             for block in range(walk.outer_blocks)] \
        if isinstance(walk, _BlockWalk) \
        else _grid_rels(sq, sk, block_q, block_k)
    live = masked = 0
    for rel in tiles:
        for (o0, o1), pieces in _bands_at(cases, rel):
            for i0, i1, under_mask in pieces:
                area = (o1 - o0) * (i1 - i0) // (sub_q * sub_k)
                live += area
                masked += area * bool(under_mask)
    return len(tiles) * (block_q // sub_q) * (block_k // sub_k), live, masked


def _clip(x, lo, hi):
    """``max(min(x, hi), lo)`` of Python ints or of traced scalars: ``lo``
    where ``hi`` lies under it."""
    if all(isinstance(t, int) for t in (x, lo, hi)):
        return max(min(x, hi), lo)
    return jnp.maximum(jnp.minimum(x, hi), lo)


@dataclasses.dataclass(frozen=True)
class _Walk:
    """The innermost grid axis of one flash kernel: which inner block step
    ``j`` of outer block ``o`` stands for, and which block its index maps
    name. Outer blocks are q blocks and inner ones K/V blocks (forward, dQ)
    or, ``by_columns``, the other way round (dK/dV). All of it follows from
    the static lengths, as ``_schedule`` does, and works on Python ints (the
    gauges, the tests) as on ``program_id``s.

    ``span(o)`` is the first and last inner block with anything to compute:
    down to the window's far edge and up to the diagonal for a block of
    rows, from the diagonal to the window's far edge for a block of columns
    (``last < first`` where no row sees them). A windowed call's axis has
    as many ``steps`` as its widest span and starts at the span's first
    block; a call without a window keeps the whole sequence (a triangle has
    no fixed width). A step stands for one ``block`` (``rel`` is computed
    from it) and its index maps name that block clamped into the span
    (``named``), so a step outside the span names what its neighbour holds
    and nothing is copied for it. Where every step computes (a call that is
    not causal, a grid of one tile) nothing ``clamps``: step ``j`` is block
    ``j`` and names it, the index maps and kernel text of ever."""
    outer: int         # block sizes
    inner: int
    outer_blocks: int  # and how many the padded lengths hold
    inner_blocks: int
    shift: int         # sk - sq: a row's position is its index plus this
    window: int
    by_columns: bool
    clamps: bool

    def span(self, o):
        # Inner positions that block ``o``'s own reach: a row at position p
        # sees columns (p - window, p], a column c is seen by the rows at
        # positions [c, c + window).
        lo = hi = None
        if self.by_columns:
            lo = o * self.outer - self.shift
            if self.window:
                hi = lo + self.outer - 1 + self.window - 1
        else:
            hi = o * self.outer + self.outer - 1 + self.shift
            if self.window:
                lo = hi - (self.outer - 1) - (self.window - 1)
        last = self.inner_blocks - 1
        return (0 if lo is None else _clip(lo // self.inner, 0, last),
                last if hi is None else _clip(hi // self.inner, -1, last))

    @functools.cached_property
    def steps(self) -> int:
        if not (self.clamps and self.window):
            return self.inner_blocks
        spans = [self.span(o) for o in range(self.outer_blocks)]
        return max(1, max(last - first + 1 for first, last in spans))

    def block(self, o, j):
        """``(block, live)``: the inner block step ``j`` of outer block
        ``o`` stands for, and whether it lies in the span. A step past its
        span is not live whatever its ``rel`` (which can equal a live
        tile's); without a window ``rel`` alone says so, as ever."""
        if not (self.clamps and self.window):
            return j, True
        first, last = self.span(o)
        return first + j, first + j <= last

    def named(self, o, j):
        """The inner block that step's index maps name."""
        if not self.clamps:
            return j
        first, last = self.span(o)
        return _clip(self.block(o, j)[0], first, last)

    def rel(self, o, block):
        """A tile's first row's position less its first column's."""
        return (block * self.inner - o * self.outer if self.by_columns
                else o * self.outer - block * self.inner) + self.shift

    def place(self, o, block):
        """``(rel, q0, k0)``: what the tile's case is looked up by, and the
        positions its masks count its first row and first column from."""
        rel = self.rel(o, block)
        return rel, rel, 0

    @property
    def mask(self) -> str:
        """The ``mask`` label of the call's flash gauges (``_mask_name``)."""
        return _mask_name(self.window)


def _pick(cond, a, b):
    """``a if cond else b`` of Python ints or of traced scalars."""
    if isinstance(cond, bool):
        return a if cond else b
    return jnp.where(cond, a, b)


@dataclasses.dataclass(frozen=True)
class _BlockWalk(_Walk):
    """The innermost grid axis under a block-diffusion layout whose copies
    are ``half`` square grid tiles each: ``2 half`` outer blocks, noised
    before clean. An outer block has inner blocks in two runs, noised ones
    then clean ones (``_runs``), and a step stands for the next block of
    the first run, then of the second. A block of noised rows has its own
    noised columns and the clean columns up to its own block (to the block
    before where a tile is one block of the layout: ``strict``); a block of
    clean rows the clean columns up to its own; a block of noised columns
    its own rows; a block of clean columns the noised rows from its own
    block (the next, ``strict``) on and the clean rows from its own on. The
    axis has as many ``steps`` as the longest pair of runs, and a step past
    the runs names the last block of them, as a windowed call's does."""
    half: int = 0
    strict: int = 0

    def _runs(self, o):
        """``(first, count)`` of noised inner blocks, then of clean ones."""
        n, clean = self.half, o >= self.half
        at = o - _pick(clean, n, 0)
        if self.by_columns:
            return (_pick(clean, at + self.strict, at),
                    _pick(clean, n - at - self.strict, 1),
                    n + at, _pick(clean, n - at, 0))
        return (at, _pick(clean, 0, 1), n,
                at + 1 - _pick(clean, 0, self.strict))

    @functools.cached_property
    def steps(self) -> int:
        return (2 * self.half if self.by_columns else self.half + 1) \
            - self.strict

    def _at(self, o, j):
        first_a, n_a, first_b, n_b = self._runs(o)
        return _pick(j < n_a, first_a + j, first_b + j - n_a), n_a + n_b

    def block(self, o, j):
        block, count = self._at(o, j)
        return block, j < count

    def named(self, o, j):
        count = self._at(o, j)[1]
        return self._at(o, _clip(j, 0, count - 1))[0]

    def _halves(self, o, block):
        """The row block and the column block of a tile, each as ``(index
        within its copy, whether the copy is the clean one)``."""
        q, k = (block, o) if self.by_columns else (o, block)
        q_clean, k_clean = q >= self.half, k >= self.half
        return (q - _pick(q_clean, self.half, 0), q_clean,
                k - _pick(k_clean, self.half, 0), k_clean)

    def rel(self, o, block):
        """What a tile is called: the edge it lies on, 0 under an edge, -1
        where nothing of it is live."""
        q, q_clean, k, k_clean = self._halves(o, block)
        on_edge = _pick(q_clean, _pick(k_clean, _BLOCKS_UP_TO, -1),
                        _pick(k_clean, _EARLIER_BLOCKS, _SAME_BLOCK))
        return _pick(q == k, on_edge,
                     _pick(k_clean, _pick(q > k, 0, -1), -1))

    def place(self, o, block):
        q, _, k, _ = self._halves(o, block)
        return self.rel(o, block), q * self.outer, k * self.inner

    mask = "block_diffusion"


class _SelectedWalk(_Walk):
    """A causal call's walk under a selection (``fused_attention``'s
    ``selected``): the triangle's tiles as ever, each masked by the call's
    bit mask; only the gauges' label differs."""
    mask = "selected"


def _walk(sq: int, sk: int, plan, causal: bool, window: int,
          by_columns: bool, layout: Optional[BlockDiffusion] = None,
          selected: bool = False) -> _Walk:
    """The ``_Walk`` of one kernel of a call (``sq`` and ``sk`` of a
    ``layout``: its padded square's)."""
    block_q, block_k = plan[:2]
    if selected:
        n = -(-sq // block_q)
        return _SelectedWalk(block_k if by_columns else block_q,
                             block_q if by_columns else block_k, n, n, 0, 0,
                             by_columns, n != 1)
    if layout is not None:
        half = sq // (2 * block_q)
        return _BlockWalk(block_q, block_q, 2 * half, 2 * half, 0, 0,
                          by_columns, True, half,
                          int(block_q == layout.block))
    n_q, n_k = -(-sq // block_q), -(-sk // block_k)
    clamps = causal and (n_q, n_k) != (1, 1)
    if by_columns:
        return _Walk(block_k, block_q, n_k, n_q, sk - sq, window, True,
                     clamps)
    return _Walk(block_q, block_k, n_q, n_k, sk - sq, window, False, clamps)


_GRID_GAUGES = ("grid_steps", "dead_grid_steps", "dead_step_copies")


def _labels(kernel: str, mask: str) -> dict:
    """A flash gauge's labels: the kernel, and the call's ``mask`` where it
    has one of its own (``_mask_name``)."""
    return dict(kernel=kernel, mask=mask) if mask else dict(kernel=kernel)


def _grid_steps(walk: _Walk, cases, group: int = 1):
    """A kernel's grid steps for one head (one K/V head and its ``group``
    for dK/dV), in the order the grid runs them, through the index maps the
    ``pallas_call`` is given: ``[(outer, block, named, bands), ...]`` with
    the block the step stands for, the block index its innermost operands'
    map names, and the bands it computes (none: a dead step)."""
    named = _q_by_inner(walk, group) if walk.by_columns \
        else _kv_by_inner(walk, group)
    steps = []
    for o in range(walk.outer_blocks):
        for step in range(walk.steps * (group if walk.by_columns else 1)):
            block, live = walk.block(o, step % walk.steps)
            steps.append((o, block, named(0, 0, o, step), _bands_at(
                cases, walk.rel(o, block)) if live else []))
    return steps


def _record_grid(kernel: str, walk: _Walk, cases, group: int) -> None:
    """Three more gauges beside ``_record_subtiles``', each a count for one
    query head (the dK/dV kernel's innermost axis covers a group, so its
    counts are divided by ``group``): the grid's steps, the dead ones (no
    case runs), and the dead ones that start a copy no live step reads (a
    block other than the resident one, gone before a live step names it)."""
    steps = _grid_steps(walk, cases, group)
    dead = copies = 0
    read = False  # is the block a step names read before another is named?
    for at in reversed(range(len(steps))):
        _, _, named, bands = steps[at]
        held_on = at + 1 < len(steps) and steps[at + 1][2] == named
        read = bool(bands) or (held_on and read)
        dead += not bands
        copies += not bands and not read and not (
            at and steps[at - 1][2] == named)
    heads = group if walk.by_columns else 1
    registry = get_tracer().registry
    for name, count, what in zip(_GRID_GAUGES, (len(steps), dead, copies), (
            "grid steps of a flash kernel", "those in which no case runs",
            "dead steps that copy a block no live step reads")):
        registry.gauge(f"attention.flash.{name}", f"{what}, a query head"
                       ).set(count / heads, **_labels(kernel, walk.mask))


def _grid_gauges(kernel: str, window: int = 0,
                 layout: Optional[BlockDiffusion] = None,
                 selected: bool = False) -> Tuple[float, ...]:
    """What ``_record_grid`` last set for a kernel: ``(grid_steps,
    dead_grid_steps, dead_step_copies)`` a query head."""
    registry = get_tracer().registry
    return tuple(registry.gauge(f"attention.flash.{name}").value(
        **_labels(kernel, _mask_name(window, layout, selected)))
        for name in _GRID_GAUGES)


def _record_subtiles(kernel: str, counts: Tuple[int, int, int],
                     mask: str = "") -> None:
    """The mechanism's engagement is static, so it is two gauges set when a
    kernel is traced (docs/OBSERVABILITY.md); those of a call with a mask of
    its own are labelled ``mask="window"``, ``mask="block_diffusion"`` or
    ``mask="selected"`` beside the kernel."""
    total, live, masked = counts
    labels = _labels(kernel, mask)
    registry = get_tracer().registry
    registry.gauge(
        "attention.flash.live_subtile_share",
        "sub-tiles of the score matrix a flash kernel computes, of all",
    ).set(live / total, **labels)
    registry.gauge(
        "attention.flash.masked_subtile_share",
        "sub-tiles a flash kernel computes under the mask, of all",
    ).set(masked / total, **labels)


def _when(cond):
    """``pl.when``, decided in Python where the condition is static."""
    if isinstance(cond, bool):
        return lambda f: f() if cond else None
    from jax.experimental import pallas as pl
    return pl.when(cond)


def _run_schedule(cases, rel, band, live=True) -> None:
    """Inside a kernel: ``band(o0, o1, pieces)`` for every band of the case
    that takes this grid step's ``rel`` (a Python int where the grid is one
    tile), if the step is ``live`` (``_Walk.block``)."""
    def run(bands):
        for (o0, o1), pieces in bands:
            band(o0, o1, pieces)

    for lo, hi, bands in cases:
        if lo is None:
            cond = True
        elif hi is None:
            cond = rel >= lo
        else:  # a staircase: lo == hi
            cond = rel == lo
        if live is not True:
            cond = jnp.logical_and(live, cond)
        _when(cond)(functools.partial(run, bands))


def _below_diagonal(shape, q0, k0):
    """``k_pos <= q_pos`` on a score tile whose first row is at position
    ``q0`` and whose first column is at ``k0``."""
    rel = jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return rel <= q0 - k0


def _inside_window(shape, q0, k0, window):
    """``q_pos - k_pos < window`` on the same tile."""
    rel = jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
        - jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return rel > q0 - k0 - window


def _by_blocks(shape, q0, k0, edge, block):
    """A block-diffusion edge on a score tile whose first row is at position
    ``q0`` of its copy and whose first column is at ``k0`` of its own: the
    column's block against the row's (``block`` is a power of two)."""
    shift = block.bit_length() - 1
    qb = jax.lax.shift_right_logical(
        q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0), shift)
    kb = jax.lax.shift_right_logical(
        k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1), shift)
    if edge == _SAME_BLOCK:
        return kb == qb
    return kb < qb if edge == _EARLIER_BLOCKS else kb <= qb


def _kept(sel, r0, r1, c0, c1):
    """The pairs a selection keeps of a piece, rows ``[r0, r1)`` by columns
    ``[c0, c1)`` of a grid tile: ``sel`` is the tile's words (a ref ``[1,
    block_q, 128]``) and the bit its first 128 columns stand at
    (``ops/sparse_index.py`` has the layout)."""
    sel_ref, first_bit = sel
    return selected_bits(sel_ref[0, r0:r1, :], first_bit + c0 // LANES,
                         (c1 - c0) // LANES) != 0


def _selection_plan(selected, sq: int, plan):
    """A selected call's words padded to its grid, the index of the 128
    lanes that hold a K/V block's bits, and the bit its first columns stand
    at: ``(words, lanes_of(block), first_bit_of(block))``."""
    block_q, block_k, _, sub_k = plan
    sq_p = -(-sq // block_q) * block_q
    tiles = sq_p // block_k
    if block_k % LANES or sub_k % LANES or sq_p % block_k or (
            tiles > 1 and SUPER % block_k):
        raise ValueError(
            f"the flash kernels take a selection under grid tiles of whole "
            f"runs of {LANES} columns that divide {SUPER}; got {plan}")
    words = jnp.pad(selected, [
        (0, 0), (0, sq_p - selected.shape[1]),
        (0, packed_width(sq_p) - selected.shape[2])])
    per_run = max(SUPER // block_k, 1)
    return (words, lambda block: block // per_run,
            lambda block: (block % per_run) * (block_k // LANES))


# ---------------------------------------------------------------------------
# Reference implementation (oracle + fallback + backward)
# ---------------------------------------------------------------------------


def attention_reference(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    window: int = 0,
    layout: Optional[BlockDiffusion] = None,
    selected: Optional[jnp.ndarray] = None,
    return_stats: bool = False,
):
    """Plain jnp attention; computes in f32 regardless of input dtype (the
    softmax accumulator precision the kernel also uses). Fewer K/V heads
    than query heads are repeated to them here (the kernels index them
    instead); ``window`` keeps, of a causal row ``i``, columns ``j`` with
    ``i - j < window``; a ``layout`` keeps the pairs of its definition;
    ``selected`` (a packed bit mask a batch row, ``ops/sparse_index.py``)
    keeps, of the causal pairs, those whose bit is set. ``return_stats``
    adds each row's logsumexp over what it keeps, ``[B, H, Sq]`` float32."""
    *_, sq, d = q.shape
    sk = k.shape[-2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    group = q.shape[1] // k.shape[1]
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        q_pos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends
        k_pos = jnp.arange(sk)[None, :]
        logits = jnp.where(k_pos <= q_pos, logits, _NEG_INF)
        if window:
            logits = jnp.where(q_pos - k_pos < window, logits, _NEG_INF)
    if layout is not None:
        logits = jnp.where(layout.mask(), logits, _NEG_INF)
    if selected is not None:
        logits = jnp.where(unpack_selection(selected, sk)[:, None], logits,
                           _NEG_INF)
    weights = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    if return_stats:
        return out, jax.nn.logsumexp(logits, axis=-1)
    return out


# ---------------------------------------------------------------------------
# Pallas flash kernel (forward)
# ---------------------------------------------------------------------------


def _flash_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                  m_scr, l_scr, acc_scr, *,
                  causal: bool, sm_scale: float, cases, one_tile: bool,
                  walk: _Walk, window: int = 0, block: int = 0,
                  sel_ref=None, sel_bit=None):
    """One (batch, head, q-block, kv-block) grid step of the online softmax.

    The kv-block axis is the innermost ("arbitrary") grid dimension: the
    (m, l, acc) running statistics live in VMEM scratch that persists
    across those steps, and the output block (indexed by the q block only)
    is written once, on the last kv step. Every operand is block-mapped —
    per-step VMEM is O(block), independent of sequence length, which is
    what lets the same kernel serve seq-512 BERT and seq-32k long-context.
    (An earlier design held K/V whole in VMEM and looped inside the
    kernel; it hit Mosaic's scoped-vmem limit at long S.)

    Of its block the step computes the pieces that ``cases`` (``_schedule``)
    gives for the block's place against the diagonal: a band of rows takes
    one softmax step over its pieces together, then the next band. A whole
    kv block above the diagonal matches no case, and the index maps name the
    diagonal's block for it again, so neither FLOPs nor a DMA are spent on
    it; a windowed call's kv axis walks the q block's band alone (``walk``,
    a ``_Walk``). ``one_tile`` says the grid has one q and one kv block, so
    that place is known while tracing.

    The walk's ``rel`` is by the TRUE (unpadded) lengths — the causal
    diagonal aligns their ends; the refs hold block-padded arrays. The
    [S,S] score matrix never exists in HBM.

    ``sel_ref`` (a selected call: the words of this tile's rows for the 128
    lanes that hold this K/V block's bits, ``sel_bit(block)`` the bit its
    first columns stand at) is then the whole mask of every piece: the
    selection holds causal pairs alone.
    """
    from jax.experimental import pallas as pl  # deferred: TPU-only path

    iq = 0 if one_tile else pl.program_id(2)
    step = 0 if one_tile else pl.program_id(3)
    last_step = 0 if one_tile else pl.num_programs(3) - 1
    kb, live = walk.block(iq, step)
    rel, q0, k0 = walk.place(iq, kb)

    @_when(step == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _band(r0, r1, pieces):
        q = q_ref[0, 0, r0:r1, :].astype(jnp.float32) * sm_scale
        scores = []
        for c0, c1, masked in pieces:
            s = jax.lax.dot_general(
                q, k_ref[0, 0, c0:c1, :].astype(jnp.float32),
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [r1 - r0, c1 - c0]
            if bias_ref is not None:  # the plan gives a bias one piece
                s = s + bias_ref[0, 0, :, :].astype(jnp.float32)
            if sel_ref is not None:
                # As under a window, a row may keep nothing of a piece.
                masked = 0
                s = jnp.where(_kept((sel_ref, sel_bit(kb)), r0, r1, c0, c1),
                              s, _NEG_INF)
            if masked & _CAUSAL:
                s = jnp.where(_below_diagonal(s.shape, q0 + r0, k0 + c0),
                              s, _NEG_INF)
            if masked & _WINDOW:
                # A row may see nothing of its first piece; what it then
                # sums (p = 1 at m = -1e30) is wiped by alpha = 0 when its
                # own diagonal comes.
                s = jnp.where(_inside_window(s.shape, q0 + r0, k0 + c0,
                                             window), s, _NEG_INF)
            if masked & _BLOCK_EDGES:
                # Every row has seen its own block by then: a noised row's
                # first step is its own columns, a clean row's first piece
                # holds its block or lies under the edge.
                s = jnp.where(_by_blocks(s.shape, q0 + r0, k0 + c0, masked,
                                         block), s, _NEG_INF)
            scores.append(s)
        m_prev = m_scr[r0:r1, :1]
        l_prev = l_scr[r0:r1, :1]
        m_cur = functools.reduce(jnp.maximum, [
            jnp.max(s, axis=-1, keepdims=True) for s in scores])
        m_new = jnp.maximum(m_prev, m_cur)
        # In this order (every p before alpha and the products) one piece
        # is the kernel as it was before the sub-tiles, operation for
        # operation; scaling acc first cost it 9 % (PERF.md, PR 25).
        ps = [jnp.exp(s - m_new) for s in scores]
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + functools.reduce(jnp.add, [
            jnp.sum(p, axis=-1, keepdims=True) for p in ps])
        acc_new = acc_scr[r0:r1, :] * alpha + functools.reduce(jnp.add, [
            jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, 0, c0:c1, :],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            for p, (c0, c1, _) in zip(ps, pieces)])
        m_scr[r0:r1, :] = jnp.broadcast_to(m_new, (r1 - r0, _STAT_LANES))
        l_scr[r0:r1, :] = jnp.broadcast_to(l_new, (r1 - r0, _STAT_LANES))
        acc_scr[r0:r1, :] = acc_new

    _run_schedule(cases, rel, _band, live)

    @_when(step == last_step)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        # Guard divide-by-zero for rows that saw no KV block at all (only
        # the padded tail rows of the last q block, which the caller slices
        # off; -1e30-bias "masked" rows still have l > 0 and softmax
        # normally).
        o_ref[0, 0, :, :] = (acc_scr[...] / jnp.maximum(l, 1e-30)) \
            .astype(o_ref.dtype)
        if lse_ref is not None:
            # Per-row logsumexp of the SCALED logits — the statistic the
            # flash backward needs to rebuild P without a second online
            # softmax. Rows that saw nothing (padded tail) get +LARGE so
            # the backward's exp(s - lse) underflows to exactly 0 for
            # them. Stored lane-replicated (see _STAT_LANES).
            lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-37)),
                            -_NEG_INF)  # [block_q, 1]
            lse_ref[0, 0, :, :] = jnp.broadcast_to(
                lse, lse_ref.shape[2:]).astype(jnp.float32)


def _kv_by_inner(walk: _Walk, group: int):
    """Index map of a K/V block where the kv axis is innermost (forward,
    dQ). A group of query heads reads its one K/V head through it: nothing
    is repeated in HBM."""
    if group == 1:
        return lambda ib, ih, iq, step: (ib, ih, walk.named(iq, step), 0)
    return lambda ib, ih, iq, step: (
        ib, ih // group, walk.named(iq, step), 0)


def _q_by_inner(walk: _Walk, group: int):
    """Index map of a q-side block where the q axis is innermost (dK/dV):
    the walk's steps for each query head of the group in turn."""
    if group == 1:
        return lambda ib, ih, ik, step: (ib, ih, walk.named(ik, step), 0)
    n_q = walk.steps
    return lambda ib, ih, ik, step: (
        ib, ih * group + step // n_q, walk.named(ik, step % n_q), 0)


def _pad_to(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pad_copies(x: jnp.ndarray, layout: Optional[BlockDiffusion],
                tile: int, fill=0) -> jnp.ndarray:
    """Each copy of a block-diffusion row (axis 2 of ``x``) padded to whole
    grid tiles. The padding opens blocks of its own after the row's last, so
    no real row sees a padded column: the layout's edges are the only masks
    its kernels need."""
    if layout is None or layout.length % tile == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[2] = (0, -layout.length % tile)
    return jnp.concatenate([
        jnp.pad(copy, widths, constant_values=fill)
        for copy in jnp.split(x, 2, axis=2)], axis=2)


def _unpad_copies(x: jnp.ndarray, layout: Optional[BlockDiffusion]
                  ) -> jnp.ndarray:
    """``_pad_copies`` undone."""
    if layout is None or x.shape[2] == 2 * layout.length:
        return x
    return jnp.concatenate([copy[:, :, :layout.length] for copy in
                            jnp.split(x, 2, axis=2)], axis=2)


def _flash_forward(q, k, v, bias, causal, sm_scale, interpret=False,
                   return_stats=False, plan=None, window=0, layout=None,
                   selected=None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = q.shape[2]
    if selected is not None:
        # Whole runs of 128 columns; the padding's bits are clear.
        q, k, v = (_pad_to(t, 2, LANES) for t in (q, k, v))
    if layout is not None:
        plan = plan or _tile_plan(0, 0, q.shape[-1], causal, layout=layout)
        _check_layout_plan(layout, plan)
        q, k, v = (_pad_copies(t, layout, plan[0]) for t in (q, k, v))
    b, h, sq, d = q.shape
    sk = k.shape[-2]
    group = h // k.shape[1]  # query heads to one K/V head
    if bias is not None and bias.shape[-1] == 1:
        # The contract is "broadcastable to [B,H,Sq,Sk]"; a bias constant
        # across the K (softmax) axis shifts every logit in a row equally,
        # and softmax is invariant to that — it contributes nothing to the
        # output. Drop it instead of materializing [...,Sk] (its gradient,
        # exactly zero, still flows via the custom VJP's reference
        # recompute, which sees the original bias).
        bias = None
    # Blocks are multiples of 8 (the f32 sublane count) — Mosaic's
    # block-shape rule. ``plan`` is for tests, which force small sub-tiles.
    plan = plan or _tile_plan(sq, sk, d, causal, window=window)
    block_q, block_k = plan[:2]
    cases = _schedule(sq, sk, plan, causal, window=window, layout=layout)
    walk = _walk(sq, sk, plan, causal, window, by_columns=False,
                 layout=layout, selected=selected is not None)
    _record_subtiles("flash_fwd",
                     _subtile_counts(sq, sk, plan, cases, walk), walk.mask)

    qp = _pad_to(q, 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    if bias is not None:
        # Align the user bias's K axis with the padded KV (zeros are fine:
        # the pad_bias below kills padded columns).
        if bias.shape[-1] not in (sk, sk_p):
            raise ValueError(
                f"bias K dim {bias.shape[-1]} incompatible with kv length "
                f"{sk}")
        bias = _pad_to(bias.astype(jnp.float32), 3, block_k) \
            if bias.shape[-1] == sk else bias.astype(jnp.float32)
    if sk_p != sk and not causal:
        # Padded KV columns must never win the softmax. (The causal mask
        # already excludes them: q_pos < sk for every real row.)
        pad_bias = jnp.where(
            jnp.arange(sk_p) < sk, 0.0, _NEG_INF)[None, None, None, :]
        bias = pad_bias if bias is None else bias + pad_bias

    _record_grid("flash_fwd", walk, cases, group)
    kv_spec = pl.BlockSpec((1, 1, block_k, d), _kv_by_inner(walk, group))
    in_specs = [
        pl.BlockSpec((1, 1, block_q, d),
                     lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        kv_spec, kv_spec,
    ]
    args = [qp, kp, vp]
    # The causal diagonal is defined by the TRUE lengths (ends aligned, as
    # in attention_reference); padded q rows are sliced off at the end and
    # padded k columns sit above the diagonal, so neither corrupts it.
    kernel_kw = dict(causal=causal, sm_scale=sm_scale, cases=cases,
                     window=window, walk=walk,
                     one_tile=(sq_p, sk_p) == (block_q, block_k))
    if layout is not None:
        kernel_kw["block"] = layout.block
    if selected is not None:
        words, lanes_of, kernel_kw["sel_bit"] = _selection_plan(
            selected, sq, plan)
        in_specs.append(pl.BlockSpec(
            (1, block_q, LANES), lambda ib, ih, iq, ik: (
                ib, iq, lanes_of(walk.named(iq, ik)))))
        args.append(words)

        def kernel(q_ref, k_ref, v_ref, sel_ref, o_ref, *rest):
            lse = rest[0] if return_stats else None
            _flash_kernel(q_ref, k_ref, v_ref, None, o_ref, lse,
                          *rest[-3:], sel_ref=sel_ref, **kernel_kw)
    elif bias is not None:
        # Keep broadcast dims at size 1 (indexed with block 0) instead of
        # materializing [B,H,Sq,Sk] in HBM.
        bb, bh, bq = bias.shape[0], bias.shape[1], bias.shape[2]
        if bq > 1:
            bias = _pad_to(bias, 2, block_q)
        block_bq = block_q if bq > 1 else 1
        in_specs.append(pl.BlockSpec(
            (1, 1, block_bq, block_k),
            lambda ib, ih, iq, ik: (ib if bb > 1 else 0,
                                    ih if bh > 1 else 0,
                                    iq if bq > 1 else 0,
                                    walk.named(iq, ik))))
        args.append(bias)

        def kernel(q_ref, k_ref, v_ref, b_ref, o_ref, *rest):
            # rest = (lse_ref if return_stats) + 3 scratch refs
            lse = rest[0] if return_stats else None
            _flash_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, lse,
                          *rest[-3:], **kernel_kw)
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, *rest):
            lse = rest[0] if return_stats else None
            _flash_kernel(q_ref, k_ref, v_ref, None, o_ref, lse,
                          *rest[-3:], **kernel_kw)

    out_specs = pl.BlockSpec((1, 1, block_q, d),
                             lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    out_shape = jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype)
    if return_stats:
        out_specs = [out_specs,
                     pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                                  lambda ib, ih, iq, ik: (ib, ih, iq, 0))]
        out_shape = [out_shape,
                     jax.ShapeDtypeStruct((b, h, sq_p, _STAT_LANES),
                                          jnp.float32)]

    result = pl.pallas_call(
        kernel,
        grid=(b, h, sq_p // block_q, walk.steps),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),  # m
            pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),  # l
            pltpu.VMEM((block_q, d), jnp.float32),            # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ) if not interpret else None,
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    sq = min(sq, rows) if selected is not None else sq
    if return_stats:
        out, lse = result
        return _unpad_copies(out[:, :, :sq, :], layout), \
            _unpad_copies(lse[:, :, :sq, 0], layout)
    return _unpad_copies(result[:, :, :sq, :], layout)


# ---------------------------------------------------------------------------
# Pallas flash kernels (backward)
#
# FlashAttention-2-style: the forward saves only O and the per-row
# logsumexp; the backward recomputes P block-by-block from (q, k, lse) — so
# no [Sq,Sk] tensor ever reaches HBM in training either. Two kernels:
# dK/dV (grid over KV blocks, inner loop over Q blocks) and dQ (grid over Q
# blocks, inner loop over KV blocks). delta = rowsum(dO * O) is a cheap
# jnp precompute.
#
# Derivation (S = scale·QKᵀ, P = softmax(S), O = PV):
#   dV = Pᵀ dO
#   dP = dO Vᵀ ;  dS = P ∘ (dP - delta)
#   dQ = scale · dS K ;  dK = scale · dSᵀ Q
# ---------------------------------------------------------------------------


def _bwd_mask(s, q0, k0, causal, cols):
    """Recreate the forward's masking (true-length causal diagonal + padded
    KV columns) on one score tile whose first row is at position ``q0`` of
    the block's columns, whose first column is the block's ``k0``-th, in a
    block with ``cols`` true columns."""
    live = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < cols - k0
    if causal:
        live = live & _below_diagonal(s.shape, q0, k0)
    return jnp.where(live, s, _NEG_INF)


def _piece_mask(kind, q0, k0, causal, cols, window, block=0):
    """The mask of one piece of a backward kernel that needs one: the call's
    own mask as ever or, in a windowed call, the edges that cross the
    piece; under a block-diffusion layout the edge of the piece's quadrant
    (its padded columns are blocks no real row sees)."""
    if kind & _BLOCK_EDGES:
        return lambda s: jnp.where(
            _by_blocks(s.shape, q0, k0, kind, block), s, _NEG_INF)
    if not window:
        return functools.partial(_bwd_mask, q0=q0, k0=k0, causal=causal,
                                 cols=cols)

    def mask(s):
        if kind & _CAUSAL:
            s = _bwd_mask(s, q0, k0, True, cols)
        if kind & _WINDOW:
            s = jnp.where(_inside_window(s.shape, q0, k0, window), s,
                          _NEG_INF)
        return s

    return mask


def _selection_mask(sel, r0, r1, c0, c1):
    """The mask of a backward kernel's piece under a selection: the whole
    of it, on every piece (``_kept``)."""
    return lambda s: jnp.where(_kept(sel, r0, r1, c0, c1), s, _NEG_INF)


def _bwd_rows(q_ref, do_ref, lse_ref, delta_ref, r0, r1):
    """Rows ``[r0, r1)`` of the q-side refs as the backward kernels use
    them: ``(q, do, lse, delta)``, float32."""
    return (q_ref[0, 0, r0:r1, :].astype(jnp.float32),
            do_ref[0, 0, r0:r1, :].astype(jnp.float32),
            # Stats are lane-replicated [rows, _STAT_LANES]; one column
            # suffices.
            lse_ref[0, 0, r0:r1, :][:, :1],
            delta_ref[0, 0, r0:r1, :][:, :1])


def _bwd_piece(rows, k_blk, v_blk, *, sm_scale, mask):
    """What both backward kernels recompute on one piece: ``(p, ds)`` from
    ``_bwd_rows`` and a float32 k/v piece. ``mask`` masks the scores, or is
    None below the diagonal."""
    q_blk, do_blk, lse, delta = rows
    s = jax.lax.dot_general(
        q_blk, k_blk, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = mask(s)
    p = jnp.exp(s - lse)  # 0 for masked/padded rows
    dp = jax.lax.dot_general(
        do_blk, v_blk, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - delta)


def _flash_bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           dk_ref, dv_ref, dk_scr, dv_scr, *, causal,
                           sm_scale, seq_k, cases, one_tile, walk,
                           window=0, group=1, block=0, sel_ref=None,
                           sel_bit=None):
    """One (batch, head, kv-block, q-block) grid step: accumulate this q
    block's contribution to dK/dV of one kv block in VMEM scratch; write on
    the last q step. Same block-mapped structure as the forward kernel, and
    the same schedule inside the step, here band of columns by band of
    columns with pieces of rows. With ``group`` query heads to one K/V head
    the innermost axis takes the ``walk``'s steps (a ``_Walk``: the q
    blocks, in a windowed call those of this kv block's band) for each of
    them in turn, so dK/dV are summed over the group where they are
    accumulated."""
    from jax.experimental import pallas as pl

    block_k = k_ref.shape[-2]
    ik = 0 if one_tile else pl.program_id(2)
    step = 0 if one_tile and group == 1 else pl.program_id(3)
    last_step = 0 if one_tile and group == 1 else pl.num_programs(3) - 1
    if group == 1:
        qi = step
    else:
        qi = 0 if walk.steps == 1 else jax.lax.rem(step, walk.steps)
    qi, live = walk.block(ik, qi)
    rel, q0, k0 = walk.place(ik, qi)
    cols = seq_k - ik * block_k

    @_when(step == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _band(c0, c1, pieces):
        k_blk = k_ref[0, 0, c0:c1, :].astype(jnp.float32)
        v_blk = v_ref[0, 0, c0:c1, :].astype(jnp.float32)
        dk = dk_scr[c0:c1, :]
        dv = dv_scr[c0:c1, :]
        for r0, r1, masked in pieces:
            mask = _piece_mask(masked, q0 + r0, k0 + c0, causal, cols,
                               window, block) if masked else None
            if sel_ref is not None:
                mask = _selection_mask((sel_ref, sel_bit(ik)), r0, r1, c0,
                                       c1)
            rows = _bwd_rows(q_ref, do_ref, lse_ref, delta_ref, r0, r1)
            q_blk, do_blk = rows[:2]
            p, ds = _bwd_piece(rows, k_blk, v_blk, sm_scale=sm_scale,
                               mask=mask)
            dv = dv + jax.lax.dot_general(
                p, do_blk, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk = dk + sm_scale * jax.lax.dot_general(
                ds, q_blk, dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_scr[c0:c1, :] = dk
        dv_scr[c0:c1, :] = dv

    _run_schedule(cases, rel, _band, live)

    @_when(step == last_step)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, causal, sm_scale, seq_k, cases,
                         one_tile, walk, window=0, block=0, sel_ref=None,
                         sel_bit=None):
    """One (batch, head, q-block, kv-block) grid step: accumulate one kv
    block's contribution to dQ of one q block; write on the last kv step.
    Band of rows by band of rows, as the forward is."""
    from jax.experimental import pallas as pl

    block_k = k_ref.shape[-2]
    iq = 0 if one_tile else pl.program_id(2)
    step = 0 if one_tile else pl.program_id(3)
    last_step = 0 if one_tile else pl.num_programs(3) - 1
    kb, live = walk.block(iq, step)
    rel, q0, k0 = walk.place(iq, kb)
    cols = seq_k - kb * block_k

    @_when(step == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _band(r0, r1, pieces):
        rows = _bwd_rows(q_ref, do_ref, lse_ref, delta_ref, r0, r1)
        dq = dq_scr[r0:r1, :]
        for c0, c1, masked in pieces:
            k_blk = k_ref[0, 0, c0:c1, :].astype(jnp.float32)
            v_blk = v_ref[0, 0, c0:c1, :].astype(jnp.float32)
            mask = _piece_mask(masked, q0 + r0, k0 + c0, causal, cols,
                               window, block) if masked else None
            if sel_ref is not None:
                mask = _selection_mask((sel_ref, sel_bit(kb)), r0, r1, c0,
                                       c1)
            _, ds = _bwd_piece(rows, k_blk, v_blk, sm_scale=sm_scale,
                               mask=mask)
            dq = dq + sm_scale * jax.lax.dot_general(
                ds, k_blk, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dq_scr[r0:r1, :] = dq

    _run_schedule(cases, rel, _band, live)

    @_when(step == last_step)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[...].astype(dq_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, sm_scale, interpret,
                    plan=None, window=0, layout=None, selected=None):
    """dq, dk, dv via the blocked kernels (bias-free path)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = q.shape[2]
    if selected is not None:
        q, k, v, out, g = (_pad_to(t, 2, LANES) for t in (q, k, v, out, g))
        lse = jnp.pad(lse, [(0, 0), (0, 0), (0, q.shape[2] - rows)],
                      constant_values=-_NEG_INF)
    if layout is not None:
        plan = plan or _tile_plan(0, 0, q.shape[-1], causal, backward=True,
                                  layout=layout)
        _check_layout_plan(layout, plan)
        q, k, v, out, g = (_pad_copies(t, layout, plan[0])
                           for t in (q, k, v, out, g))
        # A padded row's +LARGE makes exp(s - lse) underflow to 0.
        lse = _pad_copies(lse, layout, plan[0], fill=-_NEG_INF)
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[-2]
    group = h // hk
    plan = plan or _tile_plan(sq, sk, d, causal, backward=True,
                              window=window)
    block_q, block_k = plan[:2]
    cases = {"flash_bwd_dkdv": _schedule(sq, sk, plan, causal,
                                         by_columns=True, mask_whole=True,
                                         window=window, layout=layout),
             "flash_bwd_dq": _schedule(sq, sk, plan, causal,
                                       mask_whole=True, window=window,
                                       layout=layout)}
    walks = {name: _walk(sq, sk, plan, causal, window,
                         by_columns=name == "flash_bwd_dkdv", layout=layout,
                         selected=selected is not None)
             for name in cases}
    for name, kernel_cases in cases.items():
        _record_subtiles(name, _subtile_counts(
            sq, sk, plan, kernel_cases, walks[name]), walks[name].mask)

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    qp = _pad_to(q, 2, block_q)
    dop = _pad_to(g.astype(q.dtype), 2, block_q)
    kp = _pad_to(k, 2, block_k)
    vp = _pad_to(v, 2, block_k)
    # Padded q rows: lse=+LARGE makes exp(s - lse) underflow to 0, delta=0.
    lse_p = _pad_to(lse, 2, block_q)
    if lse_p.shape[-1] != sq:
        pad_rows = jnp.arange(lse_p.shape[-1]) >= sq
        lse_p = jnp.where(pad_rows[None, None, :], -_NEG_INF, lse_p)
    delta_p = _pad_to(delta, 2, block_q)
    sq_p, sk_p = qp.shape[2], kp.shape[2]
    # Lane-replicate the row stats (see _STAT_LANES): a [..., rows] array
    # cannot be block-mapped on real hardware.
    lse_p = jnp.broadcast_to(lse_p[..., None], (b, h, sq_p, _STAT_LANES))
    delta_p = jnp.broadcast_to(delta_p[..., None], (b, h, sq_p, _STAT_LANES))

    common = dict(causal=causal, sm_scale=sm_scale, seq_k=sk, window=window,
                  one_tile=(sq_p, sk_p) == (block_q, block_k))
    if layout is not None:
        common["block"] = layout.block
    for name, walk in walks.items():
        _record_grid(name, walk, cases[name], group)
    semantics = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary")) if not interpret else None

    # dK/dV: grid over kv blocks, q blocks innermost (accumulated).
    # With grouped heads the grid runs over K/V heads, and the innermost
    # axis over the q blocks of every query head of the group.
    walk = walks["flash_bwd_dkdv"]
    q_rows = _q_by_inner(walk, group)
    q_by_inner = pl.BlockSpec((1, 1, block_q, d), q_rows)
    row_by_inner = pl.BlockSpec((1, 1, block_q, _STAT_LANES), q_rows)
    kv_by_outer = pl.BlockSpec((1, 1, block_k, d),
                               lambda ib, ih, ik, iq: (ib, ih, ik, 0))
    dkdv_kernel = functools.partial(
        _flash_bwd_dkdv_kernel, **common, group=group,
        cases=cases["flash_bwd_dkdv"], walk=walk)
    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, **common, cases=cases["flash_bwd_dq"],
        walk=walks["flash_bwd_dq"])
    sel_by_inner, sel_by_outer, words = [], [], []
    if selected is not None:
        padded, lanes_of, sel_bit = _selection_plan(selected, sq, plan)
        words, by_columns = [padded], walk
        sel_by_inner = [pl.BlockSpec(
            (1, block_q, LANES), lambda ib, ih, ik, step: (
                ib, by_columns.named(ik, step % by_columns.steps),
                lanes_of(ik)))]
        sel_by_outer = [pl.BlockSpec(
            (1, block_q, LANES), lambda ib, ih, iq, ik: (
                ib, iq, lanes_of(walks["flash_bwd_dq"].named(iq, ik))))]

        def with_selection(kernel):
            # The words come after the six operands every call has.
            return lambda *refs: kernel(*refs[:6], *refs[7:],
                                        sel_ref=refs[6], sel_bit=sel_bit)

        dkdv_kernel, dq_kernel = map(with_selection,
                                     (dkdv_kernel, dq_kernel))
    dk, dv = pl.pallas_call(
        dkdv_kernel,
        grid=(b, hk, sk_p // block_k, group * walk.steps),
        in_specs=[q_by_inner, kv_by_outer, kv_by_outer, q_by_inner,
                  row_by_inner, row_by_inner] + sel_by_inner,
        out_specs=[kv_by_outer, kv_by_outer],
        out_shape=[jax.ShapeDtypeStruct((b, hk, sk_p, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hk, sk_p, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="flash_bwd_dkdv",
    )(qp, kp, vp, dop, lse_p, delta_p, *words)

    # dQ: grid over q blocks, kv blocks innermost (accumulated).
    q_by_outer = pl.BlockSpec((1, 1, block_q, d),
                              lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    row_by_outer = pl.BlockSpec((1, 1, block_q, _STAT_LANES),
                                lambda ib, ih, iq, ik: (ib, ih, iq, 0))
    walk = walks["flash_bwd_dq"]
    kv_by_inner = pl.BlockSpec((1, 1, block_k, d), _kv_by_inner(walk, group))
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, sq_p // block_q, walk.steps),
        in_specs=[q_by_outer, kv_by_inner, kv_by_inner, q_by_outer,
                  row_by_outer, row_by_outer] + sel_by_outer,
        out_specs=q_by_outer,
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=semantics,
        interpret=interpret,
        name="flash_bwd_dq",
    )(qp, kp, vp, dop, lse_p, delta_p, *words)

    if selected is not None:
        sq = sk = rows
    return tuple(_unpad_copies(t, layout) for t in (
        dq[:, :, :sq, :], dk[:, :, :sk, :], dv[:, :, :sk, :]))


# ---------------------------------------------------------------------------
# Public entry with custom VJP
# ---------------------------------------------------------------------------


# A block-diffusion layout's kernels are one jitted function each, which
# every layer of a model shares: its forward is traced three times a
# recomputed block (the pass, the rule of the custom_vjp, the recomputation)
# and a schedule of three staircases is half a second of tracing each time;
# through ``jax.jit`` the second call finds the first one's jaxpr and the
# step's text holds each kernel once. ``core_attention`` is opened again
# inside, as ``shard_rows`` does: the wrapper's name (``jit(...)``) stands
# in an operation's ``op_name`` between the caller's scope and the kernel's.
# (``causal`` and ``window`` calls are traced a call site at a time, as
# they were: PERF.md section 7.)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "return_stats", "layout"))
def _layout_forward(q, k, v, *, sm_scale, interpret, return_stats, layout):
    with jax.named_scope("core_attention"):
        return _flash_forward(q, k, v, None, False, sm_scale,
                              interpret=interpret, return_stats=return_stats,
                              layout=layout)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "layout"))
def _layout_backward(q, k, v, out, lse, g, *, sm_scale, interpret, layout):
    with jax.named_scope("core_attention"):
        return _flash_backward(q, k, v, out, lse, g, False, sm_scale,
                               interpret, layout=layout)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _fused_attention(q, k, v, bias, causal, sm_scale, use_pallas, interpret,
                     window, layout):
    if use_pallas and layout is not None:
        return _layout_forward(q, k, v, sm_scale=sm_scale,
                               interpret=interpret, return_stats=False,
                               layout=layout)
    if use_pallas:
        return _flash_forward(q, k, v, bias, causal, sm_scale,
                              interpret=interpret, window=window)
    return attention_reference(q, k, v, bias, causal, sm_scale, window,
                               layout)


def _fwd(q, k, v, bias, causal, sm_scale, use_pallas, interpret, window,
         layout):
    if use_pallas and bias is None:
        # Full flash path: keep O + logsumexp so the backward kernels can
        # rebuild P per block — O(S) residual memory in training too.
        if layout is not None:
            out, lse = _layout_forward(q, k, v, sm_scale=sm_scale,
                                       interpret=interpret,
                                       return_stats=True, layout=layout)
        else:
            out, lse = _flash_forward(q, k, v, None, causal, sm_scale,
                                      interpret=interpret, return_stats=True,
                                      window=window)
        # Named for a recomputed block's policy (models/lm.py): what the
        # block returns and what the backward kernels read are these two,
        # so the kernel is not run again. An identity anywhere else.
        out = checkpoint_name(out, FLASH_OUT)
        lse = checkpoint_name(lse, FLASH_LSE)
        return out, (q, k, v, None, out, lse)
    out = _fused_attention(q, k, v, bias, causal, sm_scale, use_pallas,
                           interpret, window, layout)
    return out, (q, k, v, bias, None, None)


def _bwd(causal, sm_scale, use_pallas, interpret, window, layout, res, g):
    q, k, v, bias, out, lse = res
    if use_pallas and bias is None:
        if layout is not None:
            dq, dk, dv = _layout_backward(q, k, v, out, lse, g,
                                          sm_scale=sm_scale,
                                          interpret=interpret, layout=layout)
        else:
            dq, dk, dv = _flash_backward(q, k, v, out, lse, g, causal,
                                         sm_scale, interpret, window=window)
        return dq, dk, dv, None
    # Bias path (trainable biases must receive a cotangent, and dS would be
    # a full [Sq,Sk] output anyway): recompute through the reference
    # formulation — XLA fuses it. Costs O(S²) backward memory; bias-free
    # training (the long-context path) never lands here.
    def f(q, k, v, bias):
        return attention_reference(q, k, v, bias, causal, sm_scale, window,
                                   layout)
    _, vjp = jax.vjp(f, q, k, v, bias)
    dq, dk, dv, dbias = vjp(g)
    return dq, dk, dv, None if bias is None else dbias


_fused_attention.defvjp(_fwd, _bwd)


# A selected call's kernels, one jitted function each as a layout's are. The
# selection is the fourth mask and the first that is an operand: a call of
# its own beside ``_fused_attention``, which returns the row statistics with
# the output (the indexer's loss reads them, as constants) and whose rule
# hands the words on to the backward kernels.


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _selected_forward(q, k, v, selected, *, sm_scale, interpret):
    with jax.named_scope("core_attention"):
        return _flash_forward(q, k, v, None, True, sm_scale,
                              interpret=interpret, return_stats=True,
                              selected=selected)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _selected_backward(q, k, v, out, lse, g, selected, *, sm_scale,
                       interpret):
    with jax.named_scope("core_attention"):
        return _flash_backward(q, k, v, out, lse, g, True, sm_scale,
                               interpret, selected=selected)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _selected_attention(q, k, v, selected, sm_scale, use_pallas, interpret):
    """``(out, lse)`` of a causal call under a selection."""
    if use_pallas:
        return _selected_forward(q, k, v, selected, sm_scale=sm_scale,
                                 interpret=interpret)
    return attention_reference(q, k, v, None, True, sm_scale,
                               selected=selected, return_stats=True)


def _selected_fwd(q, k, v, selected, sm_scale, use_pallas, interpret):
    out, lse = _selected_attention(q, k, v, selected, sm_scale, use_pallas,
                                   interpret)
    if use_pallas:
        out = checkpoint_name(out, FLASH_OUT)
        lse = checkpoint_name(lse, FLASH_LSE)
    return (out, lse), (q, k, v, selected, out, lse)


def _selected_bwd(sm_scale, use_pallas, interpret, res, g):
    q, k, v, selected, out, lse = res
    g = g[0]  # the row statistics are read as constants
    if use_pallas:
        return _selected_backward(q, k, v, out, lse, g, selected,
                                  sm_scale=sm_scale,
                                  interpret=interpret) + (None,)
    _, vjp = jax.vjp(lambda q, k, v: attention_reference(
        q, k, v, None, True, sm_scale, selected=selected), q, k, v)
    return vjp(g) + (None,)


_selected_attention.defvjp(_selected_fwd, _selected_bwd)


# Auto-dispatch crossover, measured on a v5e in r03 (that log is lost and
# no cell measures it: PERF.md section 7): XLA's own fused attention beat the flash kernel at S=512
# (9.0 ms vs 6.7 ms, 0.74×) while flash won 1.4× at S=2048 and 35× at
# S=8192 (where XLA spills the [S,S] matrix to HBM). Between the measured
# points the switch sits at 1024. The XLA path's backward holds 2-3
# O(B·H·Sq·Sk) f32 buffers live at once (softmax residual + dp/dlogits),
# so eligibility is capped on ONE such buffer at 512 MiB — ~1.5 GiB real
# peak, a safe transient on a 16 GB chip. Above it the flash kernel's
# O(S) memory wins regardless of speed.
_SHORT_SEQ_THRESHOLD = 1024
_REF_BWD_BYTES_CAP = 512 << 20


def _auto_use_pallas(backend: str, b: int, h: int, sq: int, sk: int) -> bool:
    """The 'auto' dispatch decision (pure, unit-tested): flash kernel on
    TPU except in the measured short-sequence window where XLA's fused
    attention is faster AND its quadratic backward intermediate fits."""
    if backend != "tpu":
        return False
    ref_bytes = b * h * sq * sk * 4
    return not (sk < _SHORT_SEQ_THRESHOLD and ref_bytes <= _REF_BWD_BYTES_CAP)


def _kernel_path(implementation: str, b: int, h: int, sq: int, sk: int
                 ) -> Tuple[bool, bool]:
    """``(use_pallas, interpret)`` of a call's ``implementation``."""
    if implementation == "auto":
        return _auto_use_pallas(jax.default_backend(), b, h, sq, sk), False
    if implementation not in ("pallas", "interpret", "reference"):
        raise ValueError(f"unknown implementation {implementation!r}")
    return implementation != "reference", implementation == "interpret"


def flash_kept_bytes(b: int, h: int, s: int, d: int, dtype,
                     implementation: str) -> int:
    """The bytes of :data:`FLASH_OUT` and :data:`FLASH_LSE` for a
    self-attention call without a bias over q ``[b, h, s, d]`` of ``dtype``:
    the output and a float32 a row, ``b h s (d itemsize + 4)``; 0 where the
    call does not take the kernels and names nothing."""
    if not _kernel_path(implementation, b, h, s, s)[0]:
        return 0
    return b * h * s * (d * jnp.dtype(dtype).itemsize + 4)


def fused_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    implementation: str = "auto",
    window: int = 0,
    mesh=None,
    layout: Optional[BlockDiffusion] = None,
    selected: Optional[jnp.ndarray] = None,
):
    """Multi-head attention, fused on TPU.

    ``mesh``: the mesh the step is compiled for. Where its batch axes hold
    more than one device the kernels run under a ``shard_map`` over them,
    each device on its own rows of the batch (``parallel/kernels.py``; the
    kernels keep the scope ``core_attention/flash_*`` that
    ``MultiHeadAttention.core_attention`` gives them on one device); on one
    device, and without a mesh, nothing is wrapped.

    ``k`` and ``v`` may have fewer heads than ``q``, a whole number of query
    heads to each (query head ``i`` reads K/V head ``i // group``): the
    kernels index them, nothing is repeated in HBM, and dK/dV come back
    summed over the group. ``window`` > 0 (causal calls without a bias)
    keeps, of row ``i``, the columns ``j`` with ``i - j < window``; tiles
    and sub-tiles wholly outside it are not computed. ``layout`` (a
    :class:`BlockDiffusion`; calls that are not causal, with neither bias
    nor window, over its ``2 * length`` positions) keeps the pairs of a
    block-diffusion training row instead: a third static mask, whose dead
    quadrant, dead tiles and dead sub-tiles the kernels leave out as they do
    a window's. ``selected`` (``[B, Sq, W]`` int32, the packed bit mask of
    ``ops/sparse_index.py``; causal self-attention calls with no bias,
    window or layout) is the fourth mask and the one that is data: of the
    causal pairs a row keeps those whose bit is set, one selection for all
    the heads. The kernels walk the causal triangle's tiles and mask inside
    them by the words; the call returns ``(out, lse)``, the rows' logsumexp
    over what they keep beside the output (``[B, H, Sq]`` float32, a
    constant to differentiation), and no gradient passes to the selection.

    The four masks, and which exclude which: ``causal`` alone; ``window``
    (needs ``causal``, excludes ``bias``); ``layout`` (excludes ``causal``,
    ``window``, ``bias``); ``selected`` (needs ``causal``, excludes
    ``window``, ``layout``, ``bias``). Each is counted when a call is
    traced: ``attention.flash.calls`` labelled ``mask`` (``causal``,
    ``window``, ``block_diffusion``, ``selected``, ``none``) and ``path``
    (docs/OBSERVABILITY.md).

    implementation: 'auto' (on TPU: flash kernel, except the measured
    short-sequence window — Sk < 1024 with the quadratic backward
    intermediate under cap — where XLA's own fused attention is faster;
    off-TPU: reference), 'pallas', 'reference', or 'interpret' (pallas
    kernel in interpreter mode — CPU-runnable, used by tests to validate
    kernel numerics).
    """
    if q.ndim != 4 or k.ndim != 4 or v.ndim != 4:
        raise ValueError(f"expected [B,H,S,D] inputs, got {q.shape}")
    if k.shape[1] != v.shape[1] or q.shape[1] % k.shape[1]:
        raise ValueError(
            f"{q.shape[1]} query heads cannot share {k.shape[1]} key and "
            f"{v.shape[1]} value heads")
    if window and (not causal or bias is not None or window < 0):
        raise ValueError("a window needs a causal call without a bias, and "
                         f"a positive size; got window={window}, "
                         f"causal={causal}, bias given: {bias is not None}")
    if layout is not None and (
            causal or window or bias is not None
            or not q.shape[-2] == k.shape[-2] == 2 * layout.length):
        raise ValueError(
            f"a block-diffusion layout is the whole mask of a call over its "
            f"2 x {layout.length} positions: no causal flag, window or "
            f"bias; got causal={causal}, window={window}, bias given: "
            f"{bias is not None}, {q.shape[-2]} rows, {k.shape[-2]} columns")
    if selected is not None and (
            not causal or window or layout is not None or bias is not None
            or q.shape[-2] != k.shape[-2]
            or selected.shape[:2] != (q.shape[0], q.shape[-2])
            or selected.shape[2] != packed_width(q.shape[-2])):
        raise ValueError(
            f"a selection masks a causal self-attention call with no "
            f"window, layout or bias, by [B, S, {packed_width(q.shape[-2])}]"
            f" words; got causal={causal}, window={window}, layout given: "
            f"{layout is not None}, bias given: {bias is not None}, "
            f"{q.shape[-2]} rows, {k.shape[-2]} columns, words "
            f"{selected.shape}")
    if causal and q.shape[-2] > k.shape[-2]:
        # Ill-defined: ends are aligned, so the leading queries would
        # precede every key (and the kernel/reference paths would disagree
        # on what an all-masked softmax row means).
        raise ValueError(
            f"causal attention requires Sq <= Sk, got {q.shape[-2]} > "
            f"{k.shape[-2]}")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    use_pallas, interpret = _kernel_path(implementation, *q.shape[:3],
                                         k.shape[-2])
    get_tracer().registry.counter(
        "attention.flash.calls",
        "attention calls traced, by their static mask and the path taken",
    ).inc(mask=_mask_name(window, layout, selected is not None)
          or ("causal" if causal else "none"),
          path="kernel" if use_pallas else "xla")
    if selected is not None:
        rows = [rows_spec(batch_axes_of(mesh), n) for n in (4, 3)]
        return shard_rows(
            lambda q, k, v, words: _selected_attention(
                q, k, v, words, scale, use_pallas, interpret),
            mesh if use_pallas else None, "flash",
            (rows[0], rows[0], rows[0], rows[1]), tuple(rows),
            scope="core_attention")(q, k, v, selected)
    if use_pallas and bias is None:
        rows = rows_spec(batch_axes_of(mesh), 4)
        return shard_rows(
            lambda q, k, v: _fused_attention(
                q, k, v, None, causal, scale, True, interpret, window,
                layout),
            mesh, "flash", (rows, rows, rows), rows,
            scope="core_attention")(q, k, v)
    return _fused_attention(q, k, v, bias, causal, scale, use_pallas,
                            interpret, window, layout)
