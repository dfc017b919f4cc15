"""Learned sparse attention's selection and its loss (DeepSeek-Sparse-
Attention's indexer, here over grouped-query attention).

An indexer scores every earlier key of a row,

    I[t, s] = D^-1/2 H^-1/2 sum_j w[t, j] relu(qI[t, j] . kI[s]),   s <= t,

from ``qI [B, H, S, D]``, ``kI [B, S, D]`` and ``w [B, S, H]`` (float32),
with bfloat16 (the model's dtype) operands, float32 accumulation and float32
``w``, relu and sum. Row ``t`` keeps ``S_t = {s <= t : I[t, s] >= tau[t]}``,
``tau[t]`` the ``topk``-th largest of its scores (every key while there are
at most ``topk``): the best ``topk`` and whatever ties the last.

- :func:`select_top_k` gives the selection as a packed bit mask (the layout
  below) with each row's ``logsumexp`` of its kept scores and the number it
  kept. The kernel holds a tile of rows' scores in VMEM, as integers that
  order as the floats do, and finds the threshold by bisection on their bits:
  32 counts, exact. ``[S, S]`` never stands in HBM.
- :func:`index_loss` is the indexer's loss, a row's ``KL(P || softmax_{S_t}
  I)`` summed over the rows, where ``P`` is the attention's own distribution
  over ``S_t`` averaged over the query heads (from q, k and the flash
  kernel's row statistics, all constants here). One kernel by tiles computes
  the sum and, in the same pass, its gradient in ``qI``, ``kI`` and ``w``
  (in ``I`` it is ``softmax(I) - P`` on ``S_t``); the backward rule scales
  what the forward kept.

The bit mask: ``[B, S, W]`` int32 with ``W = 128 ceil(S / 4096)``. Columns go
in runs of 4096 (``SUPER``), a run to 128 lanes of words: column ``c`` is bit
``(c % 4096) // 128`` of lane ``c % 128`` of run ``c // 4096``. A kernel that
holds ``[rows, 128]`` words has the mask of the run's ``b``-th 128 columns as
``(words >> b) & 1``: a shift, no movement across lanes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..obs.trace import get_tracer
from ..parallel.kernels import batch_axes_of, rows_spec, shard_rows

LANES = 128
SUPER = 32 * LANES

# What a recomputed block keeps of the selection and of the loss's pass
# (``models/lm.py``'s policy), so that neither kernel runs a second time.
INDEX_SELECTED = "index_selected"
INDEX_STATS = "index_stats"
INDEX_GRADS = "index_grads"

_INT_MIN = -2 ** 31
_SELECT_ROWS = 128   # a tile of rows whose scores one grid step holds
_SELECT_COLS = 512   # the columns scored at a time
_LOSS_ROWS = 128
_LOSS_COLS = 512
_VMEM_LIMIT = 64 * 2 ** 20


def packed_width(s: int) -> int:
    return -(-s // SUPER) * LANES


def pack_selection(keep: jnp.ndarray) -> jnp.ndarray:
    """``[..., S, S]`` booleans as the bit mask ``[..., S, W]``."""
    s = keep.shape[-1]
    pad = [(0, 0)] * (keep.ndim - 1) + [(0, -s % SUPER)]
    bits = jnp.pad(keep, pad).reshape(*keep.shape[:-1], -1, 32, LANES)
    words = jnp.sum(bits.astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32)[:, None], axis=-2,
                    dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(
        *keep.shape[:-1], -1)


def unpack_selection(words: jnp.ndarray, s: int) -> jnp.ndarray:
    """The bit mask back as ``[..., S, S]`` booleans (``pack_selection``
    undone; the words' rows beyond ``s`` are left out)."""
    w = jax.lax.bitcast_convert_type(words[..., :s, :], jnp.uint32)
    w = w.reshape(*w.shape[:-1], -1, 1, LANES)
    bits = (w >> jnp.arange(32, dtype=jnp.uint32)[:, None]) & 1
    return bits.reshape(*bits.shape[:-3], -1)[..., :s].astype(bool)


def index_scale(heads: int, dim: int) -> float:
    return 1.0 / math.sqrt(dim) / math.sqrt(heads)


def index_scores(qi, ki, w) -> jnp.ndarray:
    """``I [B, S, S]`` float32, from the definition (every pair, the causal
    ones among them)."""
    _, heads, _, dim = qi.shape
    s = jnp.einsum("bhtd,bsd->bhts", qi, ki,
                   preferred_element_type=jnp.float32)
    return index_scale(heads, dim) * jnp.einsum(
        "bth,bhts->bts", w.astype(jnp.float32), jax.nn.relu(s))


def select_from_scores(scores: jnp.ndarray, topk: int):
    """The selection of ``scores [B, S, S]`` from the definition: ``(keep
    [B, S, S] bool, lse [B, S], kept [B, S])``, the threshold by
    ``lax.top_k``."""
    s = scores.shape[-1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    masked = jnp.where(causal, scores, -jnp.inf)
    if topk < s:
        kth = jax.lax.top_k(masked, topk)[0][..., -1]
        tau = jnp.where(jnp.arange(s) < topk, -jnp.inf, kth)
        keep = causal & (masked >= tau[..., None])
    else:
        keep = jnp.broadcast_to(causal, scores.shape)
    lse = jax.nn.logsumexp(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return keep, lse, jnp.sum(keep, axis=-1).astype(jnp.float32)


def head_mean_attention(q, k, flash_lse, sm_scale) -> jnp.ndarray:
    """``P [B, S, S]``: ``exp(q . k scale - lse)`` averaged over the query
    heads, float32, on every pair (the caller masks)."""
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1) if group > 1 else k
    s = jnp.einsum("bhtd,bhsd->bhts", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    return jnp.mean(jnp.exp(s - flash_lse[..., None]), axis=1)


def index_loss_from_scores(scores, keep, p) -> jnp.ndarray:
    """``sum_t KL(P_t || softmax_{S_t} I_t)`` a batch row, ``[B]``, from the
    definition; ``p`` a constant."""
    p = jnp.where(keep, jax.lax.stop_gradient(p), 0.0)
    log_q = scores - jax.nn.logsumexp(
        jnp.where(keep, scores, -jnp.inf), axis=-1, keepdims=True)
    terms = jnp.where(p > 0, p * (jnp.log(jnp.maximum(p, 1e-37)) - log_q),
                      0.0)
    return jnp.sum(terms, axis=(-1, -2))


def _order_keys(x):
    """float32 as int32 that compare as the floats do (an involution)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ (jax.lax.shift_right_arithmetic(bits, 31) & 0x7FFFFFFF)


def _keys_back(keys):
    bits = keys ^ (jax.lax.shift_right_arithmetic(keys, 31) & 0x7FFFFFFF)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def selected_bits(words, first_bit, chunks: int):
    """Inside a kernel: the mask of ``chunks`` runs of 128 columns from a
    tile of words ``[rows, 128]``, the first run at bit ``first_bit`` (a
    Python int or a traced scalar): int32 ``[rows, 128 chunks]``, 1 where
    the pair is kept."""
    parts = []
    for j in range(chunks):
        shift = jnp.broadcast_to(jnp.asarray(first_bit + j, jnp.int32),
                                 words.shape)
        parts.append(jax.lax.shift_right_logical(words, shift) & 1)
    return parts[0] if chunks == 1 else jnp.concatenate(parts, axis=1)


def _pad_axis(t, axis: int, size: int, fill=0):
    """``t`` padded along ``axis`` to ``size``."""
    widths = [(0, 0)] * t.ndim
    widths[axis] = (0, size - t.shape[axis])
    return jnp.pad(t, widths, constant_values=fill)


def _kernel_path(implementation: str):
    """``(use_pallas, interpret)``: ``fused_attention``'s names, but ``auto``
    takes the kernels on a TPU whatever the length (its window of short rows
    is the flash kernels' own measurement, and a selection's two kernels
    must not part ways by length)."""
    if implementation == "auto":
        return jax.default_backend() == "tpu", False
    if implementation not in ("pallas", "interpret", "reference"):
        raise ValueError(f"unknown implementation {implementation!r}")
    return implementation != "reference", implementation == "interpret"


def _column(x, j: int):
    """Column ``j`` of a narrow tile ``[rows, n]`` as ``[rows, 1]``, by a
    masked sum: a slice of one lane is not something the chip's tiling
    takes everywhere."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == j, x, 0), axis=1, keepdims=True)


def _tile_scores(qi_ref, ki, w, scale):
    """``I`` of a tile: the q-side ref ``[1, H, tq, D]`` and the tile's
    weights ``w [tq, H]`` against the keys ``ki [ck, D]``."""
    acc = None
    for j in range(qi_ref.shape[1]):
        s = jax.lax.dot_general(
            qi_ref[0, j], ki, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        term = _column(w, j) * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    return acc * scale


# ---------------------------------------------------------------------------
# The selection
# ---------------------------------------------------------------------------


def _select_kernel(qi_ref, ki_ref, w_ref, words_ref, stats_ref, keys_scr, *,
                   topk, tq, ck, scale):
    """One tile of ``tq`` rows: their scores over the columns the tile can
    see, as ordered integers in ``keys_scr [chunks, tq, ck]``; the
    ``topk``-th largest of each row by bisection on the integers' bits; then
    the words, the kept scores' logsumexp and the count."""
    from jax.experimental import pallas as pl

    n_chunks = keys_scr.shape[0]
    row0 = pl.program_id(1) * tq
    live = jnp.minimum((row0 + tq - 1) // ck + 1, n_chunks)
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, (tq, ck), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (tq, ck), 1)

    def fill(c, carry):
        ki = ki_ref[0, pl.ds(pl.multiple_of(c * ck, ck), ck), :]
        keys = _order_keys(_tile_scores(qi_ref, ki, w_ref[0], scale))
        keys_scr[c] = jnp.where(c * ck + col <= row, keys, _INT_MIN)
        return carry

    jax.lax.fori_loop(0, live, fill, 0)

    def over_chunks(f, combine, init):
        """``combine`` over every live chunk's ``f(keys)`` and then over the
        lanes: 128 lanes of partial results are carried from chunk to chunk,
        so the lanes are crossed once a pass, not once a chunk."""
        def step(c, acc):
            part = f(keys_scr[c])
            for j in range(ck // LANES):
                acc = combine(acc, part[:, j * LANES:(j + 1) * LANES])
            return acc

        return jax.lax.fori_loop(0, live, step,
                                 jnp.full((tq, LANES), init, jnp.float32))

    def count_ge(cand):
        return jnp.sum(over_chunks(
            lambda keys: (keys >= cand).astype(jnp.float32), jnp.add, 0.0),
            axis=1, keepdims=True)

    zero = jnp.zeros((tq, 1), jnp.int32)
    start = jnp.where(count_ge(zero) >= topk, zero, _INT_MIN)

    def bit_step(i, tau):
        cand = tau | jax.lax.shift_left(jnp.int32(1), jnp.int32(30) - i)
        return jnp.where(count_ge(cand) >= topk, cand, tau)

    tau = jax.lax.fori_loop(0, 31, bit_step, start)
    # A padded or masked pair holds INT_MIN; a real score never does.
    tau = jnp.maximum(tau, _INT_MIN + 1)

    kept = count_ge(tau)
    # Every row keeps its best score, so the largest kept is the largest.
    top = jnp.max(over_chunks(
        lambda keys: jnp.where(keys >= tau, _keys_back(keys), -jnp.inf),
        jnp.maximum, -jnp.inf), axis=1, keepdims=True)
    total = jnp.sum(over_chunks(
        lambda keys: jnp.where(keys >= tau, jnp.exp(_keys_back(keys) - top),
                               0.0), jnp.add, 0.0), axis=1, keepdims=True)
    lse = top + jnp.log(jnp.maximum(total, 1e-37))
    lane = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape[1:], 1)
    stats_ref[0] = jnp.where(lane == 0, lse, kept)

    row128 = row0 + jax.lax.broadcasted_iota(jnp.int32, (tq, LANES), 0)
    col128 = jax.lax.broadcasted_iota(jnp.int32, (tq, LANES), 1)
    for run in range(words_ref.shape[2] // LANES):
        words = jnp.zeros((tq, LANES), jnp.int32)
        for b in range(32):
            c0 = run * SUPER + b * LANES
            if c0 // ck >= n_chunks:
                break
            keys = keys_scr[c0 // ck, :, c0 % ck:c0 % ck + LANES]
            keep = (keys >= tau) & (c0 + col128 <= row128)
            words = words | jax.lax.shift_left(
                keep.astype(jnp.int32), jnp.full((tq, LANES), b, jnp.int32))
        words_ref[0, :, run * LANES:(run + 1) * LANES] = words


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def _select_pallas(qi, ki, w, *, topk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, heads, s, dim = qi.shape
    tq, ck = _SELECT_ROWS, _SELECT_COLS
    s_p = -(-s // ck) * ck
    width = packed_width(s_p)
    with jax.named_scope("indexer_select"):
        words, stats = pl.pallas_call(
            functools.partial(_select_kernel, topk=topk, tq=tq, ck=ck,
                              scale=index_scale(heads, dim)),
            grid=(b, s_p // tq),
            in_specs=[
                pl.BlockSpec((1, heads, tq, dim),
                             lambda ib, iq: (ib, 0, iq, 0)),
                pl.BlockSpec((1, s_p, dim), lambda ib, iq: (ib, 0, 0)),
                pl.BlockSpec((1, tq, heads), lambda ib, iq: (ib, iq, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, tq, width), lambda ib, iq: (ib, iq, 0)),
                pl.BlockSpec((1, tq, 8), lambda ib, iq: (ib, iq, 0)),
            ],
            out_shape=[jax.ShapeDtypeStruct((b, s_p, width), jnp.int32),
                       jax.ShapeDtypeStruct((b, s_p, 8), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((s_p // ck, tq, ck), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT) if not interpret else None,
            interpret=interpret,
            name="index_select",
        )(_pad_axis(qi, 2, s_p), _pad_axis(ki, 1, s_p),
          _pad_axis(w.astype(jnp.float32), 1, s_p))
    return words[:, :s], stats[:, :s, 0], stats[:, :s, 1]


def _rows(mesh, *ndims):
    return tuple(rows_spec(batch_axes_of(mesh), n) for n in ndims)


def select_top_k(qi, ki, w, topk: int, implementation: str = "auto",
                 mesh=None):
    """``(words [B, S, W] int32, lse [B, S], kept [B, S])`` of the indexer's
    selection: the packed mask, each row's logsumexp over its kept scores
    and how many it kept. No gradient passes. ``implementation`` as
    ``fused_attention``'s: the kernel on a TPU (``auto``) or by name, else
    the definition (``lax.top_k`` over ``[S, S]``); ``mesh`` as its. Under
    the scope ``indexer_select``: the kernel forms the scores and finds the
    thresholds in one pass; the definition's scores are under
    ``indexer_scores``."""
    qi, ki, w = (jax.lax.stop_gradient(t) for t in (qi, ki, w))
    b, heads, s, _ = qi.shape
    if w.shape != (b, s, heads) or ki.shape[:2] != (b, s):
        raise ValueError(f"an indexer's qI [B, H, S, D], kI [B, S, D] and w "
                         f"[B, S, H]; got {qi.shape}, {ki.shape}, {w.shape}")
    use_pallas, interpret = _kernel_path(implementation)
    get_tracer().registry.counter(
        "attention.selected.calls",
        "selections traced, by the path taken").inc(
            path="kernel" if use_pallas else "xla")
    get_tracer().registry.gauge(
        "attention.selected.topk",
        "keys a row of the traced selection keeps at most, but for ties",
    ).set(topk)
    if use_pallas:
        words, lse, kept = shard_rows(
            functools.partial(_select_pallas, topk=topk,
                              interpret=interpret),
            mesh, "index_select", _rows(mesh, 4, 3, 3), _rows(mesh, 3, 2, 2),
            scope="indexer_select")(qi, ki, w)
    else:
        with jax.named_scope("indexer_scores"):
            scores = index_scores(qi, ki, w)
        with jax.named_scope("indexer_select"):
            keep, lse, kept = select_from_scores(scores, topk)
            words = pack_selection(keep)
    return (checkpoint_name(words, INDEX_SELECTED),
            checkpoint_name(lse, INDEX_STATS),
            checkpoint_name(kept, INDEX_STATS))


# ---------------------------------------------------------------------------
# The loss
# ---------------------------------------------------------------------------


def _loss_kernel(q_ref, k_ref, lse_ref, qi_ref, ki_ref, w_ref, words_ref,
                 lsei_ref, kl_ref, dqi_ref, dw_ref, dki_ref, *, tq, ck,
                 sm_scale, scale, per_run):
    """One (batch, row tile, column tile) step, columns innermost: the
    tile's ``P`` (every query head's ``exp(q . k - lse)``, averaged) and
    ``I``, their share of the rows' KL, and ``dI = softmax(I) - P`` carried
    on into the tile's share of the gradients. ``dqi``, ``dw`` and ``kl``
    accumulate in their output blocks over the columns; ``dki`` is one block
    a batch row, resident throughout. Row statistics and weights come a
    head to a lane (``[tq, H]``): a trailing dimension of 1 is 128 lanes in
    HBM."""
    from jax.experimental import pallas as pl

    iq, step = pl.program_id(1), pl.program_id(2)
    last = (iq * tq + tq - 1) // ck  # the last column tile a row here sees
    heads, index_heads = q_ref.shape[1], qi_ref.shape[1]
    group = heads // k_ref.shape[1]

    @pl.when((iq == 0) & (step == 0))
    def _():
        dki_ref[...] = jnp.zeros_like(dki_ref)

    @pl.when(step == 0)
    def _():
        kl_ref[...] = jnp.zeros_like(kl_ref)
        dqi_ref[...] = jnp.zeros_like(dqi_ref)
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(step <= last)
    def _():
        first_bit = (step % per_run) * (ck // LANES)
        keep = selected_bits(words_ref[0], first_bit, ck // LANES) != 0
        p = None
        flash_lse, w = lse_ref[0], w_ref[0]
        for h in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, h], k_ref[0, h // group], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            e = jnp.exp(s - _column(flash_lse, h))
            p = e if p is None else p + e
        p = jnp.where(keep, p * (1.0 / heads), 0.0)
        ki = ki_ref[0]
        scores = _tile_scores(qi_ref, ki, w, scale)
        lse_i = lsei_ref[0][:, :1]
        soft = jnp.where(keep, jnp.exp(scores - lse_i), 0.0)
        terms = jnp.where(p > 0, p * (jnp.log(jnp.maximum(p, 1e-37))
                                      - scores + lse_i), 0.0)
        kl_ref[0] += jnp.broadcast_to(
            jnp.sum(terms, axis=1, keepdims=True), kl_ref.shape[1:])
        d_scores = (soft - p) * scale
        cols = pl.ds(pl.multiple_of(step * ck, ck), ck)
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        dw, dki = jnp.zeros_like(w), None
        for j in range(index_heads):
            qj = qi_ref[0, j]
            s = jax.lax.dot_general(qj, ki, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            dw = jnp.where(lane == j, jnp.sum(
                d_scores * jnp.maximum(s, 0.0), axis=1, keepdims=True), dw)
            ds = jnp.where(s > 0, d_scores * _column(w, j), 0.0) \
                .astype(ki.dtype)
            dqi_ref[0, j] += jax.lax.dot_general(
                ds, ki, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            part = jax.lax.dot_general(
                ds, qj, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dki = part if dki is None else dki + part
        dw_ref[0] += dw
        dki_ref[0, cols, :] += dki


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _loss_pallas(qi, ki, w, q, k, flash_lse, words, lse_i, *, sm_scale,
                 interpret):
    """``(kl [B], dqi, dki, dw)``: the loss summed over a batch row's rows
    and its gradient at a cotangent of 1, ``dqi`` in ``qi``'s dtype."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, index_heads, s, dim = qi.shape
    heads, d = q.shape[1], q.shape[3]
    tq, ck = _LOSS_ROWS, _LOSS_COLS
    s_p = -(-s // ck) * ck
    rows = lambda t, axis, fill=0: _pad_axis(t, axis, s_p, fill)
    words = _pad_axis(rows(words, 1), 2, packed_width(s_p))
    per_run = SUPER // ck
    # Past the diagonal a step names the diagonal's tile again: no copy.
    live = lambda iq, ik: jnp.minimum(ik, (iq * tq + tq - 1) // ck)
    by_rows = lambda ib, iq, ik: (ib, 0, iq, 0)
    with jax.named_scope("indexer_loss"):
        kl, dqi, dw, dki = pl.pallas_call(
            functools.partial(_loss_kernel, tq=tq, ck=ck, sm_scale=sm_scale,
                              scale=index_scale(index_heads, dim),
                              per_run=per_run),
            grid=(b, s_p // tq, s_p // ck),
            in_specs=[
                pl.BlockSpec((1, heads, tq, d), by_rows),
                pl.BlockSpec((1, k.shape[1], ck, d),
                             lambda ib, iq, ik: (ib, 0, live(iq, ik), 0)),
                pl.BlockSpec((1, tq, heads), lambda ib, iq, ik: (ib, iq, 0)),
                pl.BlockSpec((1, index_heads, tq, dim), by_rows),
                pl.BlockSpec((1, ck, dim),
                             lambda ib, iq, ik: (ib, live(iq, ik), 0)),
                pl.BlockSpec((1, tq, index_heads),
                             lambda ib, iq, ik: (ib, iq, 0)),
                pl.BlockSpec((1, tq, LANES), lambda ib, iq, ik: (
                    ib, iq, live(iq, ik) // per_run)),
                pl.BlockSpec((1, tq, 8), lambda ib, iq, ik: (ib, iq, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, tq, 8), lambda ib, iq, ik: (ib, iq, 0)),
                pl.BlockSpec((1, index_heads, tq, dim), by_rows),
                pl.BlockSpec((1, tq, index_heads),
                             lambda ib, iq, ik: (ib, iq, 0)),
                pl.BlockSpec((1, s_p, dim), lambda ib, iq, ik: (ib, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b, s_p, 8), jnp.float32),
                jax.ShapeDtypeStruct((b, index_heads, s_p, dim),
                                     jnp.float32),
                jax.ShapeDtypeStruct((b, s_p, index_heads), jnp.float32),
                jax.ShapeDtypeStruct((b, s_p, dim), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT) if not interpret else None,
            interpret=interpret,
            name="index_loss",
        )(rows(q, 2), rows(k, 2),
          # A padded row's +LARGE makes exp(s - lse) underflow to 0.
          rows(flash_lse.transpose(0, 2, 1), 1, 1e30), rows(qi, 2),
          rows(ki, 1), rows(w.astype(jnp.float32), 1), words,
          jnp.broadcast_to(rows(lse_i, 1)[..., None], (b, s_p, 8)))
    return (jnp.sum(kl[:, :s, 0], axis=1), dqi[:, :, :s].astype(qi.dtype),
            dki[:, :s].astype(ki.dtype), dw[:, :s].astype(w.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _index_loss_kernel(qi, ki, w, q, k, flash_lse, words, lse_i, sm_scale,
                       interpret):
    return _loss_pallas(qi, ki, w, q, k, flash_lse, words, lse_i,
                        sm_scale=sm_scale, interpret=interpret)[0]


def _index_loss_fwd(qi, ki, w, q, k, flash_lse, words, lse_i, sm_scale,
                    interpret):
    kl, *grads = _loss_pallas(qi, ki, w, q, k, flash_lse, words, lse_i,
                              sm_scale=sm_scale, interpret=interpret)
    return kl, tuple(checkpoint_name(g, INDEX_GRADS) for g in grads)


def _index_loss_bwd(sm_scale, interpret, grads, g):
    dqi, dki, dw = grads
    scaled = lambda t: (t.astype(jnp.float32) * g.reshape(
        (-1,) + (1,) * (t.ndim - 1))).astype(t.dtype)
    return (scaled(dqi), scaled(dki), scaled(dw)) + (None,) * 5


_index_loss_kernel.defvjp(_index_loss_fwd, _index_loss_bwd)


def index_loss(qi, ki, w, q, k, flash_lse, words, lse_i, sm_scale: float,
               implementation: str = "auto", mesh=None) -> jnp.ndarray:
    """``sum_t KL(P_t || softmax_{S_t} I_t)`` a batch row, ``[B]`` float32.
    Differentiable in ``qi``, ``ki`` and ``w`` alone: ``q``, ``k [B, Hk, S,
    D]`` and ``flash_lse [B, H, S]`` (the attention's, under the same
    selection) are constants, as are the selection ``words`` and ``lse_i``
    (:func:`select_top_k`'s)."""
    q, k, flash_lse, words, lse_i = (
        jax.lax.stop_gradient(t) for t in (q, k, flash_lse, words, lse_i))
    use_pallas, interpret = _kernel_path(implementation)
    if use_pallas:
        return shard_rows(
            lambda *operands: _index_loss_kernel(*operands, sm_scale,
                                                 interpret),
            mesh, "index_loss", _rows(mesh, 4, 3, 3, 4, 4, 3, 3, 2),
            _rows(mesh, 1)[0], scope="indexer_loss")(
                qi, ki, w, q, k, flash_lse, words, lse_i)
    keep = unpack_selection(words, qi.shape[2])
    p = head_mean_attention(q, k, flash_lse, sm_scale)
    return index_loss_from_scores(index_scores(qi, ki, w), keep, p)
