"""Expert layers: the dropless layer the styled decoders use, and the 2020
capacity layer the BERT/GPT trunks of ``moe_every`` keep.

**:class:`HeldExpertsMlp`** is the expert layer of the current open models
(Laguna, ZAYA1, Mellum2 through ``BlockStyle.mlp == "experts"``): a router
module scores every token over all the experts, the (token, choice) pairs
are sorted by expert, the rows of the experts held go through one grouped
matmul (megablox's Pallas ``gmm`` / ``tgmm`` on a TPU, each kernel at a tile
chosen from its own shape: :func:`gmm_tile`) and are summed back into their
tokens; rows move through the sort's permutation and its inverse
(:func:`take_rows`, :func:`sum_rows`), fetched by XLA's gathers or, where a
rank's tokens are a source large enough to make those dear, the live ones
alone by a Pallas row kernel (``ops/rows.py``, :func:`rows_path`), and no
row is dropped whatever the routing. Which experts are held is static (``held``); on one
device the layer computes their part of the result and nothing else (one
expert-parallel rank of a pod, the exchange not run). **On a mesh whose
``expert`` axis has R > 1 devices the held experts are divided R ways, the
stacks sharded on their rows, and the exchange between the ranks is run**:
every rank gathers the R ranks' tokens with their choices (``all_gather``),
computes its own experts' parts for all of them, and the parts are summed in
float32 back to the ranks the tokens came from (``psum_scatter``), all
inside one ``shard_map`` (``parallel/kernels.py``), under the scopes
``moe_exchange_in`` / ``moe_exchange_out``. The routers: one matrix with
sigmoid scores (:class:`SigmoidTopKRouter`) or softmax scores
(:class:`SoftmaxTopKRouter`), the chosen normalised; an MLP with memory and
a balancing bias that a controller moves (:class:`MlpStateRouter`).

**:class:`MoeMlp`** is the GShard/Switch formulation (``num_experts`` /
``moe_every`` of the 2018 trunks): routing as dense one-hot einsums over
static shapes, a fixed capacity an expert, dropped tokens masked, a
load-balance and a router-z loss returned to the caller; its stacked
weights ``[E, ...]`` are sharded over ``expert`` by ``MOE_PARAM_RULES`` and
GSPMD inserts the dispatch and combine collectives. No benchmark cell runs
it.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..obs.trace import get_tracer
from ..ops.rows import fits as rows_kernel_fits, sort_with, sum_live_rows
from ..parallel.kernels import batch_axes_of, rows_spec, shard_rows

Dtype = Any

# Param-path rules for the 'expert' mesh axis (see
# parallel.sharding.param_sharding_tree): stacked expert weights shard
# their leading expert dim; the router stays replicated. An ``ExpertStack``
# is one matrix ``[experts * d_in, d_out]`` whose rows are an expert's after
# an expert's: sharded on its rows, each rank holds whole experts.
MOE_PARAM_RULES = (
    (r"moe_mlp/w_in", P("expert", None, None)),
    (r"moe_mlp/w_out", P("expert", None, None)),
    (r"moe_mlp/b_in", P("expert", None)),
    (r"moe_mlp/b_out", P("expert", None)),
    (r"mlp/experts_(in|out)/kernel", P("expert", None)),
)


def router_assignment(
    probs: jnp.ndarray, capacity: int, top_k: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static-shape token→expert assignment.

    probs: [B, S, E] router probabilities. Returns (dispatch, combine):
    dispatch [B, S, E, C] is a 0/1 mask placing each kept token in one
    capacity slot of each chosen expert; combine is dispatch scaled by the
    token's (renormalized) gate for that expert.

    Position assignment is first-come within the sequence (cumsum order),
    with choice-rank priority: all first-choice tokens claim slots before
    any second-choice token, matching GShard's scheme.
    """
    b, s, e = probs.shape
    remaining = probs
    kept_per_expert = jnp.zeros((b, e), probs.dtype)  # slots already claimed
    dispatch = jnp.zeros((b, s, e, capacity), probs.dtype)
    gates = []
    masks = []
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)                      # [B, S]
        mask = jax.nn.one_hot(idx, e, dtype=probs.dtype)          # [B, S, E]
        # Slot index for each token: tokens earlier in the sequence first,
        # offset by slots already claimed by higher-priority choices.
        pos = (jnp.cumsum(mask, axis=1) - mask) \
            + kept_per_expert[:, None, :]                          # [B, S, E]
        keep = mask * (pos < capacity)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                              dtype=probs.dtype)                   # [B,S,E,C]
        dispatch = dispatch + keep[..., None] * slot
        kept_per_expert = kept_per_expert + jnp.sum(keep, axis=1)
        gates.append(jnp.sum(probs * mask, axis=-1))               # [B, S]
        masks.append(keep)
        remaining = remaining * (1.0 - mask)
    # Renormalize the k gates to sum to 1 over the token's chosen experts,
    # then zero the dropped ones.
    gate_stack = jnp.stack(gates, axis=-1)                         # [B, S, K]
    gate_stack = gate_stack / jnp.maximum(
        jnp.sum(gate_stack, axis=-1, keepdims=True), 1e-9)
    combine = jnp.zeros_like(dispatch)
    for k, keep in enumerate(masks):
        # keep is one-hot over E for choice k; place its gate in the slot.
        slot = dispatch * keep[..., None]                          # [B,S,E,C]
        combine = combine + slot * gate_stack[..., k][..., None, None]
    return dispatch, combine


class MoeMlp(nn.Module):
    """Drop-in MoE replacement for transformer.Mlp.

    Returns ``(y, aux)`` where aux = {"load_balance": ..., "router_z": ...}
    (unweighted scalars; the model sums them into its loss with its own
    weights).
    """

    num_experts: int
    mlp_dim: int
    capacity_factor: float = 1.25
    top_k: int = 2
    dtype: Dtype = jnp.bfloat16
    act: Callable = nn.gelu

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        b, s, f = x.shape
        e, m = self.num_experts, self.mlp_dim
        if self.top_k > e:
            raise ValueError(f"top_k={self.top_k} > num_experts={e}")
        capacity = max(1, int(self.top_k * s / e * self.capacity_factor))

        logits = nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32,
                          kernel_init=nn.initializers.normal(0.02),
                          use_bias=False, name="router")(x.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)                    # [B, S, E]
        dispatch, combine = router_assignment(probs, capacity, self.top_k)
        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)

        # Stacked expert weights, expert dim sharded over the mesh.
        w_in = self.param("w_in", nn.initializers.xavier_uniform(),
                          (e, f, m), jnp.float32)
        b_in = self.param("b_in", nn.initializers.zeros_init(),
                          (e, m), jnp.float32)
        w_out = self.param("w_out", nn.initializers.xavier_uniform(),
                           (e, m, f), jnp.float32)
        b_out = self.param("b_out", nn.initializers.zeros_init(),
                           (e, f), jnp.float32)

        xd = x.astype(self.dtype)
        # Dispatch: gather each expert's capacity slots from the sequence.
        x_e = jnp.einsum("bsec,bsf->becf", dispatch, xd)           # [B,E,C,F]
        h = jnp.einsum("becf,efm->becm", x_e, w_in.astype(self.dtype))
        h = self.act(h + b_in.astype(self.dtype)[None, :, None, :])
        y_e = jnp.einsum("becm,emf->becf", h, w_out.astype(self.dtype))
        y_e = y_e + b_out.astype(self.dtype)[None, :, None, :]
        # Combine: scatter expert outputs back to token positions, gated.
        y = jnp.einsum("bsec,becf->bsf", combine, y_e)

        # Aux losses (float32, per-token means — DP/psum-correct).
        first_choice = jax.nn.one_hot(jnp.argmax(probs, -1), e,
                                      dtype=jnp.float32)
        f_e = jnp.mean(first_choice, axis=(0, 1))                  # [E]
        p_e = jnp.mean(probs, axis=(0, 1))                         # [E]
        load_balance = e * jnp.sum(f_e * p_e)
        router_z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        return y, {"load_balance": load_balance, "router_z": router_z}


# ---------------------------------------------------------------------------
# An expert layer that is told which experts it holds
# ---------------------------------------------------------------------------


# A grouped product's tile is chosen from its own shape, a kernel at a time
# (:func:`gmm_tile`), by constants read from the three kernels timed apart at
# the three expert cells' shapes (tools/gmm_tile_sweep.py; my chip run, PR 38,
# call A, the lines in tools/gmm_tile_sweep_pr38.jsonl): ``gmm``, forward and
# transposed, is fastest with its contraction whole (the weights' block stays
# in VMEM over a group's row tiles), ``tgmm`` with a result block of a million
# elements, its rows under 1152. The contraction tile's cap, a kernel:
_GMM_CONTRACTION = {"gmm": 4096, "gmm_t": 4096, "tgmm": 1152}
# The longest row tile, and the buffer rows a group from which it pays (in
# ``tgmm`` 512 rows are 3 % ahead of 256 at Mellum2's 8,192 a group, 10 and
# 26 % behind at ZAYA1's 1,024 and Laguna's 512).
_ROW_TILE = 512
_LONG_GROUP = 2048
# Of the 16 MiB a kernel may use. Every tile up to 13.75 MiB by
# :func:`gmm_tile_vmem` compiled and ran (call A); (256, 1152, 1792) in
# ``tgmm``, 19.5 MiB, "ran out of memory in memory space vmem" (PR 35).
_GMM_VMEM = 13 * 2 ** 20
# The usual row buffer, in rows a uniform router would send to the experts
# held: twice them.
_BUFFER_SHARE = 2.0
# The rate of the controller that moves an MLP router's balancing bias: the
# largest step a training step makes. ZAYA1-8B's router from seeded weights
# starts collapsed onto two or three of its 16 experts (fullest over mean
# 2.7-4.9) and is balanced (1.2-1.3) after three steps at this rate; 0.03 is
# a step faster and noisier afterwards, 0.01 takes about ten (PERF.md).
BALANCE_RATE = 0.02


def _named(implementation: str) -> str:
    """``auto`` read: the kernels on a TPU, XLA's forms elsewhere."""
    if implementation != "auto":
        return implementation
    return "megablox" if jax.default_backend() == "tpu" else "ragged_dot"


def gmm_tile_vmem(kernel: str, tm: int, tk: int, tn: int) -> int:
    """The bytes of VMEM a grid step of ``kernel`` holds at a tile, bfloat16
    operands: its three blocks twice over (Pallas fetches the next while one
    is computed) and the float32 accumulator, ``[tm, tn]`` in ``gmm``,
    ``[tk, tn]`` in ``tgmm``, whose row tile is its contraction."""
    blocks, acc = (tm * (tk + tn) + tk * tn, tk * tn) if kernel == "tgmm" \
        else (tk * (tm + tn) + tm * tn, tm * tn)
    return 2 * 2 * blocks + 4 * acc


def _dividing(x: int, cap: int) -> int:
    """The tile of a dimension ``x``: the whole of it under ``cap``, else the
    largest multiple of 128 lanes up to ``cap`` that divides it, else (no
    divisor over half of ``cap``: a padded tile wastes less than a narrow
    one) ``cap``'s whole lane tiles, the last tile padded and masked."""
    if x <= cap:
        return x
    cap -= cap % 128
    return next((t for t in range(cap, cap // 2, -128) if x % t == 0), cap)


def gmm_tile(kernel: str, m: int, k: int, n: int, groups: int
             ) -> Tuple[int, int, int]:
    """``(tm, tk, tn)`` for one megablox kernel of a grouped product, from
    what the kernel is asked: ``"gmm"`` ``[m, k] x [groups, k, n]``,
    ``"gmm_t"`` the rows' gradient (the same kernel, the weights transposed:
    ``k`` is the forward's columns, ``n`` its contraction), ``"tgmm"`` the
    weights' gradient ``[k, m] x [m, n]`` (``tm`` cuts its contraction, the
    rows; ``[tk, tn]`` is a group's block of the result).

    ``tk`` is the whole of ``k`` under the kernel's ``_GMM_CONTRACTION``,
    else what divides it (:func:`_dividing`); ``tn`` the widest tile that
    divides ``n`` with :func:`gmm_tile_vmem` under ``_GMM_VMEM`` (where not
    even 512 columns fit, ``tk`` is halved); ``tm`` 256 rows, or
    ``_ROW_TILE`` where the buffer gives a group ``_LONG_GROUP`` rows or more
    and the rows are the contraction (``tgmm``) or the contraction is cut
    (the weights' block is then fetched again for every row tile), in either
    case a row tile that divides ``m``."""
    cap = _GMM_CONTRACTION[kernel]
    while cap >= 128:
        tk = _dividing(k, cap)
        want = _ROW_TILE if m >= _LONG_GROUP * groups and (
            kernel == "tgmm" or tk < k) else _ROW_TILE // 2
        tm = next((t for t in (want, want // 2, want // 4) if m % t == 0), m)
        tn = n
        while tn >= min(n, 512):
            tn = _dividing(n, tn)
            if gmm_tile_vmem(kernel, tm, tk, tn) <= _GMM_VMEM:
                return tm, tk, tn
            tn -= 128
        cap //= 2
    raise ValueError(f"no tile of {kernel} at {(m, k, n)} fits VMEM")


def _tile_of(kernel: str, m: int, k: int, n: int, groups: int):
    """:func:`gmm_tile`, counted: ``moe.gmm.calls`` once a kernel traced, by
    the kernel, its tile and whether the tile divides the contraction and the
    columns it is asked (``divides=no``: a last tile padded and masked)."""
    tile = gmm_tile(kernel, m, k, n, groups)
    get_tracer().registry.counter(
        "moe.gmm.calls",
        "grouped-matmul kernels traced, by kernel, tile and whether the "
        "tile divides the product's contraction and columns",
    ).inc(kernel=kernel, tile="x".join(map(str, tile)),
          divides="no" if k % tile[1] or n % tile[2] else "yes")
    return tile


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def megablox_gmm(lhs, rhs, group_sizes, interpret: bool = False):
    """megablox's ``gmm`` with the tile of :func:`gmm_tile`, and a VJP that
    gives the backward ``gmm`` and ``tgmm`` each its own (megablox's own VJP
    hands all three kernels the forward's)."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    (m, k), (groups, _, n) = lhs.shape, rhs.shape
    return gmm(lhs, rhs, group_sizes, lhs.dtype,
               _tile_of("gmm", m, k, n, groups), interpret=interpret)


def _megablox_gmm_fwd(lhs, rhs, group_sizes, interpret):
    return megablox_gmm(lhs, rhs, group_sizes, interpret), \
        (lhs, rhs, group_sizes)


def _megablox_gmm_bwd(interpret, kept, grad):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = kept
    (m, k), (groups, _, n) = lhs.shape, rhs.shape
    d_lhs = gmm(grad, rhs, group_sizes, lhs.dtype,
                _tile_of("gmm_t", m, n, k, groups), transpose_rhs=True,
                interpret=interpret)
    d_rhs = tgmm(lhs.swapaxes(0, 1), grad, group_sizes, rhs.dtype,
                 _tile_of("tgmm", m, k, n, groups), num_actual_groups=groups,
                 interpret=interpret)
    return d_lhs, d_rhs, None


megablox_gmm.defvjp(_megablox_gmm_fwd, _megablox_gmm_bwd)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray,
                   implementation: str = "auto") -> jnp.ndarray:
    """``lhs[rows of group g] @ rhs[g]`` for rows sorted by group: ``lhs``
    ``[rows, k]``, ``rhs`` ``[groups, k, n]``, ``group_sizes`` ``[groups]``
    int32 whose sum may be less than ``rows``. Rows past the last group cost
    next to nothing and come back undefined: the caller masks them.

    On a TPU the Pallas grouped matmul of ``jax.experimental.pallas.ops.tpu
    .megablox`` (its grid is the row tiles that hold a group's rows, found
    from ``group_sizes`` at run time; backward by the same kernel with the
    weights transposed, the weights' gradient by the transposed kernel
    ``tgmm``), each kernel at the tile :func:`gmm_tile` chooses for its
    shape (:func:`megablox_gmm`); elsewhere ``jax.lax.ragged_dot``."""
    implementation = _named(implementation)
    if implementation in ("ragged_dot", "interpret"):
        return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32),
                                  preferred_element_type=lhs.dtype)
    if implementation != "megablox":
        raise ValueError(f"unknown implementation {implementation!r}")
    return megablox_gmm(lhs, rhs, group_sizes.astype(jnp.int32))


def _whole_tiles(rows: int) -> int:
    """``rows`` rounded up to what any row tile of :func:`gmm_tile`
    divides."""
    tile = _ROW_TILE if rows > _ROW_TILE else 8
    return -(-rows // tile) * tile


class ExpertStack(nn.Module):
    """The weights of the experts held, one 2-D ``kernel``
    ``[experts * d_in, d_out]`` (a matrix like any other to initialisers,
    weight decay and checkpoints), seen as ``[experts, d_in, d_out]``."""

    experts: int
    d_in: int
    d_out: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", nn.initializers.xavier_uniform(),
                            (self.experts * self.d_in, self.d_out),
                            jnp.float32)
        return kernel.astype(self.dtype).reshape(
            self.experts, self.d_in, self.d_out)


def inverse_permutation(order: jnp.ndarray) -> jnp.ndarray:
    """``inv`` with ``inv[order[r]] = r``: where the sort put each pair. A
    second sort: on the chip it takes 0.05 ms for 65,536 pairs where a
    scatter of ``arange`` takes 0.30 (tools/moe_rows_sweep.py; PERF.md,
    PR 33)."""
    return jnp.argsort(order).astype(order.dtype)


# The bytes of a gather's source from which XLA's gather pays three times as
# much a slot (128 MiB, the chip's VMEM: tools/moe_rows_sweep.py, PR 36).
_GATHER_CLIFF = 2 ** 27
# Both sides of the rows' movement by XLA's gathers (:func:`rows_path`).
_GATHERS = ("gather", "gather")


def _buffer_rows(pairs: int, count: int, num_experts: int) -> int:
    """The rows of the usual buffer where ``count`` of ``num_experts``
    experts are held over ``pairs`` (token, choice) pairs: twice a uniform
    router's (``_BUFFER_SHARE``) in whole tiles, at most every pair."""
    return min(pairs, _whole_tiles(
        int(_BUFFER_SHARE * pairs * count / num_experts)))


def rows_path(implementation: str, tokens: int, width: int, dtype,
              top_k: int, count: int, num_experts: int) -> Tuple[str, str]:
    """How :func:`take_rows` and :func:`sum_rows` fetch a row, **each side of
    the movement from its own source**: ``(to the buffer, to the tokens)``,
    each ``"kernel"`` (``ops/rows.py``: one DMA a live slot) or XLA's
    ``"gather"``; ``"interpret"`` is the kernel interpreted, for the tests.
    *To the buffer* (``take_rows`` forward, ``sum_rows``' ``d y``) the source
    is the rank's ``tokens`` rows of ``width`` in ``dtype`` (after the
    exchange's gather) and the slots are the buffer's rows; *to the tokens*
    (``sum_rows`` forward, ``take_rows``' transpose) the source is the usual
    buffer (:func:`_buffer_rows` of the ``tokens * top_k`` pairs where
    ``count`` of ``num_experts`` experts are held) and the slots are every
    pair.

    Alone on the chip (``tools/moe_rows_sweep.py --quick``, my chip runs,
    PR 36, calls 1 and 2, ``chiprun_out/c1_rows_*.jsonl``,
    ``c2_rows_kernel.jsonl``) XLA's gather pays by the slot, dead or live,
    and what a slot costs follows the **source's size, not the row's width**:
    14-19 ns a row of 2048 bf16 from sources of 33 to 101 MB, 46 ns from 134
    MB (2 ** 27 bytes) on, and there in step with the width (46 / 51 / 56 ns
    at 2048 / 2304 / 2560: 2.85 ns each 128 lanes); the source seen as 32-bit
    words, as ``[M, F / 256, 256]`` or split at 2048 columns is no faster
    anywhere. The kernel pays 45 ns a live row and nothing for a dead slot,
    and one copy of its source to pairs of rows. A rank of Mellum2's four
    (32,768 tokens of 2304, 151 MB; a buffer of 131,072 rows, 604 MB; one
    slot in four live on the tokens' side, one in two on the buffer's): 6.70
    / 12.98 / 13.11 / 10.51 ms the four movements by XLA, 3.68 / 6.04 / 6.10
    / 5.75 by the kernel. Laguna's 8,192 tokens of 2048 (33.5 MB): 0.25 /
    0.91 / 0.94 / 0.86 by XLA, 0.36 / 0.70 / 0.70 / 0.58 by the kernel, 0.5
    ms a layer for twenty more kernels to lower at set-up; ZAYA1's one
    choice a token: 0.10 / 0.10 / 0.10 / 0.27 by XLA, 0.24 / 0.26 / 0.27 /
    0.28 by the kernel. SDAR's and Keye's 16,384 positions of 2048 (2 ** 26
    bytes) with 16 of 128 experts held, a buffer of 32,768 rows (2 ** 27
    bytes) of which 14.8 to 21.2 k are live (PR 48, call 2,
    ``tools/moe_rows_sweep_pr48.jsonl``): to the tokens, 131,072 slots out
    of the buffer, 6.07-6.14 and 6.14-6.20 ms by XLA (46-47 ns a slot) for
    1.55-1.74 and 1.58-1.76 by the kernel; to the buffer, 32,768 slots out
    of the tokens, 0.62 by XLA (18.8 ns a slot) for 0.72-0.82 by the kernel
    forward, and 2.35-2.54 for 1.23-1.31 in ``sum_rows``' transpose, where
    what XLA loses is not a row's price but the rows' dots sent back by a
    gather of 131,072 numbers (1.1 ms; the kernel's form sorts them, 0.2).
    **So: the kernel on the side whose source is 2 ** 27 bytes or more**,
    on a TPU or where the kernels are named (``implementation="megablox"``:
    a compile for a chip that is not attached), for rows the kernel can
    move; XLA's gather elsewhere, and off the TPU as :func:`grouped_matmul`
    falls back to ``ragged_dot``."""
    if not rows_kernel_fits(width, dtype):
        return _GATHERS
    if implementation == "interpret":
        return "interpret", "interpret"
    if _named(implementation) != "megablox":
        return _GATHERS
    row = width * jnp.dtype(dtype).itemsize
    return tuple(
        "kernel" if source * row >= _GATHER_CLIFF else "gather"
        for source in (tokens, _buffer_rows(tokens * top_k, count,
                                            num_experts)))


def _rows_of_tokens(y, weight, inv, n_live, top_k: int):
    """``[tokens, F]`` float32: for every token the sum over its ``top_k``
    pairs of the row of ``y`` the pair lies at (``inv``), times the pair's
    ``weight`` where one is given, over the pairs whose row is live
    (``inv < n_live``), in the choices' order; a dead pair reads the last
    row and is masked. XLA's form, where the kernel is not taken.

    Under the usual buffer a gather a choice, added in turn: in Laguna's
    step one gather ``[tokens, k, F]`` is written out in float32 before it
    is summed and loses what the gathers win (PERF.md, PR 33). Under the
    buffer of every pair, which few steps take and where both forms cost
    the same alone, the one gather: eight gathers in both branches of every
    layer are 2-3 s more program to load at set-up."""
    rows = y.shape[0]
    at = jnp.minimum(inv, rows - 1).reshape(-1, top_k)
    live = (inv < n_live).reshape(-1, top_k, 1)
    weight = None if weight is None else weight.reshape(-1, top_k, 1)

    def terms(got, j):
        got = got.astype(jnp.float32)
        if weight is not None:
            got = got * weight[:, j]
        return jnp.where(live[:, j], got, 0)

    if top_k > 1 and rows == inv.shape[0]:
        return jnp.sum(terms(y[at], slice(None)), axis=1)
    total = terms(y[at[:, 0]], 0)
    for j in range(1, top_k):
        total = total + terms(y[at[:, j]], j)
    return total


def _live_first(inv, n_live, top_k: int, weight=None):
    """What the kernel walks on the tokens' side: ``(at [tokens, k], count
    [tokens], weight [tokens, k])``, each token's live pairs' rows (and
    weights) moved to the front of its ``k`` in the choices' order, and how
    many they are. A stable partition of ``k``, written as a one-hot sum: a
    gather of 262,144 integers would pay XLA's price a slot."""
    at = inv.reshape(-1, top_k)
    live = at < n_live
    place = jnp.cumsum(live, axis=1, dtype=jnp.int32) - 1
    put = live[:, :, None] & (place[:, :, None] == jnp.arange(top_k))
    first = lambda a: jnp.sum(jnp.where(put, a[:, :, None], 0), axis=1)
    return first(at), jnp.sum(live, axis=1, dtype=jnp.int32), \
        None if weight is None else first(weight.reshape(-1, top_k))


def _rows_to_tokens(y, weight, inv, n_live, top_k: int, out_dtype, path):
    """:func:`_rows_of_tokens` in ``out_dtype``, by ``path``."""
    if path == "gather":
        return _rows_of_tokens(y, weight, inv, n_live, top_k).astype(
            out_dtype)
    at, count, weight = _live_first(inv, n_live, top_k, weight)
    return sum_live_rows(y, at, count, weight, out_dtype=out_dtype,
                         interpret=path == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def take_rows(m, token, inv, n_live, top_k: int, path=_GATHERS):
    """``xs[r] = m[token[r]]`` for the live rows ``r < n_live`` of the
    buffer, 0 for the others. Its transpose is :func:`sum_rows` without the
    weights, and is written down as that: autodiff would make a scatter-add
    of it. ``path`` (:func:`rows_path`) is how a row is fetched: to the
    buffer here, to the tokens in the transpose."""
    to_buffer, _ = path
    if to_buffer == "gather":
        live = jnp.arange(token.shape[0]) < n_live
        return jnp.where(live[:, None], m[token], 0)
    return sum_live_rows(m, token[:, None], n_live,
                         interpret=to_buffer == "interpret")


def _take_rows_fwd(m, token, inv, n_live, top_k, path):
    return take_rows(m, token, inv, n_live, top_k, path), (inv, n_live)


def _take_rows_bwd(top_k, path, kept, d_xs):
    inv, n_live = kept
    return _rows_to_tokens(d_xs, None, inv, n_live, top_k, d_xs.dtype,
                           path[1]), None, None, None


take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def sum_rows(y, weight, order, inv, n_live, top_k: int, out_dtype=None,
             path=_GATHERS):
    """``out[t] = sum over j of weight[t k + j] * y[inv[t k + j]]`` over the
    pairs whose row is live, products and sum in float32, in ``y``'s dtype
    (or ``out_dtype``: float32 where the ranks' parts are still to be
    summed): every row of the buffer added into its token by a gather
    through the sort's inverse. Its transpose is :func:`take_rows` times the
    weights: ``d y[r] = weight[order[r]] * d out[token[r]]``, and
    ``d weight[p]`` is the dot of pair ``p``'s row with its token's
    cotangent. Of ``path`` (:func:`rows_path`) the side to the tokens here,
    the side to the buffer in the transpose."""
    return _rows_to_tokens(y, weight, inv, n_live, top_k,
                           out_dtype or y.dtype, path[1])


def _sum_rows_fwd(y, weight, order, inv, n_live, top_k, out_dtype, path):
    return sum_rows(y, weight, order, inv, n_live, top_k, out_dtype, path), \
        (y, weight, order, inv, n_live)


def _sum_rows_bwd(top_k, out_dtype, path, kept, d_out):
    y, weight, order, inv, n_live = kept
    rows = y.shape[0]
    pair = order[:rows]
    to_buffer, _ = path
    if to_buffer == "gather":
        live = (jnp.arange(rows) < n_live)[:, None]
        g = d_out[pair // top_k].astype(jnp.float32)
        d_y = jnp.where(live, g * weight[pair][:, None], 0).astype(y.dtype)
        dots = jnp.sum(jnp.where(live, y.astype(jnp.float32) * g, 0), axis=1)
        d_weight = jnp.where(inv < n_live,
                             dots[jnp.minimum(inv, rows - 1)], 0)
        return d_y, d_weight.astype(weight.dtype), None, None, None
    # The rows' dots with their tokens' cotangents ride in the pass that
    # fetches those cotangents. A number a pair goes through the permutation
    # by a sort (0.24 ms for 262,144), not by a gather, which pays 8.6 ns a
    # slot for a number as it pays 50 for a row (PERF.md, PR 36): the live
    # places ``r < n_live`` are each taken by one pair, so the weights sorted
    # by ``inv`` begin with ``weight[pair]``.
    d_y, dots = sum_live_rows(
        d_out, (pair // top_k)[:, None], n_live,
        sort_with(inv, weight)[1][:rows, None], dot_with=y,
        out_dtype=y.dtype, interpret=to_buffer == "interpret")
    if order.shape[0] == inv.shape[0]:
        at_pair = sort_with(order, jnp.pad(
            dots[:, 0], (0, inv.shape[0] - rows)))[1]
    else:       # a window of the sorted pairs (``_in_passes``)
        at_pair = dots[jnp.minimum(inv, rows - 1), 0]
    d_weight = jnp.where(inv < n_live, at_pair, 0)
    return d_y, d_weight.astype(weight.dtype), None, None, None


sum_rows.defvjp(_sum_rows_fwd, _sum_rows_bwd)


def _held_rows(m, pair_weight, order, inv, sizes, n_held, w_in, w_out, *,
               rows: int, top_k: int, implementation: str,
               path=_GATHERS, out_dtype=None):
    """The held experts' part of the layer's result from a buffer of ``rows``
    rows: the first ``rows`` (token, choice) pairs in ``order`` (sorted by
    expert, those of held experts first, ``n_held`` of them); ``inv`` is
    where ``order`` put each pair."""
    n_live = jnp.minimum(n_held, rows)
    valid = (jnp.arange(rows) < n_live)[:, None]
    with jax.named_scope("moe_dispatch"):
        xs = take_rows(m, order[:rows] // top_k, inv, n_live, top_k, path)
    with jax.named_scope("moe_experts"):
        h = grouped_matmul(xs, w_in, sizes, implementation)
        gate, up = jnp.split(jnp.where(valid, h, 0), 2, axis=-1)
        y = grouped_matmul(nn.silu(gate) * up, w_out, sizes, implementation)
    with jax.named_scope("moe_combine"):
        return sum_rows(y, pair_weight, order, inv, n_live, top_k, out_dtype,
                        path)


def _in_passes(part, rows: int, m, pair_weight, order, inv, sizes, n_held,
               w_in, w_out):
    """The second buffer of a rank that gathers its ranks' tokens: not one
    buffer of every pair (R times a lone rank's, 4.3 GiB more of
    temporaries in the Mellum2 cell by the chip's compiler) but the usual
    one again, a window of ``rows`` sorted rows at a time until every pair
    is taken, the windows' results added in float32. ``part`` is
    :func:`_held_rows` at ``rows``; a window sees its own slice of
    ``order``, where that slice put each pair, and each group's rows inside
    it."""
    pairs = order.shape[0]
    passes = -(-pairs // rows)
    order = jnp.pad(order, (0, passes * rows - pairs))
    ends = jnp.cumsum(sizes)

    def one(out, lo):
        inside = lambda at: jnp.clip(at, lo, lo + rows)
        return out + part(
            m, pair_weight, jax.lax.dynamic_slice(order, (lo,), (rows,)),
            jnp.where(inv >= lo, inv - lo, pairs),
            inside(ends) - inside(ends - sizes),
            jnp.clip(n_held - lo, 0, rows), w_in, w_out), None

    return jax.lax.scan(one, jnp.zeros(m.shape, jnp.float32),
                        jnp.arange(passes, dtype=jnp.int32) * rows)[0]


def _rank_part(m, chosen, weight, w_in, w_out, *, num_experts: int,
               first: int, ranks: int, implementation: str, path):
    """What one rank adds to the layer's result: ``m [T, F]`` its own tokens
    with their ``chosen [T, k]`` experts and ``weight [T, k]``, ``w_in`` /
    ``w_out`` the stacks of the experts it holds, ``first`` the first of
    them where there is one rank. Returns ``(y [T, F], sizes [1, held])``:
    the rows each held expert took.

    With ``ranks`` > 1 (inside the ``shard_map``, the ``expert`` axis
    manual) the rank's first expert follows its index on the axis, and the
    exchange is here: the ranks' tokens, choices and weights are gathered,
    so that every rank computes its own experts' parts for all ``ranks * T``
    tokens, and the float32 parts are summed over the ranks and scattered
    back, each token's sum to the rank it came from. At 8 choices over 4
    ranks a token goes to 3.6 of them: the gather moves no more bytes than
    an all-to-all of rows would, and the rows' sort and gathers stay what
    they are on one rank. **Dropless**, and no rank computes another's
    experts."""
    e, count = num_experts, w_in.shape[0]
    if ranks > 1:
        with jax.named_scope("moe_exchange_in"):
            m, chosen, weight = (
                jax.lax.all_gather(t, "expert", axis=0, tiled=True)
                for t in (m, chosen, weight))
        first = first + jax.lax.axis_index("expert") * count
    k = chosen.shape[-1]
    pairs = chosen.size
    with jax.named_scope("moe_dispatch"):
        # Held experts become groups 0 .. count - 1, every other expert
        # the group ``count``, which sorts last and is never computed.
        group = jnp.minimum((chosen.reshape(-1) - first) % e, count)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        sizes = jnp.sum(group[:, None] == jnp.arange(count)[None, :],
                        axis=0, dtype=jnp.int32)
        n_held = jnp.sum(sizes)
        inv = inverse_permutation(order)
    # Recomputed in the backward pass: little arithmetic, and the row
    # buffers (0.3 GB a layer at the usual size, four times that at the
    # other) are then never kept.
    # The second buffer, which few steps take, keeps XLA's gathers on both
    # sides whatever ``path`` is: the row kernel there too is twenty more
    # kernels in Mellum2's step, 38 MB more of a 342 MB program to load at
    # every start and 3.6 s more to trace and lower (PERF.md, PR 36; PR 33
    # kept that branch's one gather for the same reason).
    part = lambda rows, path=path: jax.checkpoint(functools.partial(
        _held_rows, rows=rows, top_k=k, implementation=implementation,
        path=path, out_dtype=jnp.float32 if ranks > 1 else None))
    usual = _buffer_rows(pairs, count, e)
    operands = (m, weight.reshape(-1), order, inv, sizes, n_held, w_in,
                w_out)
    if usual == pairs:
        y = part(pairs)(*operands)
    else:
        second = part(pairs, _GATHERS) if ranks == 1 else functools.partial(
            _in_passes, part(usual, _GATHERS), usual)
        y = jax.lax.cond(n_held <= usual, part(usual), second, *operands)
    if ranks > 1:
        with jax.named_scope("moe_exchange_out"):
            y = jax.lax.psum_scatter(y, "expert", scatter_dimension=0,
                                     tiled=True).astype(m.dtype)
    return y, sizes[None]


class SigmoidTopKRouter(nn.Module):
    """One matrix, a sigmoid, the ``top_k`` largest: ``s = sigmoid(x W_r)`` in
    float32; ``w_e = routed_scale * s_e / sum over the chosen``. It keeps no
    state: what it is given it drops, and it hands on none."""

    num_experts: int
    top_k: int
    routed_scale: float = 1.0

    @nn.compact
    def __call__(self, m, state=None):
        del state
        kernel = self.param("kernel", nn.initializers.xavier_uniform(),
                            (m.shape[-1], self.num_experts), jnp.float32)
        logits = jnp.dot(m.astype(jnp.float32), kernel,
                         precision=jax.lax.Precision.HIGHEST)
        top, chosen = jax.lax.top_k(jax.nn.sigmoid(logits), self.top_k)
        return chosen, self.routed_scale * top \
            / jnp.sum(top, axis=-1, keepdims=True), None


class SoftmaxTopKRouter(nn.Module):
    """One matrix, a softmax over all the experts, the ``top_k`` largest,
    normalised (``norm_topk_prob``, the Qwen3-MoE convention): ``p =
    softmax(x W_r)`` in float32; ``w_e = p_e / sum over the chosen``. It keeps
    no state."""

    num_experts: int
    top_k: int

    @nn.compact
    def __call__(self, m, state=None):
        del state
        kernel = self.param("kernel", nn.initializers.xavier_uniform(),
                            (m.shape[-1], self.num_experts), jnp.float32)
        logits = jnp.dot(m.astype(jnp.float32), kernel,
                         precision=jax.lax.Precision.HIGHEST)
        top, chosen = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                    self.top_k)
        return chosen, top / jnp.sum(top, axis=-1, keepdims=True), None


class MlpStateRouter(nn.Module):
    """A router that is an MLP with memory (Zyphra's ZAYA1): ``z = x W_d +
    b_d`` ``[T, hidden]``, plus ``gamma * r`` where the layer before handed
    its own ``z`` on as ``r`` (exponential depth averaging; the first layer
    is given none and has no ``gamma``); ``r' = z`` goes to the next layer;
    ``p = softmax(W_3 gelu(W_2 gelu(W_1 RMSNorm(z) + b_1) + b_2))`` in
    float32. **One expert a token**: ``e = argmax(p + beta)``; the combine
    weight is ``p[e]`` itself. Normalised over the one chosen it would be 1,
    and the router cut off from the loss.

    ``beta`` is the balancing bias (the parameter ``bias``, 0 from the
    seed). It enters the choice alone, so no gradient reaches it; what moves
    it is a controller on the load: after a training step ``beta_e <-
    beta_e - BALANCE_RATE * min(n_e / mean(n) - 1, 1)``, ``n_e`` the tokens
    of the step that chose expert ``e``. The step is sown as
    ``nudges/.../bias`` for the trainer to add once the optimizer has run
    (``train/state.py``); nothing moves where that collection is not
    asked for."""

    num_experts: int
    hidden: int
    rms_eps: float = 1e-5

    @nn.compact
    def __call__(self, m, state=None):
        from .transformer import RMSNorm

        dense = lambda feats, name, bias=True: nn.Dense(
            feats, use_bias=bias, dtype=jnp.float32, param_dtype=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
            kernel_init=nn.initializers.xavier_uniform(), name=name)
        z = dense(self.hidden, "down")(m.astype(jnp.float32))
        if state is not None:
            gamma = self.param("scale", nn.initializers.ones,
                               (self.hidden,), jnp.float32)
            z = z + gamma * state
        y = RMSNorm(self.rms_eps, jnp.float32, name="norm")(z)
        for name in ("hidden_0", "hidden_1"):
            y = nn.gelu(dense(self.hidden, name)(y), approximate=False)
        probs = jax.nn.softmax(dense(self.num_experts, "out", False)(y))
        beta = self.param("bias", nn.initializers.zeros,
                          (self.num_experts,), jnp.float32)
        chosen = jnp.argmax(probs + jax.lax.stop_gradient(beta), axis=-1,
                            keepdims=True)
        if not self.is_initializing():
            load = jnp.sum(chosen == jnp.arange(self.num_experts)[None, :],
                           axis=0, dtype=jnp.float32)
            self.sow("nudges", "bias", -BALANCE_RATE * jnp.minimum(
                load / jnp.mean(load) - 1.0, 1.0),
                reduce_fn=lambda _, step: step, init_fn=lambda: None)
        return chosen, jnp.take_along_axis(probs, chosen, axis=-1), z


class HeldExpertsMlp(nn.Module):
    """The expert layer of a styled block. It is told the ``num_experts``
    experts of the whole layer, which of them are ``held`` here (``(first,
    count)``; ``count`` 0 is all of them) and its ``router``, a module
    ``(tokens [T, F], state) -> (chosen [T, k], weight [T, k], state')`` that
    scores every token over all the experts, held or not. Either a router or
    ``top_k`` (with ``routed_scale``), never both: told ``top_k`` the layer
    makes the :class:`SigmoidTopKRouter` of them itself. It returns the held
    experts' part of the layer's result, ``sum over chosen and held e of w_e
    E_e(x)``, each ``E`` a gated MLP ``(silu(x W1) * x W3) W2`` of width
    ``mlp_dim``, plus the shared expert's where ``shared_dim`` > 0.

    **One device** (no ``mesh``, or a mesh of one): the layer is one
    expert-parallel rank of a larger deployment. Summed over such ranks (the
    shared expert counted once) the parts are the whole layer; the exchange
    with the other ranks is not run and nothing stands in for it.

    **A mesh** (``mesh``, the step's): the rows' part of the layer (sort,
    gathers, grouped matmuls) runs under one ``shard_map`` over the mesh's
    batch axes (:func:`_rank_part` through ``parallel/kernels.py``), every
    device on its own tokens. Where the ``expert`` axis has R > 1 devices the
    held experts are divided R ways (``count`` a multiple of R; the stacks'
    rows sharded by ``MOE_PARAM_RULES``, rank r holding experts ``first + r
    count / R`` on), and **the exchange between the ranks is run**: an
    ``all_gather`` of the R ranks' tokens, choices and weights under
    ``moe_exchange_in``, each rank's own experts over all of them, a float32
    ``psum_scatter`` of the parts under ``moe_exchange_out``; the backward
    pass is the transposes (a gather of the cotangents, a scatter-sum of the
    tokens'). ``moe.exchange.calls`` (``path=all_gather``, ``ranks=R``)
    counts the layer calls traced that way and the gauge
    ``moe.exchange.bytes`` is what a rank sends a layer a step, forward and
    backward. The router, the shared expert and the parameters stay outside,
    data-parallel under the partitioner like the rest of the block.

    **Dropless over the experts held.** The (token, choice) pairs are sorted
    by expert with the held experts first, their rows gathered
    (:func:`take_rows`), multiplied expert by expert in one grouped matmul
    over the sorted rows, and summed back into their tokens by a second
    gather (:func:`sum_rows`: the sort is a permutation, so every token reads
    its ``k`` rows through the inverse and adds them in float32; each
    gather's backward pass is the other, so no row is scatter-added in
    either direction; ``moe.rows.calls`` counts the calls, by the side of
    the movement and whether XLA's gather or the row kernel fetches a row
    there: :func:`rows_path`;
    ``moe.gmm.calls`` the grouped-matmul kernels traced, by kernel, tile and
    whether the tile divides its product: :func:`gmm_tile`). The row buffer is
    static: twice what a uniform router would send (``_BUFFER_SHARE``), and
    where a step's routing sends more (``lax.cond`` on the count, a rank's
    own) a second buffer of every pair, ``tokens * k`` rows, takes the step
    (with an exchange, where a rank's pairs are every rank's: the usual
    buffer again, window after window of the sorted rows, :func:`_in_passes`);
    where twice a uniform router's rows are every pair (one choice a token,
    half of the experts held) there is the one buffer. Either is recomputed
    in the backward pass, so that no buffer is kept. No row is dropped
    whatever the routing.

    Returns ``(y, aux)``: ``aux["rows_held"]`` the rows routed to a rank's
    experts (the mean over the ranks), ``aux["load_max_over_mean"]`` the
    fullest held expert's rows over the mean of its rank (the worst rank's),
    with an exchange also ``aux["rank_load_max_over_mean"]``, the fullest
    rank's rows over the mean rank's (the step waits for that rank), and,
    where the router keeps a state, ``aux["router_state"]`` ``[B, S,
    hidden]``: what the next layer's router is to be given as
    ``router_state``."""

    num_experts: int
    mlp_dim: int
    top_k: int = 0
    held: Tuple[int, int] = (0, 0)
    routed_scale: float = 1.0
    shared_dim: int = 0
    dtype: Dtype = jnp.bfloat16
    implementation: str = "auto"
    router: Optional[nn.Module] = None
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, router_state=None
                 ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        from .transformer import GatedMlp

        b, s, f = x.shape
        e = self.num_experts
        first, count = self.held if self.held[1] else (0, e)
        if not 0 < count <= e:
            raise ValueError(f"held {self.held}, experts {e}")
        # Initialisation traces one row and is not partitioned.
        mesh = None if self.is_initializing() else self.mesh
        axes = batch_axes_of(mesh)
        ranks = mesh.shape.get("expert", 1) if axes else 1
        if count % ranks:
            raise ValueError(f"{count} held experts over {ranks} ranks")
        m = x.reshape(b * s, f).astype(self.dtype)
        if (self.router is None) == (self.top_k == 0):
            raise ValueError("an expert layer is given a router or top_k, "
                             "and not both")
        if self.top_k > e:
            raise ValueError(f"top_k={self.top_k} > num_experts={e}")
        router = self.router if self.router is not None else \
            SigmoidTopKRouter(e, self.top_k, self.routed_scale, name="router")

        with jax.named_scope("moe_router"):
            chosen, weight, state = router(
                m, None if router_state is None
                else router_state.reshape(b * s, -1))
        # Rows go to the buffer and back through the sort's permutation and
        # its inverse, each side fetched by the row kernel or by XLA's
        # gathers (a rank sees its own tokens and, with an exchange, its
        # ranks'); counted here, once a layer call and side, as the path is
        # static. An initialisation keeps only the parameters: no kernel is
        # traced and lowered for rows that are never moved.
        path = _GATHERS if self.is_initializing() else rows_path(
            self.implementation, ranks * b * s // math.prod(
                mesh.shape[a] for a in axes), f, self.dtype,
            chosen.shape[-1], count // ranks, e)
        registry = get_tracer().registry
        calls = registry.counter(
            "moe.rows.calls",
            "expert-layer calls traced, by the side of their rows' movement "
            "and the way it is fetched")
        for side, way in zip(("buffer", "tokens"), path):
            calls.inc(side=side,
                      path="gather" if way == "gather" else "kernel")
        if ranks > 1:
            registry.counter(
                "moe.exchange.calls",
                "expert-layer calls traced that exchange tokens between "
                "expert-parallel ranks, by the collective and the ranks",
            ).inc(path="all_gather", ranks=str(ranks))
            # Forward a rank sends its tokens in the layer's dtype and the
            # other ranks' parts in float32; backward the transposes.
            registry.gauge(
                "moe.exchange.bytes",
                "bytes a rank sends in an expert layer's exchange, a layer "
                "a step, forward and backward",
            ).set(2 * (ranks - 1) * f * (jnp.dtype(self.dtype).itemsize + 4)
                  * (b * s // math.prod(mesh.shape[a] for a in axes)))

        w_in = ExpertStack(count, f, 2 * self.mlp_dim, self.dtype,
                           name="experts_in")()
        w_out = ExpertStack(count, self.mlp_dim, f, self.dtype,
                            name="experts_out")()
        rows = rows_spec(axes, 2)
        stack = P("expert") if ranks > 1 else P()
        y, sizes = shard_rows(
            functools.partial(_rank_part, num_experts=e, first=first,
                              ranks=ranks,
                              implementation=self.implementation, path=path),
            mesh, "gmm", (rows, rows, rows, stack, stack),
            (rows, rows))(m, chosen, weight, w_in, w_out)
        if self.shared_dim:
            with jax.named_scope("moe_shared"):
                y = y + GatedMlp(self.shared_dim, self.dtype,
                                 name="shared")(m)
        load = sizes.astype(jnp.float32)            # [ranks of the mesh, held]
        held = jnp.sum(load, axis=1)
        over_mean = lambda rows: jnp.max(rows, axis=-1) \
            / jnp.maximum(jnp.mean(rows, axis=-1), 1e-9)
        aux = {"rows_held": jnp.mean(held),
               "load_max_over_mean": jnp.max(over_mean(load))}
        if ranks > 1:
            aux["rank_load_max_over_mean"] = over_mean(held)
        if state is not None:
            aux["router_state"] = state.reshape(b, s, -1)
        return y.reshape(b, s, f), aux
