"""Shared transformer building blocks (BERT encoder, NMT encoder-decoder).

Replaces the attention/FFN layers inside the reference's TF BERT scripts and
Sockeye's MXNet transformer (SURVEY.md §3.1) with one Flax implementation.

TPU-first choices:
- attention goes through ``ops.fused_attention`` (Pallas flash kernel on
  TPU; jnp reference elsewhere) — no [S,S] score tensor in HBM;
- bfloat16 activations, float32 params and LayerNorm statistics;
- hidden/mlp dims are multiples of 128 in the shipped presets (MXU tiling);
- tensor-parallel readiness: QKV/MLP kernels carry ``param_rules`` entries
  sharding their output dim over the mesh 'model' axis (pjit inserts the
  collectives when the axis is >1; with model=1 they replicate — pure DP).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..obs.trace import get_tracer
from ..ops import fused_attention
from ..ops.rope import kernel_engages, rotate_to_heads
from ..ops.sparse_index import index_loss, select_top_k

Dtype = Any

# Param-path rules for the 'model' mesh axis (see sharding.param_sharding_tree):
# attention/MLP input projections shard their output features; output
# projections shard their input features — the Megatron column/row split.
TRANSFORMER_PARAM_RULES = (
    (r"(query|key|value)/kernel", P(None, "model")),
    (r"attn_out/kernel", P("model", None)),
    (r"mlp_in/kernel", P(None, "model")),
    (r"mlp_out/kernel", P("model", None)),
)


class RMSNorm(nn.Module):
    """``x / rms(x) * scale``: statistics in float32, no mean, no bias."""

    epsilon: float = 1e-6
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        return rms_norm(x, scale, self.epsilon, self.dtype)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, epsilon: float,
             dtype: Dtype) -> jnp.ndarray:
    """:class:`RMSNorm`'s arithmetic over the last dimension."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + epsilon) * scale).astype(dtype)


@dataclasses.dataclass(frozen=True)
class Rope:
    """Rotary positions of one attention layer. The first ``rotary_dim``
    dimensions of a head turn (0: all of them), in the two-halves layout
    (dimension ``i`` pairs with ``i + rotary_dim / 2``). ``yarn_factor`` > 0
    takes YaRN's frequencies (Peng et al. 2023): the slow ones divided by the
    factor, the fast ones kept, a linear ramp between the dimensions that
    turn ``beta_fast`` and ``beta_slow`` times over ``original_len``; cos and
    sin are multiplied by ``attention_factor``. ``sections`` (Qwen2-VL's
    multi-section positions, chunked) splits the frequency pairs among as
    many position streams: the first ``sections[0]`` pairs turn by stream
    0, the next ``sections[1]`` by stream 1, and so on; ``tables`` then
    takes ``[len(sections), seq_len]`` position ids, and equal streams give
    the tables of a ``Rope`` without sections, bit for bit."""

    theta: float = 10000.0
    rotary_dim: int = 0
    yarn_factor: float = 0.0
    original_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0
    sections: Tuple[int, ...] = ()

    def inv_freq(self, head_dim: int) -> np.ndarray:
        dim = self.rotary_dim or head_dim
        pos_freqs = self.theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                   / dim)
        if not self.yarn_factor:
            return 1.0 / pos_freqs

        def turns_dim(turns):  # the dimension that turns so often
            return dim * math.log(self.original_len / (turns * 2 * math.pi)) \
                / (2 * math.log(self.theta))

        low = max(math.floor(turns_dim(self.beta_fast)), 0)
        high = min(math.ceil(turns_dim(self.beta_slow)), dim - 1)
        ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                       / max(high - low, 1e-3), 0.0, 1.0)
        return (1.0 / (self.yarn_factor * pos_freqs)) * ramp \
            + (1.0 / pos_freqs) * (1.0 - ramp)

    def tables(self, seq_len: int, head_dim: int, positions=None):
        """``(cos, sin)``, each float32 ``[seq_len, rotary_dim / 2]``, for
        the positions ``0 .. seq_len - 1`` or for the ``seq_len`` position
        ids listed (a block-diffusion row's repeat), or for a stream of
        ``seq_len`` ids a section (``[len(sections), seq_len]``)."""
        positions = np.arange(seq_len) if positions is None \
            else np.asarray(positions)
        inv_freq = self.inv_freq(head_dim)
        if positions.ndim == 2:
            if len(positions) != len(self.sections) \
                    or sum(self.sections) != inv_freq.size:
                raise ValueError(
                    f"{len(positions)} position streams for sections "
                    f"{self.sections} of {inv_freq.size} frequency pairs")
            stream = np.repeat(np.arange(len(self.sections)), self.sections)
            positions = positions[stream].T  # [seq_len, pairs]
        else:
            positions = positions[:, None]
        angles = positions.astype(np.float64) * inv_freq[None, :]
        return tuple((f(angles) * self.attention_factor).astype(np.float32)
                     for f in (np.cos, np.sin))


def apply_rope(x: jnp.ndarray, rope: Rope, positions=None) -> jnp.ndarray:
    """Turn ``x [B, S, H, D]`` by its positions (``0 .. S - 1`` unless
    ``positions`` lists ``S`` ids, known while tracing): the plain
    form, the two turning halves computed apart and joined. At a 128-lane
    head XLA does each slice and the join through HBM in float32 (30 ms of
    the Laguna cell's 281 ms step, and 2.4 % more written as one multiply-add
    over the whole head: PERF.md, PR 26), so there ``rope_to_heads`` takes
    the kernel of ``ops/rope.py``; this stays for the heads it cannot tile,
    and is what the kernel is tested against."""
    head_dim = x.shape[-1]
    rot = rope.rotary_dim or head_dim
    cos, sin = (jnp.asarray(t)[None, :, None, :]
                for t in rope.tables(x.shape[1], head_dim, positions))
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :rot // 2], x32[..., rot // 2:rot]
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    if rot < head_dim:
        turned.append(x32[..., rot:])
    return jnp.concatenate(turned, axis=-1).astype(x.dtype)


def rope_to_heads(x: jnp.ndarray, rope: Rope,
                  implementation: str = "auto", mesh=None,
                  positions=None, norm=None) -> jnp.ndarray:
    """``x [B, S, H, D]`` turned by its positions, as ``[B, H, S, D]``: the
    kernel where ``ops/rope.py:kernel_engages`` says so (a head of whole lane
    tiles on a TPU), else :func:`apply_rope` and the transpose. Which one is
    static, so it is counted when the call is traced: ``attention.rope.calls``
    labelled ``path=kernel|xla`` (docs/OBSERVABILITY.md). ``mesh`` is the
    step's, for the kernel (``ops/rope.py:rotate_to_heads``). ``positions``
    lists the rows' position ids where they are not ``0 .. S - 1``: the
    kernel takes its tables from the host, so only the tables change.
    ``norm`` (a block with ``qk_norm``): ``(scale, epsilon)`` of an
    :class:`RMSNorm` over each head before it turns: inside the kernel where
    that runs, else :func:`rms_norm` before :func:`apply_rope`."""
    b, seq_len, h, head_dim = x.shape
    use_kernel, interpret = kernel_engages(implementation, seq_len, head_dim)
    get_tracer().registry.counter(
        "attention.rope.calls",
        "rotary-position calls traced, by the path they took",
    ).inc(path="kernel" if use_kernel else "xla")
    if use_kernel:
        # The kernel reads the projection's output as it lies: this undoes
        # the caller's split of the last dimension, and XLA drops both.
        return rotate_to_heads(x.reshape(b, seq_len, h * head_dim),
                               *rope.tables(seq_len, head_dim, positions),
                               head_dim, interpret=interpret, mesh=mesh,
                               norm=norm)
    if norm is not None:
        x = rms_norm(x, *norm, x.dtype)
    return apply_rope(x, rope, positions).transpose(0, 2, 1, 3)


@dataclasses.dataclass(frozen=True)
class BlockStyle:
    """What a block of a current open model has that the 2018 block
    (LayerNorm, biases, as many K/V heads as query heads, learned positions
    outside the block, GELU MLP) has not. A :class:`TransformerLayer` with
    ``style=None`` is the 2018 block, unchanged.

    ``mlp``: ``"swiglu"`` (``(silu(x W1) * x W3) W2``, width ``mlp_dim``) or
    ``"experts"`` (``models/moe.py:HeldExpertsMlp`` built from ``experts``, a
    tuple of its keyword pairs; ``router`` empty leaves the layer the router
    of its own ``top_k``, else it is the keyword pairs of a router of
    ``models/moe.py``: ``("kind", "softmax_top_k")`` first makes them a
    ``SoftmaxTopKRouter``'s, otherwise they are a ``MlpStateRouter``'s, which
    chooses one expert a token and hands its state to the next block's
    router).

    ``latent_mix`` makes the attention Zyphra's CCA (arXiv 2510.04476): q and
    k, projected *down* into a latent of ``heads * head_dim``, pass two causal
    convolutions along the sequence, of ``latent_mix[0]`` taps a channel and
    of ``latent_mix[1]`` taps with one ``head_dim``-square matrix a head; the
    mean of a query head and its K/V head, from before the convolutions, is
    added back; the second half of the K/V heads' values are the previous
    token's; q and k are scaled to the norm ``sqrt(head_dim)``, k times a
    learned temperature a K/V head. Rotary positions, the softmax and the
    output projection follow as in any styled block.

    ``residual_scale``: a sublayer's result ``f`` joins the stream ``h`` as
    ``(h + b_r) * s_r + (f + b_o) * s_o``, four learned vectors a sublayer
    (biases 0, scales 1 from the seed), not ``h + f``. ``from_embedding``
    says the block's input is the embedding itself: its attention sublayer
    then has no ``s_r``, ``b_r``.

    ``mixer``: what mixes tokens in the block's first sublayer.
    ``"attention"`` is the attention above; ``"mamba2"`` a state-space mixer
    (``models/ssm.py:Mamba2Mixer`` built from ``ssm``, a tuple of its keyword
    pairs), which reads none of the attention's fields. Either is the module
    ``self_attn`` under the norm ``self_attn_norm``: the name says where the
    sublayer stands, and what is sorted by it (a trace's sections, sharding
    rules) finds the mixer there.

    ``residual_multiplier`` ``m`` other than 1: a sublayer's result joins
    the stream as ``h + m * f`` (Granite's ``residual_multiplier``; one
    constant for the model, where ``residual_scale`` is learned vectors).
    ``attn_scale`` other than 0 is what the scores ``q . k`` are multiplied
    by in place of ``1 / sqrt(head_dim)`` (Granite's
    ``attention_multiplier``). ``rope=None`` is attention with no positional
    signal at all.

    ``remat``: the block is recomputed in the backward pass
    (``flax.linen.remat`` round the layer, ``models/lm.py``), so that one
    block's intermediates are alive at a time. Kept from the forward pass
    are its input and, where its attention takes the flash kernels, the
    forward kernel's output and row statistics (``2 B S H D + 4 B H S``
    bytes in bfloat16), which the recomputation reads in place of running
    the kernel a second time; q, k and v are computed again.

    ``qk_norm``: an RMSNorm over each head's channels on q and on k, a
    learned scale of ``head_dim`` each (``query_norm``, ``key_norm``), before
    the rotary turn (Qwen3's).

    ``indexer`` makes the attention learned sparse attention
    (DeepSeek-Sparse-Attention's indexer over these grouped heads): keyword
    pairs ``heads``, ``head_dim``, ``topk``. From the block's normed input
    under ``stop_gradient`` come ``heads`` index queries of ``head_dim``
    (``index_query``), one index key (``index_key``, a LayerNorm on it:
    ``index_key_norm``) and ``heads`` float32 weights a token
    (``index_weight``); queries and key turn by the rope's theta over their
    whole head, by position stream 0. A row keeps the causal keys whose
    index score is among its ``topk`` best (``ops/sparse_index.py``), one
    selection for every head; the flash kernels take it as their mask; the
    selection passes no gradient. The block then returns ``(x, aux)`` with
    ``aux["indexer_kl"]``, the rows' mean ``KL(P || softmax I)`` over the
    kept keys (``P`` the attention's own distribution, averaged over the
    heads, a constant), which reaches the indexer's parameters alone, and
    what the selection kept (``selected_kept_share``, ``selected_ties``).
    With a rope of ``sections`` the block's rows are given one position
    stream a section (a text row's streams are equal)."""

    num_kv_heads: int = 0          # 0: as many as query heads
    head_dim: int = 0              # 0: hidden size / heads
    rope: Optional[Rope] = None
    window: int = 0                # > 0: row i sees i - window < j <= i
    out_gate: bool = False         # sigmoid gate a head, from the normed input
    rms_eps: float = 1e-6
    mlp: str = "swiglu"
    experts: Tuple[Tuple[str, Any], ...] = ()
    router: Tuple[Tuple[str, Any], ...] = ()
    latent_mix: Tuple[int, ...] = ()   # (): q, k, v straight from x
    residual_scale: bool = False
    from_embedding: bool = False
    mixer: str = "attention"
    ssm: Tuple[Tuple[str, Any], ...] = ()
    residual_multiplier: float = 1.0
    attn_scale: float = 0.0        # 0: 1 / sqrt(head_dim)
    # Asked for by a preset's model kwargs (granite4_h_micro_lm,
    # sdar_30b_a3b_lm: remat_blocks); such a block keeps its input and its
    # flash forward kernel's output and row statistics, 2 B S H D + 4 B H S
    # bytes an attention block.
    remat: bool = False
    qk_norm: bool = False
    indexer: Tuple[Tuple[str, Any], ...] = ()


class Leaf(nn.Module):
    """One float32 parameter under a module name of its own, called ``kind``
    (``kernel``, ``scale`` or ``bias``: what initialisers, weight decay's
    mask and seeded weights go by)."""

    kind: str
    shape: Tuple[int, ...]

    @nn.compact
    def __call__(self):
        init = {"kernel": nn.initializers.xavier_uniform(),
                "scale": nn.initializers.ones,
                "bias": nn.initializers.zeros}[self.kind]
        return self.param(self.kind, init, self.shape, jnp.float32)


class ShiftScale(nn.Module):
    """``(x + bias) * scale`` in float32, a learned vector each."""

    @nn.compact
    def __call__(self, x):
        shape = (x.shape[-1],)
        bias = self.param("bias", nn.initializers.zeros, shape, jnp.float32)
        scale = self.param("scale", nn.initializers.ones, shape, jnp.float32)
        return (x.astype(jnp.float32) + bias) * scale


def shift_later(x: jnp.ndarray, n: int = 1) -> jnp.ndarray:
    """``x [B, S, ...]`` moved ``n`` positions later along the sequence,
    zeros before its first position: ``y[:, t] = x[:, t - n]``."""
    if n == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (n, 0)
    return jnp.pad(x[:, :x.shape[1] - n], pad)


class QuantDense(nn.Module):
    """Weight-only int8 Dense: ``y = (x @ q) * scale + bias``.

    Drop-in replacement for the decode-path ``nn.Dense`` layers when the
    serve loader quantizes a checkpoint (serve/quant.py): ``kernel`` is the
    int8 code tensor [in, out], ``scale`` the per-output-channel float32
    dequant factor, ``bias`` unchanged float32. The dequant multiplies
    AFTER the matmul — per-out-channel scales factor out of the contraction
    — so the kernel stays int8 in HBM and is only widened to the activation
    dtype inside the op (the LLM.int8/AWQ weight-only shape). Params are
    produced by ``quantize_variables``, never trained, hence zeros init.
    """

    features: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.zeros,
                            (x.shape[-1], self.features), jnp.int8)
        scale = self.param("scale", nn.initializers.ones,
                           (self.features,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.features,), jnp.float32)
        y = jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype))
        return y * scale.astype(self.dtype) + bias.astype(self.dtype)


class QuantEmbed(nn.Module):
    """Weight-only int8 embedding table with tied-output ``attend``.

    Mirrors the ``nn.Embed`` surface the NMT embeddings use (lookup +
    ``attend`` for the tied logits projection). ``scale`` is per-hidden-
    channel [H], which serves both directions: lookup dequantizes the
    gathered rows, attend folds the scale into the query so the [V, H]
    table is never materialized in float.
    """

    num_embeddings: int
    features: int

    def setup(self):
        self.embedding = self.param(
            "embedding", nn.initializers.zeros,
            (self.num_embeddings, self.features), jnp.int8)
        self.scale = self.param("scale", nn.initializers.ones,
                                (self.features,), jnp.float32)

    def __call__(self, ids):
        return jnp.take(self.embedding, ids, axis=0) \
            .astype(jnp.float32) * self.scale

    def attend(self, query):
        # query @ (q * scale).T == (query * scale) @ q.T
        return jnp.dot(query * self.scale.astype(query.dtype),
                       self.embedding.astype(query.dtype).T)


class MultiHeadAttention(nn.Module):
    """Self- or cross-attention over [B, S, H*D] activations.

    ``decode=True`` is the autoregressive single-position mode: ``x`` is
    [B, 1, F], and this step's K/V are appended into a ``cache`` collection
    (``cached_key``/``cached_value`` [B, H, max_decode_len, D] plus a
    ``cache_index`` scalar) so attention touches only projected-once keys —
    the KV-cache that turns O(T²) decode recompute into O(T). Create the
    cache by running ``model.init`` on the decode path and keep the
    returned "cache" collection as scan carry (flax's standard pattern).

    ``decode_pos`` (with ``decode=True``) replaces the shared scalar
    ``cache_index`` with an explicit per-row position vector [B]: row b's
    K/V land at ``decode_pos[b]`` and row b attends to positions
    ``<= decode_pos[b]``. The caller owns advancing the positions. This is
    the continuous-batching mode (serve/engine.py): every cache row can sit
    at a different depth, so a finished request's rows are recycled —
    restart a row at position 0 and the step bias hides whatever a prior
    occupant left above it — without stalling in-flight neighbours.

    ``block_tables`` (with ``decode=True`` and ``decode_pos``) switches the
    cache from one [B, H, max_decode_len, D] row per batch entry to a
    shared **block pool** [kv_num_blocks, H, kv_block_size, D] — the
    vLLM/PagedAttention layout. ``block_tables`` is [B, max_blocks] int32:
    row b's logical position p lives in pool block
    ``block_tables[b, p // kv_block_size]`` at offset ``p % kv_block_size``.
    The caller (a host-side block allocator) owns the tables; block 0 is
    conventionally a null sentinel that unbound table entries point at, so
    writes from idle rows land there harmlessly and the step bias masks
    whatever they left. With ``max_blocks * kv_block_size ==
    max_decode_len`` the gathered K/V span equals the dense row, so the
    attention output is bit-identical to the ``decode_pos`` path.

    ``kv_quant="int8"`` (paged mode only) stores the block pool as int8
    codes plus per-block/per-head float32 absmax scales
    (``cached_key_scale`` / ``cached_value_scale`` [kv_num_blocks, H]) —
    the KIVI-style layout that quarters KV bytes. Writes requantize the
    touched block window (gather → dequant → insert → rescale → scatter);
    the attend gather dequantizes through the block table. Divergence
    from the fp pool is bounded by the per-block rounding step, the same
    contract ``--quantize`` carries for weights.
    """

    num_heads: int
    dtype: Dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    attention_impl: str = "auto"
    quantized: bool = False
    kv_quant: str = ""
    # A current block's attention reads its shape from the block's style:
    # fewer K/V heads than query heads, a head size of its own, no biases,
    # rotary positions, a sliding window, a sigmoid gate a head on the
    # output. Training and evaluation only: the decode paths below keep the
    # 2018 layout.
    style: Optional[BlockStyle] = None
    # The mesh the step is compiled for: its Pallas kernels (flash, rope) go
    # under a shard_map over its batch axes (parallel/kernels.py). None, or
    # a mesh of one device: nothing is wrapped; nor while the parameters are
    # initialised, which traces one row.
    mesh: Any = None

    def _kernel_mesh(self):
        return None if self.is_initializing() else self.mesh

    def core_attention(self, q, k, v, bias, causal, layout=None,
                       selected=None):
        """The [B,H,S,D] attention op. Subclasses swap this for a
        distributed strategy (SeqParallelAttention) while inheriting the
        projections/KV-cache/dropout plumbing unchanged. A ``layout``
        (``ops/attention.py:BlockDiffusion``) is the call's whole mask, in
        place of ``causal`` and the style's window. ``selected`` (an
        indexer's packed selection) masks the causal call, which then
        returns the rows' statistics beside the output."""
        st = self.style or BlockStyle()
        if layout is not None:
            causal, window = False, 0
        else:
            window = st.window
        return fused_attention(q, k, v, bias=bias, causal=causal,
                               sm_scale=st.attn_scale or None,
                               implementation=self.attention_impl,
                               window=window, mesh=self._kernel_mesh(),
                               layout=layout, selected=selected)

    def _indexer(self, x, q, k, v):
        """Learned sparse attention over ``q``, ``k``, ``v`` ``[B, H, S,
        D]`` from the block's normed input ``x``: ``(out, aux)``
        (:class:`BlockStyle`'s ``indexer``)."""
        st = self.style
        ix = dict(st.indexer)
        heads, dim, topk = ix["heads"], ix["head_dim"], ix["topk"]
        b, s = x.shape[:2]
        x = jax.lax.stop_gradient(x)
        dense = lambda name, feats, dtype=self.dtype: nn.Dense(
            feats, dtype=dtype, param_dtype=jnp.float32, name=name,
            use_bias=False, kernel_init=nn.initializers.xavier_uniform())
        with jax.named_scope("indexer_proj"):
            qi = dense("index_query", heads * dim)(x).reshape(
                b, s, heads, dim)
            ki = nn.LayerNorm(epsilon=1e-6, dtype=self.dtype,
                              param_dtype=jnp.float32,
                              name="index_key_norm")(
                                  dense("index_key", dim)(x))
            # Float32 as a router's logits are: a flipped selection is as
            # discrete as a flipped expert.
            w = dense("index_weight", heads, jnp.float32)(
                x.astype(jnp.float32))
            turn = Rope(theta=st.rope.theta)
            qi, ki = (rope_to_heads(t, turn, self.attention_impl,
                                    self._kernel_mesh())
                      for t in (qi, ki[:, :, None, :]))
            ki = ki[:, 0]
        words, lse_i, kept = select_top_k(qi, ki, w, topk,
                                          self.attention_impl,
                                          mesh=self._kernel_mesh())
        out, lse = self.core_attention(q, k, v, None, True, selected=words)
        with jax.named_scope("indexer_loss"):
            kl = index_loss(qi, ki, w, q, k, lse, words, lse_i,
                            st.attn_scale or q.shape[-1] ** -0.5,
                            self.attention_impl, mesh=self._kernel_mesh())
        return out, {
            "indexer_kl": jnp.sum(kl) / (b * s),
            "selected_kept_share": jnp.sum(kept) / (b * s * (s + 1) / 2),
            "selected_ties": jnp.sum(jnp.maximum(kept - topk, 0.0))}

    @nn.compact
    def __call__(self, x, kv=None, bias=None, causal=False,
                 deterministic=True, decode=False,
                 max_decode_len: int = 0, decode_pos=None,
                 block_tables=None, kv_num_blocks: int = 0,
                 kv_block_size: int = 0, layout=None):
        self_attention = kv is None
        kv = x if kv is None else kv
        features = x.shape[-1]
        st = self.style or BlockStyle()
        if not st.head_dim and features % self.num_heads:
            raise ValueError(
                f"hidden size {features} not divisible by "
                f"{self.num_heads} heads")
        head_dim = st.head_dim or features // self.num_heads
        kv_heads = st.num_kv_heads or self.num_heads
        if self.style is not None and (
                decode or not self_attention or self.quantized):
            raise NotImplementedError(
                "rotary positions, a window, an output gate and grouped K/V "
                "heads are for self-attention in training and evaluation; "
                "the decode paths keep the 2018 layout")
        if self.quantized:
            dense = lambda name, feats=features: QuantDense(
                feats, dtype=self.dtype, name=name)
        else:
            dense = lambda name, feats=features: nn.Dense(
                feats, dtype=self.dtype, param_dtype=jnp.float32,
                name=name, use_bias=self.style is None,
                kernel_init=nn.initializers.xavier_uniform())

        def heads(t, n):  # [B,S,n*D] -> [B,S,n,D]
            return t.reshape(*t.shape[:2], n, head_dim)

        def latent_mix(q, k):
            """q ``[B,S,H*D]`` and k ``[B,S,KV*D]`` as projected, through the
            two convolutions, the q-k mean and the norm: ``[B,S,n,D]``
            each. Float32 but for the operands of the grouped product."""
            b, s = q.shape[:2]
            h, g = self.num_heads, self.num_heads // kv_heads
            taps, head_taps = st.latent_mix
            c = jnp.concatenate([q, k], axis=-1).astype(jnp.float32)
            w = Leaf("kernel", (taps, c.shape[-1]), name="conv_depth")()
            c = sum(w[j] * shift_later(c, taps - 1 - j) for j in range(taps))
            c = heads(c.astype(self.dtype), h + kv_heads)
            w = Leaf("kernel", (head_taps * head_dim, (h + kv_heads)
                                * head_dim), name="conv_heads")()
            w = w.astype(self.dtype).reshape(head_taps, head_dim,
                                             h + kv_heads, head_dim)
            c = sum(jnp.einsum("bsgi,igo->bsgo",
                               shift_later(c, head_taps - 1 - j), w[j],
                               preferred_element_type=jnp.float32)
                    for j in range(head_taps))
            mean_q = 0.5 * (
                q.astype(jnp.float32).reshape(b, s, kv_heads, g, head_dim)
                + k.astype(jnp.float32).reshape(b, s, kv_heads, 1, head_dim))
            q = c[:, :, :h] + mean_q.reshape(b, s, h, head_dim)
            k = c[:, :, h:] + jnp.mean(mean_q, axis=3)
            unit = lambda t: t * jax.lax.rsqrt(
                jnp.mean(jnp.square(t), axis=-1, keepdims=True))
            temperature = Leaf("scale", (kv_heads,), name="key_temp")()
            return unit(q).astype(self.dtype), \
                (unit(k) * temperature[:, None]).astype(self.dtype)

        if st.latent_mix:
            get_tracer().registry.counter(
                "attention.cca.calls",
                "attention calls traced that mix q and k in the latent",
            ).inc()
            q = dense("query", self.num_heads * head_dim)(x)
            k = dense("key", kv_heads * head_dim)(kv)
            # The first K/V heads see the token, the others the one before.
            prev = kv_heads // 2
            v = heads(jnp.concatenate([
                dense("value", (kv_heads - prev) * head_dim)(kv),
                shift_later(dense("value_prev", prev * head_dim)(kv))], -1),
                kv_heads)
            with jax.named_scope("cca_mix"):
                q, k = latent_mix(q, k)
        else:
            q = heads(dense("query", self.num_heads * head_dim)(x),
                      self.num_heads)
            k = heads(dense("key", kv_heads * head_dim)(kv), kv_heads)
            v = heads(dense("value", kv_heads * head_dim)(kv), kv_heads)
        if layout is not None and self.style is None:
            raise NotImplementedError(
                "a block-diffusion layout is for a styled block's "
                "self-attention in training and evaluation")
        norms = (None, None)
        if st.qk_norm:
            norms = tuple((Leaf("scale", (head_dim,), name=name)(),
                           st.rms_eps) for name in ("query_norm", "key_norm"))
            # With rotary positions the norm goes where the turn goes: into
            # the rotary kernel where that runs (``rope_to_heads``).
            fused = st.rope is not None and kernel_engages(
                self.attention_impl, q.shape[1], head_dim)[0]
            get_tracer().registry.counter(
                "attention.qk_norm.calls",
                "q/k norm pairs traced, by where the norm is computed",
            ).inc(path="fused" if fused else "xla")
            if st.rope is None:
                with jax.named_scope("qk_norm"):
                    q, k = (rms_norm(t, *norm, self.dtype)
                            for t, norm in zip((q, k), norms))
        if st.indexer and (layout is not None or st.rope is None
                           or st.window or bias is not None or not causal):
            raise NotImplementedError(
                "an indexer selects among the keys of causal self-attention "
                "with rotary positions: no layout, window or bias")
        if st.rope is not None:
            # Both copies of a block-diffusion row stand at the row's own
            # positions: 0 .. L - 1 twice.
            positions = None if layout is None \
                else np.tile(np.arange(layout.length), 2)
            if st.rope.sections:
                if layout is not None:
                    raise NotImplementedError(
                        "sectioned rotary positions under a layout")
                # A text row: every stream counts its tokens.
                positions = np.tile(np.arange(x.shape[1]),
                                    (len(st.rope.sections), 1))
            # In a block with ``qk_norm`` the norm and the turn are one
            # call, under both names: ``qk_norm/rope``.
            with jax.named_scope("qk_norm/rope" if st.qk_norm else "rope"):
                q, k = (rope_to_heads(t, st.rope, self.attention_impl,
                                      self._kernel_mesh(), positions, norm)
                        for t, norm in zip((q, k), norms))
            v = v.transpose(0, 2, 1, 3)
        else:
            q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B,H,S,D]
        if decode and self_attention and block_tables is not None:
            if kv_num_blocks <= 0 or kv_block_size <= 0:
                raise ValueError(
                    "paged decode needs kv_num_blocks and kv_block_size")
            if decode_pos is None:
                raise ValueError(
                    "paged decode is per-row — pass decode_pos")
            b = q.shape[0]
            pool_shape = (kv_num_blocks, self.num_heads, kv_block_size,
                          head_dim)
            if self.kv_quant and self.kv_quant != "int8":
                raise ValueError(
                    f"unsupported kv_quant {self.kv_quant!r} "
                    "(supported: int8)")
            is_initialized = self.has_variable("cache", "cached_key")
            if self.kv_quant:
                # Int8 pool + per-block/per-head absmax scale sidecars.
                # The scale leaves sit alphabetically next to their code
                # pools in the cache tree, so everything that walks pool
                # leaves (COW forks, handoff) sees code → scale pairs.
                ck = self.variable("cache", "cached_key",
                                   lambda: jnp.zeros(pool_shape, jnp.int8))
                cks = self.variable(
                    "cache", "cached_key_scale",
                    lambda: jnp.ones((kv_num_blocks, self.num_heads),
                                     jnp.float32))
                cv = self.variable("cache", "cached_value",
                                   lambda: jnp.zeros(pool_shape, jnp.int8))
                cvs = self.variable(
                    "cache", "cached_value_scale",
                    lambda: jnp.ones((kv_num_blocks, self.num_heads),
                                     jnp.float32))
            else:
                ck = self.variable("cache", "cached_key",
                                   lambda: jnp.zeros(pool_shape, self.dtype))
                cv = self.variable("cache", "cached_value",
                                   lambda: jnp.zeros(pool_shape, self.dtype))
                cks = cvs = None
            max_blocks = block_tables.shape[1]
            span = max_blocks * kv_block_size
            s = q.shape[2]
            if is_initialized:
                rows = jnp.arange(b)
                if self.kv_quant:
                    # Read-modify-write requantization, one code path for
                    # s == 1 and the speculative-verify span: gather the
                    # touched window of blocks, dequantize, insert this
                    # step's K/V, re-scale per block/head (absmax / 127,
                    # the serve/quant.py grid), scatter codes + scales
                    # back. Positions past the bound span and windows
                    # landing on the null block are routed to the
                    # out-of-range index kv_num_blocks, which the scatter
                    # drops — the fp path's null-block masking, expressed
                    # as OOB-drop so clamped duplicates can't corrupt a
                    # row's real tail block.
                    T = (s + 2 * kv_block_size - 2) // kv_block_size
                    base = decode_pos // kv_block_size
                    tb_log = base[:, None] + jnp.arange(T)  # [B, T]
                    in_table = tb_log < max_blocks
                    phys = jnp.where(
                        in_table,
                        block_tables[rows[:, None],
                                     jnp.minimum(tb_log, max_blocks - 1)],
                        0)  # [B, T]
                    pos_mat = decode_pos[:, None] + jnp.arange(s)
                    woff = jnp.where(
                        pos_mat < span,
                        pos_mat - base[:, None] * kv_block_size,
                        T * kv_block_size)
                    wpos = base[:, None] * kv_block_size + \
                        jnp.arange(T * kv_block_size)
                    live = wpos < jnp.minimum(decode_pos + s,
                                              span)[:, None]
                    tgt = jnp.where(in_table & (phys > 0), phys,
                                    kv_num_blocks)

                    def requant_write(cvar, svar, new):
                        # new: [B, S, H, D] — this step's projections.
                        vals = cvar.value[phys].astype(jnp.float32) * \
                            svar.value[phys][..., None, None]
                        win = vals.transpose(0, 1, 3, 2, 4).reshape(
                            b, T * kv_block_size, self.num_heads,
                            head_dim)
                        win = win.at[rows[:, None], woff].set(
                            new.astype(jnp.float32))
                        # Zero everything above the row's live extent so
                        # recycled-block garbage can't inflate the absmax
                        # (the step bias hides it from attention either
                        # way; this keeps the quantization grid tight).
                        win = jnp.where(live[:, :, None, None], win, 0.0)
                        blocks = win.reshape(
                            b, T, kv_block_size, self.num_heads,
                            head_dim).transpose(0, 1, 3, 2, 4)
                        amax = jnp.max(jnp.abs(blocks), axis=(3, 4))
                        scale = jnp.where(amax > 0.0, amax / 127.0, 1.0)
                        codes = jnp.clip(
                            jnp.rint(blocks / scale[..., None, None]),
                            -127.0, 127.0).astype(jnp.int8)
                        cvar.value = cvar.value.at[tgt].set(codes)
                        svar.value = svar.value.at[tgt].set(
                            scale.astype(jnp.float32))

                    requant_write(ck, cks, k.transpose(0, 2, 1, 3))
                    requant_write(cv, cvs, v.transpose(0, 2, 1, 3))
                elif s == 1:
                    # Row b's single-position K/V land in its current block:
                    # pool[block_tables[b, pos // bs], :, pos % bs]. Rows
                    # whose table entry is unbound write into the null block
                    # 0 — masked below, never attended.
                    blk = block_tables[rows, decode_pos // kv_block_size]
                    off = decode_pos % kv_block_size
                    ck.value = ck.value.at[blk, :, off, :].set(
                        k[:, :, 0, :].astype(self.dtype))
                    cv.value = cv.value.at[blk, :, off, :].set(
                        v[:, :, 0, :].astype(self.dtype))
                else:
                    # Multi-position (speculative-verify) write: row b's s
                    # K/V vectors land at logical positions pos[b]..pos[b]+
                    # s-1. Positions past the bound span must NOT be routed
                    # through a clipped table index (that would corrupt the
                    # row's real last block) — they are redirected to the
                    # null block 0 explicitly.
                    pos_mat = decode_pos[:, None] + jnp.arange(s)  # [B, S]
                    valid = pos_mat < span
                    blk = jnp.where(
                        valid,
                        block_tables[rows[:, None],
                                     jnp.minimum(pos_mat // kv_block_size,
                                                 max_blocks - 1)],
                        0)
                    off = jnp.where(valid, pos_mat % kv_block_size, 0)
                    # advanced indices at axes 0/2 put [B, S] first:
                    # the update operand is k transposed to [B, S, H, D].
                    ck.value = ck.value.at[blk, :, off, :].set(
                        k.transpose(0, 2, 1, 3).astype(self.dtype))
                    cv.value = cv.value.at[blk, :, off, :].set(
                        v.transpose(0, 2, 1, 3).astype(self.dtype))
            # Gather each row's K/V span through its block table. The
            # gathered layout puts logical position p at index p, so with
            # span == max_decode_len this is bit-identical to the dense
            # per-row cache (masked positions contribute exactly 0).

            def gathered(c, sc=None):
                g = c[block_tables]  # [B, MB, H, bs, D]
                if sc is not None:
                    # Dequant-in-gather: int8 codes widen only here, the
                    # pool itself stays int8 in memory.
                    g = (g.astype(jnp.float32) *
                         sc[block_tables][..., None, None]) \
                        .astype(self.dtype)
                return g.transpose(0, 2, 1, 3, 4).reshape(
                    b, self.num_heads, span, head_dim)

            if s == 1:
                step_bias = jnp.where(
                    jnp.arange(span)[None, :] <= decode_pos[:, None],
                    0.0, -1e30)[:, None, None, :].astype(jnp.float32)
            else:
                # Query j (logical position pos+j) sees cache positions
                # <= pos+j: causal among the span's own freshly-written
                # positions (write happens before the gather above).
                pos_mat = decode_pos[:, None] + jnp.arange(s)
                step_bias = jnp.where(
                    jnp.arange(span)[None, None, :] <= pos_mat[:, :, None],
                    0.0, -1e30)[:, None, :, :].astype(jnp.float32)
            out = fused_attention(
                q,
                gathered(ck.value, None if cks is None else cks.value),
                gathered(cv.value, None if cvs is None else cvs.value),
                bias=step_bias, causal=False, implementation="reference")
        elif decode and self_attention:
            if max_decode_len <= 0:
                raise ValueError("decode=True needs max_decode_len")
            b = q.shape[0]
            shape = (b, self.num_heads, max_decode_len, head_dim)
            # Standard flax guard: during init (cache vars not yet created)
            # only allocate — running the update there would leave the
            # returned cache pre-advanced by one garbage position.
            is_initialized = self.has_variable("cache", "cached_key")
            ck = self.variable("cache", "cached_key",
                               lambda: jnp.zeros(shape, self.dtype))
            cv = self.variable("cache", "cached_value",
                               lambda: jnp.zeros(shape, self.dtype))
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            if is_initialized:
                if decode_pos is None:
                    ck.value = jax.lax.dynamic_update_slice(
                        ck.value, k.astype(self.dtype), (0, 0, idx, 0))
                    cv.value = jax.lax.dynamic_update_slice(
                        cv.value, v.astype(self.dtype), (0, 0, idx, 0))
                    ci.value = idx + 1
                elif k.shape[2] == 1:
                    # Per-row write: row b's single-position K/V land at
                    # decode_pos[b]. cache_index is left untouched — the
                    # caller (serve/engine.py) owns per-row positions.
                    rows = jnp.arange(b)
                    ck.value = ck.value.at[rows, :, decode_pos, :].set(
                        k[:, :, 0, :].astype(self.dtype))
                    cv.value = cv.value.at[rows, :, decode_pos, :].set(
                        v[:, :, 0, :].astype(self.dtype))
                else:
                    # Multi-position (speculative-verify) write: row b's s
                    # K/V vectors land at decode_pos[b]..decode_pos[b]+s-1.
                    # Out-of-range positions are dropped by the scatter.
                    rows = jnp.arange(b)
                    pos_mat = decode_pos[:, None] + \
                        jnp.arange(k.shape[2])  # [B, S]
                    ck.value = ck.value.at[rows[:, None], :, pos_mat, :].set(
                        k.transpose(0, 2, 1, 3).astype(self.dtype))
                    cv.value = cv.value.at[rows[:, None], :, pos_mat, :].set(
                        v.transpose(0, 2, 1, 3).astype(self.dtype))
            # Attend only to filled positions (<= the row's position). The
            # single-query step is tiny — the jnp reference path, not the
            # Pallas kernel, is the right tool.
            if decode_pos is None:
                step_bias = jnp.where(
                    jnp.arange(max_decode_len) <= idx, 0.0, -1e30
                )[None, None, None, :].astype(jnp.float32)
            elif q.shape[2] == 1:
                step_bias = jnp.where(
                    jnp.arange(max_decode_len)[None, :]
                    <= decode_pos[:, None], 0.0, -1e30
                )[:, None, None, :].astype(jnp.float32)
            else:
                # Span bias [B, 1, S, L]: query j attends to <= pos + j.
                pos_mat = decode_pos[:, None] + jnp.arange(q.shape[2])
                step_bias = jnp.where(
                    jnp.arange(max_decode_len)[None, None, :]
                    <= pos_mat[:, :, None], 0.0, -1e30
                )[:, None, :, :].astype(jnp.float32)
            out = fused_attention(q, ck.value, cv.value, bias=step_bias,
                                  causal=False, implementation="reference")
        elif layout is not None:
            out = self.core_attention(q, k, v, bias, causal, layout)
        elif st.indexer:
            out, index_aux = self._indexer(x, q, k, v)
        else:
            out = self.core_attention(q, k, v, bias, causal)
        b, h, s, d = out.shape
        out = out.transpose(0, 2, 1, 3)
        if st.out_gate:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(dense("gate", h)(x))[..., None]
        out = dense("attn_out")(out.reshape(b, s, h * d))
        if self.dropout_rate > 0:
            out = nn.Dropout(self.dropout_rate)(
                out, deterministic=deterministic)
        return (out, index_aux) if st.indexer else out


class Mlp(nn.Module):
    mlp_dim: int
    dtype: Dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    act: Callable = nn.gelu
    quantized: bool = False

    @nn.compact
    def __call__(self, x, deterministic=True):
        features = x.shape[-1]
        if self.quantized:
            dense = lambda feats, name: QuantDense(feats, dtype=self.dtype,
                                                   name=name)
        else:
            dense = lambda feats, name: nn.Dense(
                feats, dtype=self.dtype, param_dtype=jnp.float32, name=name,
                kernel_init=nn.initializers.xavier_uniform())
        y = dense(self.mlp_dim, "mlp_in")(x)
        y = self.act(y)
        y = dense(features, "mlp_out")(y)
        if self.dropout_rate > 0:
            y = nn.Dropout(self.dropout_rate)(y, deterministic=deterministic)
        return y


class GatedMlp(nn.Module):
    """``(silu(x W1) * x W3) W2``, no biases: ``W1`` and ``W3`` side by side
    in ``mlp_in``, one product for both."""

    mlp_dim: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = lambda feats, name: nn.Dense(
            feats, dtype=self.dtype, param_dtype=jnp.float32, name=name,
            use_bias=False, kernel_init=nn.initializers.xavier_uniform())
        gate, up = jnp.split(dense(2 * self.mlp_dim, "mlp_in")(x), 2, axis=-1)
        return dense(x.shape[-1], "mlp_out")(nn.silu(gate) * up)


class TransformerLayer(nn.Module):
    """One block: self-attn (+ optional cross-attn) + FFN.

    ``prenorm=False`` is the BERT/original-transformer post-LN layout;
    ``prenorm=True`` the more stable pre-LN used by the NMT preset.

    ``num_experts > 0`` swaps the dense FFN for a Mixture-of-Experts FFN
    (models/moe.py) and changes the return type to ``(x, moe_aux)`` where
    moe_aux is the MoE layer's aux-loss dict — callers that enable MoE own
    threading those losses into the objective.

    ``style`` makes it a current block (:class:`BlockStyle`): pre-norm with
    RMSNorm, no biases, no dropout, the style's attention and MLP. With
    ``style.mlp == "experts"`` it returns ``(x, aux)`` too, where ``aux``
    holds what the expert layer counted and, where its router keeps a state,
    ``aux["router_state"]``: what the next block is to be called with as
    ``router_state`` (the first block is called with none). A style with an
    ``indexer`` returns ``(x, aux)`` as well, the indexer's loss and what
    its selection kept in ``aux``.
    """

    num_heads: int
    mlp_dim: int
    dtype: Dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    prenorm: bool = False
    cross_attention: bool = False
    attention_impl: str = "auto"
    num_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 2
    quantized: bool = False
    kv_quant: str = ""
    style: Optional[BlockStyle] = None
    mesh: Any = None     # the step's, for the kernels and the expert layer

    def _styled(self, x, causal, router_state, layout):
        st = self.style
        norm = lambda name: RMSNorm(st.rms_eps, self.dtype, name=name)

        def join(sub, x, f, stream=True):
            if not st.residual_scale:
                if st.residual_multiplier == 1.0:
                    return x + f
                # In float32: in bfloat16 the constant itself would be
                # rounded (0.22 to 0.2197), every sublayer's share of the
                # stream with it, and the loss by a part in ten thousand.
                # Under the sublayer's scope, as the merge below is.
                with jax.named_scope(sub):
                    return (x.astype(jnp.float32) + st.residual_multiplier
                            * f.astype(jnp.float32)).astype(self.dtype)
            # Under the sublayer's own scope, so that a trace counts the
            # merge with the sublayer whose result it merges.
            with jax.named_scope(sub):
                h = ShiftScale(name=f"{sub}_stream")(x) if stream \
                    else x.astype(jnp.float32)
                return (h + ShiftScale(name=f"{sub}_result")(f)) \
                    .astype(self.dtype)

        if st.mixer == "mamba2":
            from .ssm import Mamba2Mixer

            mixed = Mamba2Mixer(dtype=self.dtype, rms_eps=st.rms_eps,
                                scan_impl=self.attention_impl,
                                mesh=self.mesh, name="self_attn",
                                **dict(st.ssm))(
                                    norm("self_attn_norm")(x))
        elif st.mixer == "attention":
            mixed = MultiHeadAttention(
                self.num_heads, self.dtype, 0.0, self.attention_impl,
                style=st, mesh=self.mesh, name="self_attn")(
                    norm("self_attn_norm")(x), causal=causal, layout=layout)
        else:
            raise ValueError(f"unknown BlockStyle.mixer {st.mixer!r}")
        index_aux = {}
        if st.mixer == "attention" and st.indexer:
            mixed, index_aux = mixed
        x = join("self_attn", x, mixed, stream=not st.from_embedding)
        y = norm("mlp_norm")(x)
        if st.mlp == "experts":
            from .moe import HeldExpertsMlp, MlpStateRouter, \
                SoftmaxTopKRouter

            experts, router = dict(st.experts), None
            if st.router:
                kw = dict(st.router)
                kind = {"mlp_state": MlpStateRouter,
                        "softmax_top_k": SoftmaxTopKRouter}[
                            kw.pop("kind", "mlp_state")]
                # Unparented: the layer it is given to adopts it, as
                # `router`.
                router = kind(experts["num_experts"], parent=None, **kw)
            out, aux = HeldExpertsMlp(
                mlp_dim=self.mlp_dim, dtype=self.dtype, name="mlp",
                router=router, mesh=self.mesh, **experts)(y, router_state)
            return join("mlp", x, out), {**aux, **index_aux}
        if st.mlp != "swiglu":
            raise ValueError(f"unknown BlockStyle.mlp {st.mlp!r}")
        x = join("mlp", x, GatedMlp(self.mlp_dim, self.dtype,
                                    name="mlp")(y))
        return (x, index_aux) if index_aux else x

    @nn.compact
    def __call__(self, x, enc=None, self_bias=None, cross_bias=None,
                 causal=False, deterministic=True, decode=False,
                 max_decode_len: int = 0, decode_pos=None,
                 block_tables=None, kv_num_blocks: int = 0,
                 kv_block_size: int = 0, router_state=None, layout=None):
        if layout is not None and (self.style is None
                                   or self.style.mixer != "attention"):
            raise NotImplementedError(
                "a block-diffusion layout is for a styled attention block")
        if self.style is not None:
            if decode or enc is not None or self_bias is not None:
                raise NotImplementedError(
                    "a styled block runs self-attention over whole "
                    "sequences: no decode step, encoder or bias yet")
            return self._styled(x, causal, router_state, layout)
        ln = lambda name: nn.LayerNorm(
            dtype=self.dtype, param_dtype=jnp.float32, name=name)
        attn = lambda name: MultiHeadAttention(
            self.num_heads, self.dtype, self.dropout_rate,
            self.attention_impl, quantized=self.quantized,
            kv_quant=self.kv_quant, mesh=self.mesh, name=name)

        def residual(x, sub, name):
            if self.prenorm:
                return x + sub(ln(f"{name}_norm")(x))
            return ln(f"{name}_norm")(x + sub(x))

        # decode mode: the self-attention runs single-position against its
        # KV cache (causal masking is implied by the cache index); cross
        # attention recomputes enc K/V per step — caching those too is a
        # constant-factor optimization, not an asymptotic one.
        x = residual(
            x, lambda y: attn("self_attn")(
                y, bias=self_bias, causal=causal and not decode,
                deterministic=deterministic, decode=decode,
                max_decode_len=max_decode_len, decode_pos=decode_pos,
                block_tables=block_tables, kv_num_blocks=kv_num_blocks,
                kv_block_size=kv_block_size),
            "self_attn")
        if self.cross_attention:
            if enc is None:
                raise ValueError("cross_attention layer needs encoder output")
            x = residual(
                x, lambda y: attn("cross_attn")(
                    y, kv=enc, bias=cross_bias,
                    deterministic=deterministic),
                "cross_attn")
        if self.num_experts > 0:
            from .moe import MoeMlp

            moe = MoeMlp(self.num_experts, self.mlp_dim,
                         self.moe_capacity_factor, self.moe_top_k,
                         self.dtype, name="moe_mlp")
            aux_box = {}

            def moe_sub(y):
                out, aux = moe(y, deterministic=deterministic)
                aux_box.update(aux)
                return out

            x = residual(x, moe_sub, "mlp")
            return x, aux_box
        x = residual(
            x, lambda y: Mlp(self.mlp_dim, self.dtype, self.dropout_rate,
                             quantized=self.quantized,
                             name="mlp")(y, deterministic=deterministic),
            "mlp")
        return x


class Embed(nn.Module):
    """Token + learned-position (+ optional segment) embeddings."""

    vocab_size: int
    hidden_size: int
    max_len: int
    num_segments: int = 0
    dtype: Dtype = jnp.bfloat16
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, ids, segment_ids=None, deterministic=True):
        emb = nn.Embed(self.vocab_size, self.hidden_size,
                       param_dtype=jnp.float32,
                       embedding_init=nn.initializers.normal(0.02),
                       name="token")
        x = emb(ids)
        pos = self.param(
            "position", nn.initializers.normal(0.02),
            (self.max_len, self.hidden_size), jnp.float32)
        x = x + pos[None, :ids.shape[1], :]
        if self.num_segments and segment_ids is not None:
            seg = nn.Embed(self.num_segments, self.hidden_size,
                           param_dtype=jnp.float32,
                           embedding_init=nn.initializers.normal(0.02),
                           name="segment")
            x = x + seg(segment_ids)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="norm")(x.astype(self.dtype))
        if self.dropout_rate > 0:
            x = nn.Dropout(self.dropout_rate)(x, deterministic=deterministic)
        return x, emb


def is_moe_layer(i: int, num_experts: int, moe_every: int) -> bool:
    """GShard's every-``moe_every``-th-layer convention, shared by every
    trunk that hosts MoE FFNs (bert, gpt) so the layer-selection rule
    can't silently diverge between them."""
    return num_experts > 0 and i % moe_every == moe_every - 1


class MoeAuxAccumulator:
    """Accumulate MoE aux losses across a trunk's MoE layers and return
    their per-layer mean — the one aggregation rule both bert and gpt
    use. Keys mirror MoeMlp's aux dict."""

    def __init__(self):
        self.totals = {"load_balance": jnp.zeros((), jnp.float32),
                       "router_z": jnp.zeros((), jnp.float32)}
        self.n = 0

    def add(self, aux) -> None:
        self.totals = {k: self.totals[k] + aux[k] for k in self.totals}
        self.n += 1

    def mean(self):
        return {k: v / max(self.n, 1) for k, v in self.totals.items()}


def padding_bias(mask: jnp.ndarray) -> jnp.ndarray:
    """[B, S] 1/0 attention mask → additive bias [B, 1, 1, S]."""
    return jnp.where(mask.astype(bool), 0.0, -1e30)[:, None, None, :] \
        .astype(jnp.float32)
