"""Decoder-only causal language models (GPT family).

Beyond the reference's scope (its newest workload era is BERT/NMT), but the
natural sixth family for a TPU framework: one trunk exercises every piece
already built — flash attention's causal path, KV-cached incremental
decode, tensor-parallel PARAM_RULES, gradient accumulation for big global
batches, and (via the shared TransformerLayer) MoE FFNs.

Weight tying: the output projection reuses the token embedding matrix
(standard for GPT-class models; halves the largest parameter and the
logits matmul reads the same HBM the embedding lookup warmed).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..obs.trace import get_tracer
from ..ops.attention import FLASH_LSE, FLASH_OUT, flash_kept_bytes
from ..ops.sparse_index import INDEX_GRADS, INDEX_SELECTED, INDEX_STATS
from . import register_model
from .moe import MOE_PARAM_RULES
from .transformer import (
    BlockStyle,
    MoeAuxAccumulator,
    RMSNorm,
    Rope,
    TRANSFORMER_PARAM_RULES,
    TransformerLayer,
    is_moe_layer,
)

Dtype = Any

# MoE rules are harmless when no MoE layers exist (regexes match nothing).
PARAM_RULES = TRANSFORMER_PARAM_RULES + MOE_PARAM_RULES


class TransformerCausalLm(nn.Module):
    """Embed → N pre-LN causal blocks → LN → tied logits.

    Training/eval run the full sequence with causal masking inside the
    attention kernel (flash path when available). Generation runs
    :meth:`decode_step` — single-position, against the blocks' KV caches
    (flax "cache" collection, NMT's decode_step contract: create the
    cache with ``model.init(..., method=TransformerCausalLm.decode_step)``
    and thread it through the loop).

    ``blocks`` makes it a current decoder instead: one ``(layer index, query
    heads, MLP width, BlockStyle)`` for each layer this chip holds, named
    ``layer_<index>`` (the other layers of the model lie on further chips as
    pipeline stages and are not here). Then there are no learned positions,
    no embedding norm and no dropout, the final norm is an RMSNorm, and
    ``num_layers``, ``num_heads`` and ``mlp_dim`` are not read. With an
    expert layer among them ``__call__`` returns ``(logits, aux)``, ``aux``
    what the expert layers counted; a router's state goes from each expert
    layer to the next one here. ``tie_embeddings=False`` gives the output
    head a matrix of its own (``lm_head/kernel``). A block's style also
    decides what mixes its tokens (attention or a state-space mixer), the
    constant its sublayers' results are multiplied by before they join the
    stream, and whether the block is recomputed in the backward pass
    (``BlockStyle.remat``: the layer under ``flax.linen.remat``, one block's
    intermediates alive at a time; an attention block on the flash kernels
    keeps the forward kernel's output and row statistics as well as its
    input, ``2 B S H D + 4 B H S`` bytes, and runs that kernel once).
    ``embedding_multiplier`` and
    ``logits_scaling`` are Granite's: the embedding times the one, the logits
    over the other, in float32.

    ``__call__``'s ``layout`` (``ops/attention.py:BlockDiffusion``, static)
    makes the call a block-diffusion training pass: ``tokens`` are ``[B, 2
    L]``, a noised copy of each row before the clean row; every attention
    block masks by the layout and turns both copies by the positions ``0 ..
    L - 1``; logits come back for the noised copy alone, ``[B, L, V]``.
    Without it the same model is called causally, a position a token.

    ``mesh`` is the mesh the step is compiled for (``CausalLmTask`` hands it
    on): on more than one device the blocks' Pallas kernels run under a
    ``shard_map`` over its batch axes and an expert layer exchanges tokens
    over its ``expert`` axis (``parallel/kernels.py``, ``models/moe.py``);
    the parameter tree is the same on any mesh."""

    vocab_size: int
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 1024
    dtype: Dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    attention_impl: str = "auto"
    # num_experts > 0 turns every moe_every-th block's FFN into a
    # Mixture-of-Experts FFN (GShard's every-other-layer convention);
    # __call__ then returns (logits, moe_aux) — bert.py's contract.
    num_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 2
    blocks: Tuple[Tuple[int, int, int, BlockStyle], ...] = ()
    tie_embeddings: bool = True
    mesh: Any = None
    embedding_multiplier: float = 1.0
    logits_scaling: float = 1.0

    def _is_moe(self, i: int) -> bool:
        return is_moe_layer(i, self.num_experts, self.moe_every)

    def setup(self):
        self.token = nn.Embed(self.vocab_size, self.hidden_size,
                              param_dtype=jnp.float32,
                              embedding_init=nn.initializers.normal(0.02))
        if not self.tie_embeddings:
            self.lm_head = nn.Dense(
                self.vocab_size, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32,
                kernel_init=nn.initializers.xavier_uniform())
        if self.blocks:
            # `causal` (the sixth argument, the module counted) is read by
            # Python: static under the recomputation.
            # What a recomputed block keeps beside its input: its flash
            # forward kernel's output and row statistics, so the backward
            # pass computes q, k and v again and not the kernel; of an
            # indexer, its selection and what its loss's pass left for the
            # backward pass (ops/sparse_index.py), for the same reason.
            recomputed = nn.remat(
                TransformerLayer, static_argnums=(5,),
                policy=jax.checkpoint_policies.save_only_these_names(
                    FLASH_OUT, FLASH_LSE, INDEX_SELECTED, INDEX_STATS,
                    INDEX_GRADS))
            if any(style.remat and style.mlp == "experts" and style.router
                   and dict(style.router).get("kind", "mlp_state")
                   == "mlp_state" for _, _, _, style in self.blocks):
                raise NotImplementedError(
                    "a recomputed block hands no router state on: "
                    "BlockStyle.remat is for blocks whose router keeps none")
            self.layers = [
                (recomputed if style.remat else TransformerLayer)(
                    heads, mlp_dim, dtype=self.dtype,
                    attention_impl=self.attention_impl, style=style,
                    mesh=self.mesh, name=f"layer_{index}")
                for index, heads, mlp_dim, style in self.blocks]
            self.final_norm = RMSNorm(self.blocks[-1][3].rms_eps, self.dtype)
            return
        self.position = self.param(
            "position", nn.initializers.normal(0.02),
            (self.max_len, self.hidden_size), jnp.float32)
        self.embed_norm = nn.LayerNorm(dtype=self.dtype,
                                       param_dtype=jnp.float32)
        self.dropout = nn.Dropout(self.dropout_rate)
        self.layers = [
            TransformerLayer(
                self.num_heads, self.mlp_dim, dtype=self.dtype,
                dropout_rate=self.dropout_rate, prenorm=True,
                attention_impl=self.attention_impl,
                num_experts=self.num_experts if self._is_moe(i) else 0,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_top_k=self.moe_top_k, mesh=self.mesh,
                name=f"layer_{i}")
            for i in range(self.num_layers)
        ]
        self.final_norm = nn.LayerNorm(dtype=self.dtype,
                                       param_dtype=jnp.float32)

    def _logits(self, x):
        # A scope of the program's own (docs/OBSERVABILITY.md): flax names
        # the tied head and the embedding lookup alike, after `token`.
        with jax.named_scope("lm_head"):
            logits = self.token.attend(x.astype(jnp.float32)) \
                if self.tie_embeddings else self.lm_head(x.astype(jnp.float32))
            return logits if self.logits_scaling == 1.0 \
                else logits / self.logits_scaling

    def _embed(self, tokens, pos_emb, train: bool):
        if self.blocks:
            x = self.token(tokens)
            if self.embedding_multiplier != 1.0:
                x = x * self.embedding_multiplier
            return x.astype(self.dtype)
        x = self.token(tokens) + pos_emb
        x = self.embed_norm(x.astype(self.dtype))
        if self.dropout_rate > 0:
            x = self.dropout(x, deterministic=not train)
        return x

    def _styled(self, tokens, layout=None):
        x = self._embed(tokens, None, False)
        # Carried from block to block beside x: the state of a router that
        # keeps one (None before the first such block, and for good where
        # no router does).
        state, received, counted = None, 0, []
        for (_, _, _, style), lyr in zip(self.blocks, self.layers):
            if style.remat:
                # Positional: x, enc, self_bias, cross_bias, causal.
                x = lyr(x, None, None, None, True, layout=layout)
                if style.mlp == "experts" or style.indexer:
                    x, aux = x
                    counted.append(aux)
            elif style.mlp == "experts":
                received += state is not None
                x, aux = lyr(x, causal=True, router_state=state,
                             layout=layout)
                state = aux.pop("router_state", None)
                counted.append(aux)
            elif style.indexer:
                x, aux = lyr(x, causal=True, layout=layout)
                counted.append(aux)
            else:
                x = lyr(x, causal=True, layout=layout)
        recomputed = sum(style.remat for _, _, _, style in self.blocks)
        if recomputed:
            registry = get_tracer().registry
            registry.counter(
                "model.blocks.recomputed",
                "blocks of the traced model that are recomputed in the "
                "backward pass, one at a time",
            ).inc(recomputed)
            kept = [flash_kept_bytes(
                x.shape[0], heads, x.shape[1],
                style.head_dim or self.hidden_size // heads, self.dtype,
                self.attention_impl)
                for _, heads, _, style in self.blocks
                if style.remat and style.mixer == "attention"]
            registry.counter(
                "model.blocks.kept_flash",
                "recomputed blocks of the traced model that keep their "
                "flash forward kernel's output and row statistics",
            ).inc(sum(map(bool, kept)))
            registry.gauge(
                "model.blocks.kept_bytes",
                "bytes the traced model's recomputed blocks keep from "
                "their flash forward kernels",
            ).set(sum(kept))
        if state is not None:
            get_tracer().registry.gauge(
                "moe.router.state_layers",
                "expert layers of the traced model whose router was given "
                "the state of the layer before",
            ).set(received)
        if layout is not None:
            # The noised copy alone is scored; the task's scope, as the
            # layout's other halves are (train/task.py).
            with jax.named_scope("bd_noise"):
                x = x[:, :layout.length]
        logits = self._logits(self.final_norm(x))
        if not counted:
            return logits
        has = lambda name: [a[name] for a in counted if name in a]
        worst = lambda name: jnp.max(jnp.stack(has(name)))
        mean = lambda name: sum(has(name)) / len(has(name))
        aux = {}
        if has("rows_held"):
            aux = {"rows_held": sum(has("rows_held")),
                   "load_max_over_mean": worst("load_max_over_mean")}
        if has("rank_load_max_over_mean"):
            aux["rank_load_max_over_mean"] = worst("rank_load_max_over_mean")
        if has("indexer_kl"):
            # The indexers' loss is the mean over layers and rows; so is
            # what they kept of the causal pairs; ties are counted.
            aux.update(indexer_kl=mean("indexer_kl"),
                       selected_kept_share=mean("selected_kept_share"),
                       selected_ties=sum(has("selected_ties")))
        return logits, aux

    def __call__(self, tokens, train: bool = False, layout=None):
        if self.blocks:
            return self._styled(tokens, layout)
        if layout is not None:
            raise NotImplementedError(
                "a block-diffusion layout is for a decoder of styled blocks")
        x = self._embed(tokens,
                        self.position[None, :tokens.shape[1], :], train)
        acc = MoeAuxAccumulator()
        for i, lyr in enumerate(self.layers):
            if self._is_moe(i):
                x, aux = lyr(x, causal=True, deterministic=not train)
                acc.add(aux)
            else:
                x = lyr(x, causal=True, deterministic=not train)
        logits = self._logits(self.final_norm(x))
        if self.num_experts > 0:
            return logits, acc.mean()
        return logits

    def decode_step(self, token, pos):
        """``token`` [B, 1] at position ``pos`` → logits [B, 1, V] for
        position ``pos + 1``, appending this position's K/V to the
        cache. MoE aux losses are a training concern; decode discards
        them."""
        if self.blocks:
            raise NotImplementedError(
                "a decoder of styled blocks has no decode step yet")
        pos_emb = jax.lax.dynamic_slice(
            self.position, (pos, 0), (1, self.hidden_size))[None, :, :]
        x = self._embed(token, pos_emb, train=False)
        for i, lyr in enumerate(self.layers):
            x = lyr(x, causal=True, deterministic=True, decode=True,
                    max_decode_len=self.max_len)
            if self._is_moe(i):
                x = x[0]
        x = self.final_norm(x)
        return self.token.attend(x.astype(jnp.float32))


class LongCausalLm(nn.Module):
    """Long-context causal LM: the GPT trunk with sequence-parallel
    attention over the 'seq' mesh axis (ring or Ulysses — both causal-
    exact; bert_long.SeqParallelAttention). Pre-LN blocks, tied logits,
    same CausalLmTask contract as TransformerCausalLm. Exact, so
    (data=k, seq=n) reproduces (data=k*n) numerics — test-pinned like
    bert_long."""

    vocab_size: int
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    mlp_dim: int = 3072
    max_len: int = 4096
    dtype: Dtype = jnp.bfloat16
    dropout_rate: float = 0.0
    seq_impl: str = "ring"
    mesh: Any = None
    batch_axes: Any = "data"

    def _constrain(self, x):
        from .bert_long import constrain_seq_sharding

        return constrain_seq_sharding(self, x, self.mesh, self.batch_axes)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        from .bert_long import SeqParallelAttention
        from .transformer import Mlp

        deterministic = not train
        token = nn.Embed(self.vocab_size, self.hidden_size,
                         param_dtype=jnp.float32,
                         embedding_init=nn.initializers.normal(0.02),
                         name="token")
        position = self.param(
            "position", nn.initializers.normal(0.02),
            (self.max_len, self.hidden_size), jnp.float32)
        x = token(tokens) + position[None, :tokens.shape[1], :]
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="embed_norm")(x.astype(self.dtype))
        if self.dropout_rate > 0:
            # Post-embedding dropout, matching TransformerCausalLm._embed
            # (same trunk contract → same regularization points).
            x = nn.Dropout(self.dropout_rate)(
                x, deterministic=deterministic)
        ln = lambda name: nn.LayerNorm(
            dtype=self.dtype, param_dtype=jnp.float32, name=name)
        for i in range(self.num_layers):
            x = self._constrain(x)
            attn = SeqParallelAttention(
                num_heads=self.num_heads, dtype=self.dtype,
                dropout_rate=self.dropout_rate, seq_impl=self.seq_impl,
                mesh=self.mesh, batch_axes=self.batch_axes,
                name=f"layer_{i}_self_attn")
            # Pre-LN residual blocks (the GPT layout).
            x = x + attn(ln(f"layer_{i}_self_attn_norm")(x), causal=True,
                         deterministic=deterministic)
            x = self._constrain(x)
            x = x + Mlp(self.mlp_dim, self.dtype, self.dropout_rate,
                        name=f"layer_{i}_mlp")(
                ln(f"layer_{i}_mlp_norm")(x), deterministic=deterministic)
        x = self._constrain(x)
        x = nn.LayerNorm(dtype=self.dtype, param_dtype=jnp.float32,
                         name="final_norm")(x)
        return token.attend(x.astype(jnp.float32))


@register_model("gpt_long")
def gpt_long(num_classes: int = 0, dtype=jnp.bfloat16, *,
             vocab_size: int = 32768, hidden_size: int = 768,
             num_layers: int = 12, num_heads: int = 12,
             mlp_dim: int = 3072, max_len: int = 4096,
             dropout_rate: float = 0.0, seq_impl: str = "ring",
             mesh=None, batch_axes="data"):
    return LongCausalLm(
        vocab_size=vocab_size, hidden_size=hidden_size,
        num_layers=num_layers, num_heads=num_heads, mlp_dim=mlp_dim,
        max_len=max_len, dtype=dtype, dropout_rate=dropout_rate,
        seq_impl=seq_impl, mesh=mesh, batch_axes=batch_axes)


@register_model("gpt_small")
def gpt_small(num_classes: int = 0, dtype=jnp.bfloat16, *,
              vocab_size: int = 32768, max_len: int = 1024, **kw):
    # GPT-2-small dims (124M with a 32k vocab); num_classes unused (the
    # "classes" are the vocab), accepted for registry-signature parity.
    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=768, num_layers=12,
        num_heads=12, mlp_dim=3072, max_len=max_len, dtype=dtype, **kw)


@register_model("gpt_tiny")
def gpt_tiny(num_classes: int = 0, dtype=jnp.float32, *,
             vocab_size: int = 512, max_len: int = 128, **kw):
    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=64, num_layers=2,
        num_heads=4, mlp_dim=128, max_len=max_len, dtype=dtype, **kw)


# Laguna-XS.2 as poolside published it (config.json, `model_type: laguna`,
# 33.4B-A3B): 40 layers of hidden size 2048, head size 128, 8 K/V heads under
# 48 query heads in the full-attention layers (every fourth, from layer 0)
# and 64 in the sliding ones (window 512); layer 0 a dense gated MLP of 8192,
# layers 1-39 256 routed experts of width 512, 8 a token, one shared expert
# of 512, routed scale 2.5; a sigmoid gate a head on the attention output;
# RMSNorm 1e-6; an untied head over 100,352 tokens. Full layers turn the
# first half of each head with YaRN's frequencies, sliding ones the whole
# head. benchmark/configs/laguna_xs2.json lists what the source leaves
# unsaid and how it was read.
_LAGUNA_XS2 = dict(
    hidden_size=2048, num_layers=40, period=4, head_dim=128, kv_heads=8,
    full_heads=48, sliding_heads=64, window=512, dense_width=8192,
    experts=256, top_k=8, expert_width=512, shared_width=512,
    routed_scale=2.5,
    full_rope=Rope(theta=500_000.0, rotary_dim=64, yarn_factor=64.0,
                   original_len=4096, beta_fast=64.0, beta_slow=1.0,
                   attention_factor=1.4158883083359672),
    sliding_rope=Rope(theta=10_000.0))
# The same block at sizes a CPU test holds: dense, sliding, full.
_LAGUNA_TINY = dict(
    hidden_size=64, num_layers=3, period=2, head_dim=16, kv_heads=2,
    full_heads=4, sliding_heads=6, window=8, dense_width=128,
    experts=16, top_k=4, expert_width=32, shared_width=32,
    routed_scale=2.5,
    full_rope=Rope(theta=500_000.0, rotary_dim=8, yarn_factor=4.0,
                   original_len=16, beta_fast=2.0, beta_slow=1.0,
                   attention_factor=1.1386294361119891),
    sliding_rope=Rope(theta=10_000.0))


def _grouped_matmul_for(attention_impl: str) -> str:
    """A model's one kernel switch: where the flash kernels are forced (a
    compile for a chip that is not attached), so is the grouped matmul;
    where they are ruled out, so is it."""
    return {"auto": "auto", "pallas": "megablox"}.get(attention_impl,
                                                      "ragged_dot")


def _laguna(sizes, dtype, vocab_size, layers_held, experts_held,
            attention_impl, mesh=None):
    """The ``laguna`` decoder at ``sizes``, or one chip's share of it:
    ``layers_held`` are the layers of this pipeline stage (None: all),
    ``experts_held`` the ``(first, count)`` of each layer's routed experts on
    this expert-parallel rank (None: all), ``vocab_size`` the rows of the
    embedding and of the head held here. A layer is full-attention where its
    index divides by ``period``, else sliding; layer 0 has the dense MLP."""
    z = sizes
    layers = range(z["num_layers"]) if layers_held is None \
        else tuple(layers_held)
    first, count = experts_held or (0, z["experts"])
    experts = (("num_experts", z["experts"]), ("top_k", z["top_k"]),
               ("held", (int(first), int(count))),
               ("routed_scale", z["routed_scale"]),
               ("shared_dim", z["shared_width"]),
               ("implementation", _grouped_matmul_for(attention_impl)))

    def block(i):
        full = i % z["period"] == 0
        style = BlockStyle(
            num_kv_heads=z["kv_heads"], head_dim=z["head_dim"],
            out_gate=True, rms_eps=1e-6,
            rope=z["full_rope"] if full else z["sliding_rope"],
            window=0 if full else z["window"],
            mlp="swiglu" if i == 0 else "experts",
            experts=() if i == 0 else experts)
        return (i, z["full_heads"] if full else z["sliding_heads"],
                z["dense_width"] if i == 0 else z["expert_width"], style)

    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=z["hidden_size"], dtype=dtype,
        attention_impl=attention_impl, tie_embeddings=False, mesh=mesh,
        blocks=tuple(block(i) for i in layers))


@register_model("gpt_laguna_xs2")
def gpt_laguna_xs2(num_classes: int = 0, dtype=jnp.bfloat16, *,
                   vocab_size: int = 100_352, max_len: int = 4096,
                   layers_held=None, experts_held=None,
                   attention_impl: str = "auto", mesh=None):
    # Every width is the published one. num_classes and max_len are not
    # read (rotary positions have no table to size); they are accepted for
    # the registry's and CausalLmTask's sake.
    return _laguna(_LAGUNA_XS2, dtype, vocab_size, layers_held, experts_held,
                   attention_impl, mesh)


@register_model("gpt_laguna_tiny")
def gpt_laguna_tiny(num_classes: int = 0, dtype=jnp.float32, *,
                    vocab_size: int = 96, max_len: int = 32,
                    layers_held=None, experts_held=None,
                    attention_impl: str = "auto", mesh=None):
    return _laguna(_LAGUNA_TINY, dtype, vocab_size, layers_held, experts_held,
                   attention_impl, mesh)


# ZAYA1-8B as Zyphra published it (config.json, `model_type: zaya`, 8.4 B
# parameters, 0.76 B active): 40 layers of hidden size 2048, every one an
# attention sublayer and an expert sublayer and no dense MLP; attention
# inside a compressed, convolved latent (CCA) of 8 query heads over 2 K/V
# heads of 128, rotary positions on half of each head at theta 5e6; 16
# experts of width 2048, one a token, chosen by an MLP router of width 256
# that hands its state to the next layer's; a learned scale and bias on the
# residual stream and on each sublayer's result; RMSNorm 1e-5; a tied head
# over 262,272 tokens. benchmark/configs/zaya1_8b.json lists what the source
# leaves unsaid and how it was read.
_ZAYA1_8B = dict(
    hidden_size=2048, num_layers=40, head_dim=128, heads=8, kv_heads=2,
    latent_mix=(2, 2), experts=16, expert_width=2048, router_hidden=256,
    rope=Rope(theta=5_000_000.0, rotary_dim=64), rms_eps=1e-5)
# The same block at sizes a CPU test holds.
_ZAYA1_TINY = dict(
    hidden_size=64, num_layers=3, head_dim=16, heads=4, kv_heads=2,
    latent_mix=(2, 2), experts=8, expert_width=64, router_hidden=16,
    rope=Rope(theta=5_000_000.0, rotary_dim=8), rms_eps=1e-5)


def _zaya1(sizes, dtype, vocab_size, layers_held, experts_held,
           attention_impl, mesh=None):
    """The ``zaya`` decoder at ``sizes``, or one chip's share of it, told as
    :func:`_laguna` is: the layers of this pipeline stage, the ``(first,
    count)`` of each layer's experts on this rank, the vocabulary rows of
    the embedding, which is the head too."""
    z = sizes
    layers = range(z["num_layers"]) if layers_held is None \
        else tuple(layers_held)
    first, count = experts_held or (0, z["experts"])
    experts = (("num_experts", z["experts"]),
               ("held", (int(first), int(count))),
               ("implementation", _grouped_matmul_for(attention_impl)))
    router = (("hidden", z["router_hidden"]), ("rms_eps", z["rms_eps"]))

    def block(i):
        return (i, z["heads"], z["expert_width"], BlockStyle(
            num_kv_heads=z["kv_heads"], head_dim=z["head_dim"],
            rope=z["rope"], rms_eps=z["rms_eps"],
            latent_mix=z["latent_mix"], residual_scale=True,
            from_embedding=i == 0, mlp="experts", experts=experts,
            router=router))

    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=z["hidden_size"], dtype=dtype,
        attention_impl=attention_impl, tie_embeddings=True, mesh=mesh,
        blocks=tuple(block(i) for i in layers))


@register_model("gpt_zaya1_8b")
def gpt_zaya1_8b(num_classes: int = 0, dtype=jnp.bfloat16, *,
                 vocab_size: int = 262_272, max_len: int = 4096,
                 layers_held=None, experts_held=None,
                 attention_impl: str = "auto", mesh=None):
    # Every width is the published one; num_classes and max_len are not read,
    # as in gpt_laguna_xs2.
    return _zaya1(_ZAYA1_8B, dtype, vocab_size, layers_held, experts_held,
                  attention_impl, mesh)


@register_model("gpt_zaya1_tiny")
def gpt_zaya1_tiny(num_classes: int = 0, dtype=jnp.float32, *,
                   vocab_size: int = 96, max_len: int = 32,
                   layers_held=None, experts_held=None,
                   attention_impl: str = "auto", mesh=None):
    return _zaya1(_ZAYA1_TINY, dtype, vocab_size, layers_held, experts_held,
                  attention_impl, mesh)


# Mellum2-12B-A2.5B as JetBrains published it (config.json, `model_type:
# mellum`): 28 layers of hidden size 2304 in periods of three sliding-window
# layers (window 1024) and one full-attention layer, 32 query heads over 4
# K/V heads of 128, rotary positions on the whole head at theta 500,000 (the
# full layers with YaRN's frequencies: factor 16 over 8192 original
# positions), every MLP 64 experts of width 896, 8 a token by softmax scores
# normalised over the chosen, no shared expert, RMSNorm 1e-6, an untied head
# over 98,304 tokens. benchmark/configs/mellum2_12b.json lists what the
# source leaves unsaid and how it was read.
_MELLUM2_12B = dict(
    hidden_size=2304, num_layers=28, period=4, head_dim=128, heads=32,
    kv_heads=4, window=1024, experts=64, top_k=8, expert_width=896,
    full_rope=Rope(theta=500_000.0, yarn_factor=16.0, original_len=8192,
                   beta_fast=32.0, beta_slow=1.0,
                   attention_factor=1.2772588722239782),
    sliding_rope=Rope(theta=500_000.0))
# The same block at sizes a CPU test holds: sliding, sliding, sliding, full.
_MELLUM2_TINY = dict(
    hidden_size=64, num_layers=4, period=4, head_dim=16, heads=4,
    kv_heads=2, window=8, experts=16, top_k=4, expert_width=32,
    full_rope=Rope(theta=500_000.0, yarn_factor=4.0, original_len=16,
                   beta_fast=2.0, beta_slow=1.0,
                   attention_factor=1.1386294361119891),
    sliding_rope=Rope(theta=500_000.0))


def _mellum2(sizes, dtype, vocab_size, layers_held, experts_held,
             attention_impl, mesh):
    """The ``mellum`` decoder at ``sizes``, told as :func:`_laguna` is. A
    layer is full-attention where it ends a period (index 3, 7, ...), else
    sliding. ``experts_held`` are the experts the whole mesh holds (None:
    all); an ``expert`` axis divides them among its ranks
    (``models/moe.py:HeldExpertsMlp``)."""
    z = sizes
    layers = range(z["num_layers"]) if layers_held is None \
        else tuple(layers_held)
    first, count = experts_held or (0, z["experts"])
    experts = (("num_experts", z["experts"]),
               ("held", (int(first), int(count))),
               ("implementation", _grouped_matmul_for(attention_impl)))
    router = (("kind", "softmax_top_k"), ("top_k", z["top_k"]))

    def block(i):
        full = i % z["period"] == z["period"] - 1
        return (i, z["heads"], z["expert_width"], BlockStyle(
            num_kv_heads=z["kv_heads"], head_dim=z["head_dim"], rms_eps=1e-6,
            rope=z["full_rope"] if full else z["sliding_rope"],
            window=0 if full else z["window"], mlp="experts",
            experts=experts, router=router))

    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=z["hidden_size"], dtype=dtype,
        attention_impl=attention_impl, tie_embeddings=False, mesh=mesh,
        blocks=tuple(block(i) for i in layers))


@register_model("gpt_mellum2_12b")
def gpt_mellum2_12b(num_classes: int = 0, dtype=jnp.bfloat16, *,
                    vocab_size: int = 98_304, max_len: int = 8192,
                    layers_held=None, experts_held=None,
                    attention_impl: str = "auto", mesh=None):
    # Every width is the published one; num_classes and max_len are not read,
    # as in gpt_laguna_xs2.
    return _mellum2(_MELLUM2_12B, dtype, vocab_size, layers_held,
                    experts_held, attention_impl, mesh)


@register_model("gpt_mellum2_tiny")
def gpt_mellum2_tiny(num_classes: int = 0, dtype=jnp.float32, *,
                     vocab_size: int = 96, max_len: int = 32,
                     layers_held=None, experts_held=None,
                     attention_impl: str = "auto", mesh=None):
    return _mellum2(_MELLUM2_TINY, dtype, vocab_size, layers_held,
                    experts_held, attention_impl, mesh)


# granite-4.0-h-micro as IBM published it (config.json, `model_type:
# granitemoehybrid`, 3 B parameters, dense: `num_local_experts` 0): 40 layers
# of hidden size 2048 in periods of ten, nine with a Mamba-2 mixer (64 heads
# of 64, a state of 128, one group of B and C, a causal depthwise convolution
# of 4 taps with a bias over 4,352 channels, chunks of 256) and one (index 5
# of each ten) with attention of 32 query heads over 8 K/V heads of 64 with
# no positions at all and scores times 1/64; every layer a gated MLP of 8192;
# the embedding times 12, each sublayer's result times 0.22 into the stream,
# the logits over 8; RMSNorm 1e-5; a tied head over 100,352 tokens.
# benchmark/configs/granite4_h_micro.json lists what the source leaves unsaid
# and how it was read.
_GRANITE4_H_MICRO = dict(
    hidden_size=2048, num_layers=40, period=10, attention_at=5, heads=32,
    kv_heads=8, head_dim=64, attn_scale=0.015625, mlp_width=8192,
    ssm=dict(heads=64, head_dim=64, state=128, groups=1, conv_taps=4,
             chunk=256),
    residual_multiplier=0.22, embedding_multiplier=12.0, logits_scaling=8.0,
    rms_eps=1e-5)
# The same block at sizes a CPU test holds: mamba, attention, mamba, mamba,
# with chunks of 8 so that 32 positions cross three boundaries.
_GRANITE4_H_TINY = dict(
    hidden_size=64, num_layers=4, period=4, attention_at=1, heads=4,
    kv_heads=2, head_dim=16, attn_scale=0.0625, mlp_width=128,
    ssm=dict(heads=4, head_dim=32, state=16, groups=1, conv_taps=4, chunk=8),
    residual_multiplier=0.22, embedding_multiplier=12.0, logits_scaling=8.0,
    rms_eps=1e-5)


def _granite4_h(sizes, dtype, vocab_size, layers_held, remat_blocks,
                attention_impl, mesh=None):
    """The ``granitemoehybrid`` decoder at ``sizes`` without experts, or one
    chip's share of it, told as :func:`_laguna` is: the layers of this
    pipeline stage and the vocabulary rows of the embedding, which is the
    head too. A layer is attention where its index is ``attention_at`` into
    its period, else Mamba-2. ``remat_blocks`` recomputes every block in the
    backward pass, one at a time."""
    z = sizes
    layers = range(z["num_layers"]) if layers_held is None \
        else tuple(layers_held)
    ssm = tuple(z["ssm"].items())

    def block(i):
        attention = i % z["period"] == z["attention_at"]
        return (i, z["heads"], z["mlp_width"], BlockStyle(
            num_kv_heads=z["kv_heads"], head_dim=z["head_dim"],
            rms_eps=z["rms_eps"], attn_scale=z["attn_scale"],
            mixer="attention" if attention else "mamba2",
            ssm=() if attention else ssm,
            residual_multiplier=z["residual_multiplier"],
            remat=bool(remat_blocks)))

    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=z["hidden_size"], dtype=dtype,
        attention_impl=attention_impl, tie_embeddings=True, mesh=mesh,
        embedding_multiplier=z["embedding_multiplier"],
        logits_scaling=z["logits_scaling"],
        blocks=tuple(block(i) for i in layers))


@register_model("gpt_granite4_h_micro")
def gpt_granite4_h_micro(num_classes: int = 0, dtype=jnp.bfloat16, *,
                         vocab_size: int = 100_352, max_len: int = 8192,
                         layers_held=None, remat_blocks: bool = False,
                         attention_impl: str = "auto", mesh=None):
    # Every width is the published one; num_classes and max_len are not read,
    # as in gpt_laguna_xs2 (no layer has a table of positions to size).
    return _granite4_h(_GRANITE4_H_MICRO, dtype, vocab_size, layers_held,
                       remat_blocks, attention_impl, mesh)


@register_model("gpt_granite4_h_tiny")
def gpt_granite4_h_tiny(num_classes: int = 0, dtype=jnp.float32, *,
                        vocab_size: int = 96, max_len: int = 32,
                        layers_held=None, remat_blocks: bool = False,
                        attention_impl: str = "auto", mesh=None):
    return _granite4_h(_GRANITE4_H_TINY, dtype, vocab_size, layers_held,
                       remat_blocks, attention_impl, mesh)


# SDAR-30B-A3B-Chat as JetLM published it (config.json, `model_type:
# sdar_moe`, 30.5 B parameters, 3.3 B active): Qwen3-MoE's layer 48 times, of
# hidden size 2048, 32 query heads over 4 K/V heads of 128 with an RMSNorm on
# each head's q and k, rotary positions on the whole head at theta 1e6, every
# MLP 128 experts of width 768, 8 a token by softmax scores normalised over
# the chosen, no shared expert, RMSNorm 1e-6, an untied head over 151,936
# tokens. It is trained and run as a block-diffusion model: the model's call
# takes the layout (``TransformerCausalLm.__call__``), the noise and the loss
# are ``train/task.py:BlockDiffusionLmTask``'s. benchmark/configs/
# sdar_30b_a3b.json lists what the source leaves unsaid and how it was read.
_SDAR_30B_A3B = dict(
    hidden_size=2048, num_layers=48, head_dim=128, heads=32, kv_heads=4,
    experts=128, top_k=8, expert_width=768, rope=Rope(theta=1_000_000.0))
# The same block at sizes a CPU test holds.
_SDAR_TINY = dict(
    hidden_size=64, num_layers=2, head_dim=16, heads=4, kv_heads=2,
    experts=8, top_k=2, expert_width=32, rope=Rope(theta=1_000_000.0))


def _sdar(sizes, dtype, vocab_size, layers_held, experts_held, remat_blocks,
          attention_impl, mesh=None):
    """The ``sdar_moe`` decoder at ``sizes``, or one chip's share of it, told
    as :func:`_laguna` is; ``remat_blocks`` as :func:`_granite4_h`'s."""
    z = sizes
    layers = range(z["num_layers"]) if layers_held is None \
        else tuple(layers_held)
    first, count = experts_held or (0, z["experts"])
    experts = (("num_experts", z["experts"]),
               ("held", (int(first), int(count))),
               ("implementation", _grouped_matmul_for(attention_impl)))
    router = (("kind", "softmax_top_k"), ("top_k", z["top_k"]))
    style = BlockStyle(
        num_kv_heads=z["kv_heads"], head_dim=z["head_dim"], rms_eps=1e-6,
        rope=z["rope"], qk_norm=True, mlp="experts", experts=experts,
        router=router, remat=bool(remat_blocks))
    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=z["hidden_size"], dtype=dtype,
        attention_impl=attention_impl, tie_embeddings=False, mesh=mesh,
        blocks=tuple((i, z["heads"], z["expert_width"], style)
                     for i in layers))


@register_model("gpt_sdar_30b_a3b")
def gpt_sdar_30b_a3b(num_classes: int = 0, dtype=jnp.bfloat16, *,
                     vocab_size: int = 151_936, max_len: int = 8192,
                     layers_held=None, experts_held=None,
                     remat_blocks: bool = False,
                     attention_impl: str = "auto", mesh=None):
    # Every width is the published one; num_classes and max_len are not read,
    # as in gpt_laguna_xs2.
    return _sdar(_SDAR_30B_A3B, dtype, vocab_size, layers_held, experts_held,
                 remat_blocks, attention_impl, mesh)


@register_model("gpt_sdar_tiny")
def gpt_sdar_tiny(num_classes: int = 0, dtype=jnp.float32, *,
                  vocab_size: int = 96, max_len: int = 64,
                  layers_held=None, experts_held=None,
                  remat_blocks: bool = False,
                  attention_impl: str = "auto", mesh=None):
    return _sdar(_SDAR_TINY, dtype, vocab_size, layers_held, experts_held,
                 remat_blocks, attention_impl, mesh)


# Keye-VL-2.0-30B-A3B's language model as Kwai-Keye published it (config.json,
# `model_type: KeyeVL2`): Qwen3-MoE's layer 48 times, of hidden size 2048, 32
# query heads over 4 K/V heads of 128 with an RMSNorm on each head's q and k,
# every MLP 128 experts of width 768, 8 a token by softmax scores normalised
# over the chosen, RMSNorm 1e-6, an untied head over 151,936 tokens; and on
# every attention layer `sa_config`: an indexer of 16 heads of 64 over one
# index key a token, each row keeping its 2048 best keys
# (``BlockStyle.indexer``); rotary positions at theta 1e7 in three sections
# of 16, 24 and 24 frequency pairs (``Rope.sections``). The vision tower is
# not here: its widths are not in the repository. benchmark/configs/
# keye_vl2_30b_a3b.json lists what the source leaves unsaid and how it was
# read.
_KEYE_VL2_30B_A3B = dict(
    hidden_size=2048, num_layers=48, head_dim=128, heads=32, kv_heads=4,
    experts=128, top_k=8, expert_width=768,
    rope=Rope(theta=10_000_000.0, sections=(16, 24, 24)),
    indexer=(("heads", 16), ("head_dim", 64), ("topk", 2048)))
# The same block at sizes a CPU test holds: a row of 64 keeps 16.
_KEYE_TINY = dict(
    hidden_size=64, num_layers=2, head_dim=16, heads=4, kv_heads=2,
    experts=8, top_k=2, expert_width=32,
    rope=Rope(theta=10_000_000.0, sections=(2, 3, 3)),
    indexer=(("heads", 2), ("head_dim", 8), ("topk", 16)))


def _keye(sizes, dtype, vocab_size, layers_held, experts_held, remat_blocks,
          attention_impl, mesh=None):
    """The ``KeyeVL2`` language model at ``sizes``, or one chip's share of
    it, told as :func:`_sdar` is."""
    z = sizes
    layers = range(z["num_layers"]) if layers_held is None \
        else tuple(layers_held)
    first, count = experts_held or (0, z["experts"])
    experts = (("num_experts", z["experts"]),
               ("held", (int(first), int(count))),
               ("implementation", _grouped_matmul_for(attention_impl)))
    router = (("kind", "softmax_top_k"), ("top_k", z["top_k"]))
    style = BlockStyle(
        num_kv_heads=z["kv_heads"], head_dim=z["head_dim"], rms_eps=1e-6,
        rope=z["rope"], qk_norm=True, mlp="experts", experts=experts,
        router=router, remat=bool(remat_blocks), indexer=z["indexer"])
    return TransformerCausalLm(
        vocab_size=vocab_size, hidden_size=z["hidden_size"], dtype=dtype,
        attention_impl=attention_impl, tie_embeddings=False, mesh=mesh,
        blocks=tuple((i, z["heads"], z["expert_width"], style)
                     for i in layers))


@register_model("gpt_keye_vl2_30b_a3b")
def gpt_keye_vl2_30b_a3b(num_classes: int = 0, dtype=jnp.bfloat16, *,
                         vocab_size: int = 151_936, max_len: int = 16_384,
                         layers_held=None, experts_held=None,
                         remat_blocks: bool = False,
                         attention_impl: str = "auto", mesh=None):
    # Every width is the published one; num_classes and max_len are not read,
    # as in gpt_sdar_30b_a3b.
    return _keye(_KEYE_VL2_30B_A3B, dtype, vocab_size, layers_held,
                 experts_held, remat_blocks, attention_impl, mesh)


@register_model("gpt_keye_tiny")
def gpt_keye_tiny(num_classes: int = 0, dtype=jnp.float32, *,
                  vocab_size: int = 96, max_len: int = 64,
                  layers_held=None, experts_held=None,
                  remat_blocks: bool = False,
                  attention_impl: str = "auto", mesh=None):
    return _keye(_KEYE_TINY, dtype, vocab_size, layers_held, experts_held,
                 remat_blocks, attention_impl, mesh)
