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

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import register_model
from .moe import MOE_PARAM_RULES
from .transformer import (
    MoeAuxAccumulator,
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
    and thread it through the loop)."""

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

    def _is_moe(self, i: int) -> bool:
        return is_moe_layer(i, self.num_experts, self.moe_every)

    def setup(self):
        self.token = nn.Embed(self.vocab_size, self.hidden_size,
                              param_dtype=jnp.float32,
                              embedding_init=nn.initializers.normal(0.02))
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
                moe_top_k=self.moe_top_k,
                name=f"layer_{i}")
            for i in range(self.num_layers)
        ]
        self.final_norm = nn.LayerNorm(dtype=self.dtype,
                                       param_dtype=jnp.float32)

    def _embed(self, tokens, pos_emb, train: bool):
        x = self.token(tokens) + pos_emb
        x = self.embed_norm(x.astype(self.dtype))
        if self.dropout_rate > 0:
            x = self.dropout(x, deterministic=not train)
        return x

    def __call__(self, tokens, train: bool = False):
        x = self._embed(tokens,
                        self.position[None, :tokens.shape[1], :], train)
        acc = MoeAuxAccumulator()
        for i, lyr in enumerate(self.layers):
            if self._is_moe(i):
                x, aux = lyr(x, causal=True, deterministic=not train)
                acc.add(aux)
            else:
                x = lyr(x, causal=True, deterministic=not train)
        x = self.final_norm(x)
        # A scope of the program's own (docs/OBSERVABILITY.md): flax names
        # the head and the embedding lookup alike, after the module `token`.
        with jax.named_scope("lm_head"):
            logits = self.token.attend(x.astype(jnp.float32))
        if self.num_experts > 0:
            return logits, acc.mean()
        return logits

    def decode_step(self, token, pos):
        """``token`` [B, 1] at position ``pos`` → logits [B, 1, V] for
        position ``pos + 1``, appending this position's K/V to the
        cache. MoE aux losses are a training concern; decode discards
        them."""
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
