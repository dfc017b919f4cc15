"""TPU kernels (Pallas) and their reference implementations.

The reference stack's kernel layer was cuDNN + framework CUDA kernels under
MXNet/TF (SURVEY.md §3.3); on TPU nearly all of it is XLA codegen, so the
in-tree kernel surface is deliberately small: fused (flash) attention for
the BERT/NMT workloads, and a ring-attention collective kernel pattern for
sequence-parallel long-context — the one place hand-scheduling beats the
compiler. Every kernel has a pure-jnp reference implementation that is the
numerics oracle in tests and the fallback on non-TPU backends.
"""

from .attention import BlockDiffusion, attention_reference, fused_attention
from .ring_attention import ring_attention, ring_attention_sharded
from .ulysses import ulysses_attention, ulysses_attention_sharded

__all__ = [
    "BlockDiffusion",
    "attention_reference",
    "fused_attention",
    "ring_attention",
    "ring_attention_sharded",
    "ulysses_attention",
    "ulysses_attention_sharded",
]
