"""``cca_mix_ms`` (model code): device time a step under the scope
``cca_mix`` of ``MultiHeadAttention``, forward and backward: what an
attention in a compressed, convolved latent does between its projections
and its rotary positions (the two causal convolutions along the sequence,
the q-k mean, the L2 norm and its temperature), from the device trace
(``harness/scopes.py``). The section ``attn_proj`` of ``blocks_ms`` holds it
too. A program without the scope leaves the metric out."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bcca_mix\b")
