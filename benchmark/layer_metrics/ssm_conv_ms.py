"""``ssm_conv_ms`` (kernels): device time a step under the scope
``ssm_conv``: the mixer's causal depthwise convolution, its bias and its
silu, forward, recomputed and backward (``harness/scopes.py``). ``ssm_ms``
holds it."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bssm_conv\b")
