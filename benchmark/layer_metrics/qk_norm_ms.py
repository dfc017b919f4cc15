"""``qk_norm_ms`` (model code): device time a step under the scope
``qk_norm``: the RMSNorm over each head's channels on q and on k, forward,
recomputed and backward (``harness/scopes.py``). Left out where the program
has no such scope."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bqk_norm\b")
