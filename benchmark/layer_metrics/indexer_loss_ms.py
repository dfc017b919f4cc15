"""``indexer_loss_ms`` (kernels): device time a step under the scope
``indexer_loss``: the loss's pass by tiles (the attention's distribution over
the kept keys again, the KL and its gradient in the indexer's operands) and
the backward rule's scaling (``harness/scopes.py``). Left out where the
program has no such scope."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bindexer_loss\b")
