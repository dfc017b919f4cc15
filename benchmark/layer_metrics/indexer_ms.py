"""``indexer_ms`` (model code): device time a step under the scopes
``indexer_proj`` (the indexer's three projections, the index key's LayerNorm
and the rotary turns) and ``indexer_scores``, forward, recomputed and
backward (``harness/scopes.py``). On the kernel path the scores are formed
inside the selection's kernel (``index_select_ms``) and ``indexer_scores``
holds nothing. Left out where the program has no such scope."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bindexer_(proj|scores)\b")
