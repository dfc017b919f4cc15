"""``index_select_ms`` (kernels): device time a step under the scope
``indexer_select``: the selection's kernel (a tile of rows' index scores and
their thresholds, the packed mask) and what pads and slices around it
(``harness/scopes.py``). Left out where the program has no such scope."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bindexer_select\b")
