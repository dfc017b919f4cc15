"""``ssm_scan_ms`` (kernels): device time a step under the scope
``ssm_scan`` alone: the step sizes' softplus, the chunked recurrence of
``ops/ssd.py`` and ``D``'s skip, forward, recomputed and backward
(``harness/scopes.py``). ``ssm_ms`` holds it."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bssm_scan\b")
