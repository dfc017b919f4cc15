"""``step_ms`` (train loop): median over the window's steps of the host-clock
time between two ``fit`` hook calls that each end in ``block_until_ready``."""
from harness.stats import median


def read(ctx):
    steps = ctx["run"].get("step_s")
    return 1e3 * median(steps) if steps else None
