"""``input_wait_ms`` (input): median per batch of the time the benchmark's
wrapping iterator spent inside ``next()`` of the program's pipeline."""
from harness.stats import median


def read(ctx):
    waits = ctx["run"].get("input_wait_s")
    return 1e3 * median(waits) if waits else None
