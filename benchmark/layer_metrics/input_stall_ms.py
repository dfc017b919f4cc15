"""``input_stall_ms`` (input): median duration of the program's span
``train.next_batch`` inside the traced window: what the step loop waited
for a batch, prefetcher and all (``harness/program_spans.py``)."""
from harness.program_spans import median_ms


def read(ctx):
    return median_ms(ctx, "train.next_batch")
