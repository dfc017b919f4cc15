"""``dispatch_ms`` (train loop): median duration of the program's span
``train.dispatch`` inside the traced window: the host's cost of handing one
compiled step to the device (``harness/program_spans.py``)."""
from harness.program_spans import median_ms


def read(ctx):
    return median_ms(ctx, "train.dispatch")
