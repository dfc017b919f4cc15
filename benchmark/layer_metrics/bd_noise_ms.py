"""``bd_noise_ms`` (model code): device time a step under the scope
``bd_noise``: the block-diffusion step's draw of a rate and a mask, the
``[noised | clean]`` input it lays out and the slice of the noised copy
before the head, forward and backward (``harness/scopes.py``). Left out
where the program has no such scope."""
from harness.scopes import ms_per_step


def read(ctx):
    return ms_per_step(ctx, r"\bbd_noise\b")
