"""``moe_exchange_ms`` (collectives): device time a step on device 0 under
the expert layers' two exchange scopes, forward and backward:
``moe_exchange_in`` (the gather of the ranks' tokens, choices and weights; its
transpose, the scatter-sum of the tokens' cotangents) and ``moe_exchange_out``
(the float32 scatter-sum of the ranks' parts; its transpose, the gather of
the cotangents), through ``harness/scopes.py``. ``blocks_ms`` holds it too,
under ``mlp``. Left out where the program has no such scope."""
from harness.scopes import ms_per_step

PARTS = ("in", "out")


def read(ctx):
    value = ms_per_step(ctx, r"\bmoe_exchange_(in|out)\b")
    if value is not None:
        ctx["say"]("moe_exchange_ms by scope, ms a step: " + ", ".join(
            f"moe_exchange_{part} "
            f"{ms_per_step(ctx, rf'moe_exchange_{part}\b') or 0.0:.2f}"
            for part in PARTS))
    return value
