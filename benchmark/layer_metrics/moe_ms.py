"""``moe_ms`` (model code): device time a step under the expert layer's five
scopes, forward and backward: ``moe_router``, ``moe_dispatch`` (the sort and
the gather), ``moe_experts`` (the grouped matmuls), ``moe_shared``,
``moe_combine``, from the device trace (``harness/scopes.py``). The section
``mlp`` of ``blocks_ms`` holds it too. The parts go on a printed line."""
from harness.scopes import ms_per_step

PARTS = ("router", "dispatch", "experts", "shared", "combine")


def read(ctx):
    value = ms_per_step(ctx, rf"\bmoe_({'|'.join(PARTS)})\b")
    if value is not None:
        ctx["say"]("moe_ms by scope, ms a step: " + ", ".join(
            f"moe_{part} {ms_per_step(ctx, rf'moe_{part}\b') or 0.0:.2f}"
            for part in PARTS))
    return value
