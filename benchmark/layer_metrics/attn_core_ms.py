"""``attn_core_ms`` (kernels): device time a step in every flash kernel of
the step, forward and both backward ones, windowed and full alike, from the
device trace (``harness/scopes.py``). Its first reading also prints how the
cell's own sections close on the busy time: the sections of
``harness/sections.py`` with the flash kernels, of which ``moe_ms`` is a part
of ``mlp``."""
from harness import scopes

FLASH = r"core_attention/flash_(fwd|bwd_dkdv|bwd_dq)\b"


def read(ctx):
    value = scopes.ms_per_step(ctx, FLASH)
    if value is None:
        return None
    ops, trace = scopes.scoped_ops(ctx), ctx["trace"]
    steps = ctx["run"]["steps"]
    total = 1e3 * sum(s for _, s in ops) / steps
    busy = 1e3 * sum(e - s for s, e in trace.busy_intervals(
        0, ctx["window"])) / 1e9 / steps
    moe = scopes.ms_per_step(ctx, r"\bmoe_\w+") or 0.0
    ctx["say"]("scopes beside the kernels under self_attn, ms a step: "
               + ", ".join(f"{name} {scopes.ms_per_step(ctx, rx) or 0.0:.2f}"
                           for name, rx in (("rope", r"/rope\b"),
                                            ("attn_gate", r"/attn_gate\b"))))
    ctx["say"](f"scopes: the flash kernels {value:.2f} + the expert layers "
               f"{moe:.2f} + everything else {total - value - moe:.2f} = "
               f"{total:.2f} ms a step against {busy:.2f} busy "
               f"({100 * (total / busy - 1):+.2f} %)")
    return value
