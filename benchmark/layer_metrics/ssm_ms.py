"""``ssm_ms`` (model code): device time a step under a state-space mixer's
five scopes (``ssm_in_proj``, ``ssm_conv``, ``ssm_scan``, ``ssm_gate_norm``,
``ssm_out_proj`` of ``models/ssm.py:Mamba2Mixer``), forward, recomputed and
backward, from the device trace (``harness/scopes.py``); it prints the five
apart. The mixer is the block's ``self_attn`` sublayer, so the section
``attn_proj`` of ``blocks_ms`` holds it too. A program without the scopes
leaves the metric out."""
from harness.scopes import ms_per_step

PARTS = ("in_proj", "conv", "scan", "gate_norm", "out_proj")


def read(ctx):
    parts = {p: ms_per_step(ctx, rf"\bssm_{p}\b") for p in PARTS}
    found = {p: ms for p, ms in parts.items() if ms is not None}
    if not found:
        return None
    ctx["say"]("ssm_ms, ms a step by scope: "
               + ", ".join(f"{p} {ms:.2f}" for p, ms in found.items()))
    return sum(found.values())
