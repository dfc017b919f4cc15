"""A kernel's share of its roofline: the least time the chip's published
peaks allow for the operations and bytes the call needs, over the kernel's
summed device time in the trace."""

from __future__ import annotations

from . import opcount


def flash_roofline(ctx, backward: bool):
    """Percent for the attention forward kernel, or for the pair of backward
    kernels together. ``None`` (the metric is then left out, never 0) where
    the compiled step holds no Pallas kernel — the XLA path ran — or where
    there is no trace to read."""
    trace, peaks = ctx["trace"], ctx["peaks"]
    name = "flash_bwd_roofline" if backward else "flash_fwd_roofline"
    if trace is None or peaks is None:
        return None
    calls = [k for k in ctx["run"].get("pallas_calls", [])
             if k["backward"] == backward and k["shape"]]
    if not calls:
        ctx["say"](f"{name}: no tpu_custom_call in the compiled step, the "
                   f"XLA attention path ran")
        return None
    seconds, events = trace.events_named(
        {k["name"] for k in calls}, 0, ctx["window"])
    if not events:
        ctx["say"](f"{name}: the trace shows none of "
                   f"{[k['name'] for k in calls][:3]}")
        return None
    b, h, s, d = calls[0]["shape"]
    count = opcount.flash_backward if backward else opcount.flash_forward
    flops, nbytes = count(b, h, s, s, d, causal=True)
    least, bound = opcount.roofline_seconds(flops, nbytes, peaks)
    # One attention call is one forward kernel, or one pair of backward ones.
    n_calls = events / (2 if backward else 1)
    ctx["say"](f"{name}: {events} kernel events, {seconds:.4f} s on device 0,"
               f" {bound}-bound, least {least * 1e3:.3f} ms a call at "
               f"[{b},{h},{s},{d}]")
    return 100.0 * least * n_calls / seconds
